#!/usr/bin/env python3
"""Is a refactoring of the serving families one to the bit? For deepseek,
brumby, granite and kimi at their tiny presets, in bfloat16 and in float32:
a digest of every leaf of `init_params`' tree (two seeds), the logits of
three chunk steps (mixed lengths, an inactive slot, a zero-length one, one
slot decoding along) and of eight decode steps, and the cache they leave, on
the CPU backend. Run it on two checkouts and compare:

    git archive <parent> | tar -x -C .scratch/parent
    JAX_PLATFORMS=cpu python benchmarks/serving_family_bits.py .scratch/parent .scratch/parent.npz
    JAX_PLATFORMS=cpu python benchmarks/serving_family_bits.py . .scratch/new.npz
    python benchmarks/serving_family_bits.py --cmp .scratch/parent.npz .scratch/new.npz

(PR 43: 512 arrays, 0 differ.) `FAMS=granite,kimi` runs some of them."""
import hashlib
import importlib
import os
import sys

import numpy as np

FAMILIES = {"deepseek": ("DeepseekConfig", "deepseek-tiny"),
            "brumby": ("BrumbyConfig", "brumby-tiny"),
            "granite": ("GraniteConfig", "granite-tiny"),
            "kimi": ("KimiConfig", "kimi-tiny")}


def compare(first: str, second: str) -> int:
    a, b = np.load(first), np.load(second)
    bad = 0
    for k in b.files:
        same = a[k].shape == b[k].shape and a[k].tobytes() == b[k].tobytes()
        if not same:
            bad += 1
            print("DIFFERS", k)
    print(f"{len(b.files)} arrays, {bad} differ")
    return 1 if bad else 0


def run(root: str, out_path: str) -> None:
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp

    out = {}
    for name, (cls, preset) in FAMILIES.items():
        if name not in os.environ.get("FAMS", name):
            continue
        mod = importlib.import_module(f"ray_tpu.models.{name}")
        assert mod.__file__.startswith(root), mod.__file__
        f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
        for tag, extra in (("bf16", {}), ("f32", f32)):
            cfg = getattr(mod, cls).preset(preset, **extra)
            for seed in (0, 7):
                params = mod.init_params(jax.random.key(seed), cfg)
                for path, leaf in jax.tree_util.tree_leaves_with_path(params):
                    arr = np.asarray(leaf.astype(jnp.float32))
                    key = f"{name}/{tag}/s{seed}/params" \
                        + jax.tree_util.keystr(path)
                    out[key] = np.frombuffer(
                        hashlib.sha256(arr.tobytes()).digest(), np.uint8)
            B, C, T = 5, 16, 96
            cache = mod.init_cache(cfg, B, T)
            chunk = jax.jit(lambda p, c, t, p0, n, a: mod.prefill_chunk(
                p, c, t, p0, n, a, cfg), donate_argnums=(1,))
            step = jax.jit(lambda p, c, t, pos, a: mod.decode_step(
                p, c, t, pos, a, cfg), donate_argnums=(1,))
            rng = np.random.default_rng(3)
            pos = np.zeros(B, np.int32)
            active = np.array([1, 1, 0, 1, 1], bool)
            plans = [[16, 16, 5, 1, 0], [16, 3, 0, 1, 9], [7, 0, 0, 16, 1]]
            for i, lengths in enumerate(plans):
                tokens = rng.integers(0, cfg.vocab_size, (B, C)).astype(
                    np.int32)
                n = np.array(lengths, np.int32)
                logits, cache = chunk(params, cache, jnp.asarray(tokens),
                                      jnp.asarray(pos), jnp.asarray(n),
                                      jnp.asarray(active))
                out[f"{name}/{tag}/chunk{i}"] = np.asarray(logits)[
                    (n > 0) & active]
                pos = pos + np.where(active, n, 0)
            for i in range(8):
                tokens = rng.integers(0, cfg.vocab_size, (B,)).astype(np.int32)
                logits, cache = step(params, cache, jnp.asarray(tokens),
                                     jnp.asarray(pos), jnp.asarray(active))
                out[f"{name}/{tag}/decode{i}"] = np.asarray(logits)[active]
                pos = pos + active
            for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
                out[f"{name}/{tag}/cache{jax.tree_util.keystr(path)}"] = \
                    np.asarray(leaf.astype(jnp.float32))
    np.savez(out_path, **out)
    print("wrote", out_path, len(out))


if __name__ == "__main__":
    if sys.argv[1] == "--cmp":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    run(sys.argv[1], sys.argv[2])
