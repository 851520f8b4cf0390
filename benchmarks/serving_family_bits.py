#!/usr/bin/env python3
"""Is a refactoring of the serving families one to the bit? For deepseek,
brumby, granite, kimi, keye, solar, nemotron and longcat (GPT-2's programs
take other arguments and have their own tests) at their tiny presets, in
bfloat16 and in float32:
a digest of every leaf of `init_params`' tree (two seeds), the logits of
three chunk steps (mixed lengths, an inactive slot, a zero-length one, one
slot decoding along) and of eight decode steps, and the cache they leave, on
the CPU backend. Run it on two checkouts and compare:

    git archive <parent> | tar -x -C .scratch/parent
    JAX_PLATFORMS=cpu python benchmarks/serving_family_bits.py .scratch/parent .scratch/parent.npz
    JAX_PLATFORMS=cpu python benchmarks/serving_family_bits.py . .scratch/new.npz
    python benchmarks/serving_family_bits.py --cmp .scratch/parent.npz .scratch/new.npz

(PR 43: 512 arrays, 0 differ, four families; PR 57: 994, eight.)
`FAMS=granite,kimi` runs some of them.

`--kernels <checkout> <out.npz>` in place of the two writing runs: the six
one-token decode kernels of `ray_tpu/ops/` through their public functions,
interpreted, at the shapes their tests use: live and dead slots, whole and
ragged last blocks at the kernels' own block, both q dtypes of
`gqa_attend`; an active and an inactive slot and the layer not named of
the three state kernels, one and eight groups of `ssm_update`, 2 and 64
heads of `kda_update`. A PR that changes one kernel shows with it which it
left alone (PR 57: 28 arrays, 0 differ; PR 63: 30 with `gqa_attend`'s
leaves by the lane at 1,024 and 8,192 positions, of which the longer
differs, as it should)."""
import hashlib
import importlib
import os
import sys

import numpy as np

FAMILIES = {"deepseek": ("DeepseekConfig", "deepseek-tiny"),
            "brumby": ("BrumbyConfig", "brumby-tiny"),
            "granite": ("GraniteConfig", "granite-tiny"),
            "kimi": ("KimiConfig", "kimi-tiny"),
            "keye": ("KeyeConfig", "keye-tiny"),
            "solar": ("KimiConfig", "solar-tiny"),
            "nemotron": ("NemotronConfig", "nemotron-tiny"),
            "longcat": ("LongcatConfig", "longcat-tiny")}


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


def kernels(root: str, out_path: str) -> None:
    root = os.path.abspath(root)
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp

    def op(name):
        mod = importlib.import_module(f"ray_tpu.ops.{name}")
        assert mod.__file__.startswith(root), mod.__file__
        return mod

    def normal(i, shape, dtype=jnp.float32):
        return jax.random.normal(jax.random.key(i), shape,
                                 jnp.float32).astype(dtype)

    out, bf = {}, jnp.bfloat16
    # 2,048: two whole blocks of 1,024; 2,200: no whole lane tiles divide
    # it, its third block hangs over the leaf's end
    for T in (2048, 2200):
        pos = jnp.asarray([300, T - 1, 0, 9, 1024, 1023], jnp.int32)
        live = jnp.asarray([False, True, False, False, True, True])
        B, L, layer, on = 6, 2, jnp.int32(1), np.asarray(live)
        got = op("mla_attend").mla_attend(
            normal(0, (B, 32, 512), bf), normal(1, (B, 32, 64), bf),
            normal(2, (L, B, T, 512), bf), normal(3, (L, B, T, 64), bf),
            layer, pos, live, 192 ** -0.5, interpret=True)
        out[f"mla_attend/T{T}"] = np.asarray(got)[on]
        G, R, d = 2, 4, 128
        for tag, dtype in (("f32", jnp.float32), ("bf16", bf)):
            got = op("gqa_attend").gqa_attend(
                normal(4, (B, G, R, d), dtype), normal(5, (L, B, G, T, d), bf),
                normal(6, (L, B, G, T, d), bf), layer, pos, live, d ** -0.5,
                interpret=True)
            out[f"gqa_attend/T{T}/q-{tag}"] = np.asarray(got)[on]
        keep = op("dsa_attend").rows_chosen(normal(7, (B, T)) + jnp.where(
            jnp.arange(T)[None] <= pos[:, None], 0.0, -1e9), 256,
            interpret=True)
        got = op("dsa_attend").dsa_attend(
            normal(8, (B, G, R, d), bf), normal(9, (L, B, T, G * d), bf),
            normal(10, (L, B, T, G * d), bf), layer, pos, live, keep,
            d ** -0.5, interpret=True)
        out[f"dsa_attend/T{T}"] = np.asarray(got)[on]
        for name in ("mla_attend", "gqa_attend"):
            out[f"{name}/T{T}/read_positions"] = np.asarray(
                op(name).read_positions(pos, live, T, interpret=True))
        out[f"dsa_attend/T{T}/read_positions"] = np.asarray(
            op("dsa_attend").read_positions(pos, live, T, 256,
                                            interpret=True))

    # leaves with the positions on the lanes (GPT-2's length and granite's:
    # the block follows the length since PR 63, so granite's sums go a block
    # of 512 at a time and differ from a parent's of 128 in the last bits)
    for T in (1024, 8192):
        pos = jnp.asarray([300, T - 1, 0, 9, 640], jnp.int32)
        live = jnp.asarray([False, True, True, False, True])
        got = op("gqa_attend").gqa_attend(
            normal(11, (5, 2, 4, 64), bf), normal(12, (2, 5, 2, 64, T), bf),
            normal(13, (2, 5, 2, 64, T), bf), jnp.int32(1), pos, live,
            0.125, interpret=True)
        out[f"gqa_attend/lanes/T{T}"] = np.asarray(got)[np.asarray(live)]

    def state_kernel(key, fn, state, *args):
        """The leaves whole (an inactive slot's and the other layer's among
        them) and the active slots' read-outs."""
        active = jnp.asarray([1, 0, 1][:state.shape[1]])
        got = jax.jit(lambda s: fn(s, jnp.int32(1), *args, active,
                                   interpret=True))(state)
        for i, leaf in enumerate(got):
            leaf = np.asarray(leaf)
            out[f"{key}/{i}"] = (leaf if leaf.shape[0] == state.shape[0]
                                 else leaf[np.asarray(active, bool)])

    pr = op("power_retention")
    B, H, R, d = 3, 2, 2, 128
    norm = normal(21, (2, B, H, pr.expanded_width(d)))
    state_kernel(
        "retention_update", lambda s, layer, *a, **how: pr.retention_update(
            s, norm, layer, *a, **how),
        normal(20, (2, B, H, d, pr.expanded_width(d))),
        normal(22, (B, H, R, d)), normal(23, (B, H, d)),
        normal(24, (B, H, d)), jax.nn.sigmoid(normal(25, (B, H)) + 4.0))
    for groups, N, F in ((1, 16, 256), (1, 128, 1024), (8, 128, 8192)):
        cols = (B, N) if groups == 1 else (B, groups, N)
        state_kernel(
            f"ssm_update/g{groups}-N{N}", op("ssm_update").ssm_update,
            normal(30, (2, B, N, F)), jax.nn.sigmoid(normal(31, (B, F)) + 2),
            normal(32, (B, F)), normal(33, cols), normal(34, cols))
    for B, H in ((3, 2), (2, 64)):
        N = 128
        state_kernel(
            f"kda_update/H{H}", op("kda_update").kda_update,
            normal(40, (2, B, H, N, N)),
            jax.nn.sigmoid(normal(41, (B, H, N)) + 3.0),
            normal(42, (B, H, N)) / N ** 0.5, normal(43, (B, H, N)) / N,
            normal(44, (B, H, N)), 2.0 * jax.nn.sigmoid(normal(45, (B, H))))
    np.savez(out_path, **out)
    print("wrote", out_path, len(out))


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
    if sys.argv[1] == "--kernels":
        sys.exit(kernels(sys.argv[2], sys.argv[3]))
    run(sys.argv[1], sys.argv[2])
