#!/usr/bin/env python3
"""Once, on the chip, outside any window: the OLMoE program against its
reference at the published widths (the configuration's depth), on seeded
4,096-token sequences of the cell's own traffic.

- forward logits, program (bf16 activations, float32 router, the Pallas
  attention and grouped-matmul kernels) against reference (float32,
  `highest`): largest and mean gap, and the share of (token, slot) expert
  choices that differ;
- the three-term loss and the routing: program, reference, the reference
  computed in bfloat16 throughout (its float32 islands gone), and the
  reference with its weights rounded to float8_e4m3's three mantissa bits
  (the nearest format below the configuration's bfloat16), which the
  family's limits have to tell from the program;
- `float32_island_gaps` of the program's pass and of each reference's.

    python benchmarks/chip/rehearse/olmoe_on_chip.py [--seeds 1,2] \
        [--sequences 2]

Writes `chiprun_out/olmoe_on_chip.json`. One process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness import spec  # noqa: E402


def compare(config: dict, traffic: dict, seed: int, sequences: int) -> dict:
    from ray_tpu.models import moe

    family = spec.family(config["family"])
    model, job = config["model"], config["job"]
    weights = job["router_losses"]
    cfg = family.program_config(model, weights, remat=job["remat"])
    params = family.seeded_params(cfg, seed)
    rows = spec.generator(traffic["generator"]).generate(
        {**traffic, "dataset_batches": 1}, config, seed)[:sequences]
    tokens = jnp.asarray(rows)
    inputs = tokens[:, :-1]

    gaps_of = jax.jit(lambda seen: family.float32_island_gaps(seen, model))
    seen = jax.jit(lambda p, b: family.program_pass(p, b, cfg))(
        params, {"tokens": tokens})
    got_chosen = jax.device_get(seen["routing"]["experts"])
    got_gaps = jax.device_get(gaps_of(seen))
    del seen
    got = jax.device_get(jax.jit(lambda p, t: moe.forward(
        p, t, cfg).astype(jnp.float32))(params, inputs))
    loss, aux = jax.jit(lambda p, b: moe.loss_fn(p, b, cfg))(
        params, {"tokens": tokens})
    out = {"seed": seed, "sequences": int(len(rows)),
           "tokens": int(inputs.size), "choices": int(got_chosen.size),
           "program_loss": float(loss),
           "program_aux": {k: float(v) for k, v in aux.items()},
           "program_island_gaps": {k: float(v) for k, v in got_gaps.items()}}
    # float8_e4m3's 3 mantissa bits at bfloat16's range (what a scaled
    # float8 store keeps), by the operation the compiler keeps: it drops a
    # convert to float8 and back as a no-op (my chip run, PR 25)
    float8 = jax.jit(lambda p: jax.tree.map(lambda a: jax.lax.reduce_precision(
        a, exponent_bits=8, mantissa_bits=3), p))(params)
    want_chosen = None
    for name, weights_used, dtype in (
            ("reference", params, "float32"),
            ("reference_bfloat16", params, "bfloat16"),
            ("reference_float8_weights", float8, "float32")):
        def forward(p, t):
            logits, routing, _ = family.reference_forward(
                p, t[:, :-1], model, dtype)
            return (logits.astype(jnp.float32), routing["experts"],
                    family.sums_of(logits, routing, t, model))

        forward = jax.jit(forward)
        reference_pass = jax.jit(lambda p, t: family.reference_pass(
            p, t, model, dtype))
        total, chosen, gaps, island_gaps = None, [], [], []
        for at in range(len(rows)):             # one sequence at a time
            fwd = jax.device_get(forward(weights_used, tokens[at:at + 1]))
            sums = fwd[2]
            total = sums if total is None else {
                k: total[k] + sums[k] for k in sums}
            chosen.append(fwd[1])
            island_gaps.append({k: float(v) for k, v in jax.device_get(
                gaps_of(reference_pass(weights_used, tokens[at:at + 1]))
            ).items()})
            if name == "reference":
                gap = np.abs(got[at:at + 1] - fwd[0])
                gaps.append((float(gap.max()), float(gap.sum()),
                             float(np.abs(fwd[0]).max())))
        chosen = np.concatenate(chosen, axis=1)             # [L, B, T, K]
        out[name] = {k: float(v) for k, v in family.loss_from_sums(
            {k: np.asarray(v, np.float64) for k, v in total.items()},
            model, weights).items()}
        out[name + "_island_gaps"] = island_gaps
        if name == "reference":
            want_chosen = chosen
            out["logit_gap_max"] = max(g[0] for g in gaps)
            out["logit_gap_mean"] = sum(g[1] for g in gaps) / got.size
            out["logit_abs_max"] = max(g[2] for g in gaps)
            out["choices_differ_pct"] = family.choices_differ_pct(
                got_chosen, want_chosen)
        else:
            out[name + "_choices_differ_pct"] = family.choices_differ_pct(
                chosen, want_chosen)
    out["program_minus_reference"] = out["program_loss"] \
        - out["reference"]["loss"]
    for name in ("reference_bfloat16", "reference_float8_weights"):
        out[name + "_minus_reference"] = \
            out[name]["loss"] - out["reference"]["loss"]
    out["tolerance"] = family.TRAIN_LOSS_TOLERANCE
    out["routing_tolerance_pct"] = family.ROUTING_DIFFER_TOLERANCE_PCT
    out["float32_island_limits"] = family.FLOAT32_ISLAND_LIMITS
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="train-olmoe-4k")
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--sequences", type=int, default=2)
    args = ap.parse_args()
    cell = spec.cell(spec.benchmark(), args.workload)
    d = jax.devices()[0]
    results = {"device": {"platform": d.platform, "kind": d.device_kind},
               "runs": []}
    for seed in args.seeds.split(","):
        r = compare(cell["config"], cell["traffic"], int(seed),
                    args.sequences)
        print(json.dumps(r), flush=True)
        results["runs"].append(r)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "olmoe_on_chip.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
