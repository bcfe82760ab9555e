#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's, over
many seeds in one process (one set-up), each judged by the cell's own limit.

    python3 bench/control.py --workload qwen3-1.7b.coded-kill --seconds 51 --seeds 1,2,3
    python3 bench/control.py --workload lcc-k3r1-ntt.kv-share-4chip --seeds 1,2,3

Serving cells: per seed, new weights, a window at the cell's own load
through the timed path, then the check's sample of served tokens read under
the float32 reference (the program's reading) and, at the same positions,
the tokens each control precision puts first read under the same reference
(the control's reading). Encode cells: per seed, the cell's packets, the
reference encode and the control (the same sums in float32); the words the
control gets wrong.

Each seed prints one line: the program's reading and each control's, each
with the limit and the ``correct`` a run would report with that reading in
the check. The command exits 1 unless the program is correct and every
control is not, on every seed. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def serve_readings(cell, config, traffic, limits, seeds, seconds, controls):
    from bench import harness
    from bench.drivers import serve

    prep = None
    ok = True
    for seed in seeds:
        ctx = harness.Ctx(cell, config, traffic, seed, seconds, False, limits)
        if prep is None:
            prep = serve.prepare(config, traffic, seed)
        else:
            serve.reseed(prep, config, seed)
        out = serve.measure(ctx, prep, controls=controls)
        limit = ctx.limit("max_logit_shortfall")
        row = {"seed": seed, "checked_tokens": out.facts["checked_tokens"],
               "program": judged(out, out.checks["max_logit_shortfall"].value, limit),
               **{f"control_{m}": judged(out, out.facts[f"control_{m}"], limit)
                  for m in controls},
               "checks": {k: c.value for k, c in out.checks.items()}}
        print(json.dumps(row), flush=True)
        ok &= row["program"]["correct"] and not any(
            row[f"control_{m}"]["correct"] for m in controls)
    return ok


def judged(out, shortfall: float, limit: float) -> dict:
    """A run's ``correct`` with ``shortfall`` as its served-token reading and
    every other check as the run read it."""
    from bench.harness import Check

    checks = dict(out.checks, max_logit_shortfall=Check(shortfall, limit))
    return {"value": shortfall, "limit": limit,
            "correct": out.failed == 0 and all(c.ok for c in checks.values())}


def encode_readings(config, limits, seeds):
    import numpy as np

    from bench.drivers.encode import packet_words
    from bench.references import lcc_encode

    limit = float(limits["encode_words_wrong"]["limit"])
    ok = True
    S = int(config.get("packet_words") or packet_words(config))
    K, N = int(config["K"]), int(config["N"])
    limb = 1 << int(config["payload"]["limb_bits"])
    for seed in seeds:
        x = np.zeros((N, S), np.uint32)
        x[:K] = np.random.default_rng([seed, 5]).integers(0, limb, size=(K, S), dtype=np.uint32)
        want = lcc_encode.encode(x, config)
        wrong = lcc_encode.words_wrong(lcc_encode.encode_control(x, config), want)
        control = {"value": wrong, "limit": limit, "correct": wrong <= limit}
        print(json.dumps({"seed": seed, "words": N * S, "control_float32": control}),
              flush=True)
        ok &= not control["correct"]
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_json(harness.BENCH / "configs" / f"{cell['config']}.json")
    traffic = harness.load_json(harness.BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = harness.load_json(harness.BENCH / "limits" / f"{args.workload}.json")
    seeds = [int(s) for s in args.seeds.split(",")]
    if traffic["driver"] == "encode":
        return 0 if encode_readings(config, limits, seeds) else 1
    try:
        harness.prepare_jax(1)
    except harness.HarnessError as e:
        print(f"[control] cannot run: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ok = serve_readings(cell, config, traffic, limits, seeds, args.seconds,
                        tuple(c for c in args.controls.split(",") if c))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
