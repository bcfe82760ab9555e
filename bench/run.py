#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload qwen3-1.7b.coded-kill --seed 7 --seconds 51 --trace 0

The cell comes from ``BENCHMARK.json``; its configuration, traffic, driver,
per-layer readers and limits are files under ``bench/`` found by name (see
``bench/harness.py``). Without a TPU, or with fewer chips than the cell asks
for, the command exits 2 and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number that decides
``correct`` beside its limit. The same numbers end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the raw profile of a --trace 1 run into DIR")
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.harness import HarnessError, run_cell

    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START, keep_trace=args.keep_trace)
    except HarnessError as e:
        print(f"[bench] cannot run: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:  # the boundary: report the failure, print no result
        traceback.print_exc()
        return 1
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
