"""Run the encode cell at a CPU size on 4 forced host devices, optionally
with a fault planted in the timed path; prints the result line. Started in
a child process by the tests (the device count is fixed at JAX start).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/encode_cpu.py [none|no_exchange|altered]
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))


def main(fault: str) -> int:
    import jax

    from bench.harness import run_cell
    from bench.tests.tiny import overrides

    if fault == "no_exchange":
        # the exchange between chips left out: every ppermute returns its input
        jax.lax.ppermute = lambda x, axis_name, perm: x
    elif fault == "altered":
        import repro.coded.lagrange_compute as lc

        real = lc.lcc_encode_collective

        def altered(*a, **kw):
            fn = real(*a, **kw)
            return lambda xp: fn(xp).at[0, 0].add(1)

        lc.lcc_encode_collective = altered
    line = run_cell("lcc-k3r1-ntt.kv-share-4chip", 2**31 + 5, 1.0, False,
                    require_tpu=False, overrides=overrides("encode"), cache=False)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "none"))
