#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py OUT_DIR     # on one TPU chip

Inside a ``bench.window`` annotation it runs, three times, a jitted matrix
product under a host span ``fixture.compute`` and then sleeps 20 ms under a
host span ``fixture.sleep``, so the device is idle in each sleep. The
profile lands in ``OUT_DIR/fixture.xplane.pb``.
"""

import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    f(a).block_until_ready()
    tdir = Path(out) / "fixture-trace"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("fixture.compute"):
                f(a).block_until_ready()
            with jax.profiler.TraceAnnotation("fixture.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    found = sorted(tdir.rglob("*.xplane.pb"))
    shutil.copy(found[-1], Path(out) / "fixture.xplane.pb")
    shutil.rmtree(tdir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
