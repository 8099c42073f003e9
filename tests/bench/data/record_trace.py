#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_harness.py`` reduces.

    python3 tests/bench/data/record_trace.py    # on a TPU; writes tick.xplane.pb

One ``tick`` host span holding a ``tick.research`` span around a jitted
while loop, and a gap of host work after it.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "tick.xplane.pb")


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jax.lax.fori_loop(
        0, 4000, lambda i, y: jnp.sin(y) @ y, x))
    x = jnp.ones((256, 256), jnp.float32) * 0.01
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("tick"):
        with jax.profiler.TraceAnnotation("tick.research"):
            f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())
