#!/usr/bin/env python3
"""Record the small scoped chip trace that ``test_bench_program.py`` reduces.

    python3 tests/bench/scoped/record_trace.py   # on a TPU; writes tick.xplane.pb

It lives apart from ``tests/bench/data``, whose reduction test takes the one
trace found there.

One ``tick`` host span holding a ``svc.research`` span around a jitted
function whose loop runs under ``jax.named_scope("engine.score")``; its last
step, under ``engine.final``, fuses into the unscoped ``+ x``, so that
fusion takes its root's scope: none.  Then a ``svc.respond`` span of host
work alone (a gap of the device).  The profiler keeps the compiled HLO
(``enable_hlo_proto``), which names each op's scopes.
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


def step(x):
    with jax.named_scope("engine.score"):
        y = jax.lax.fori_loop(0, 200, lambda i, y: jnp.tanh(y @ y), x)
    with jax.named_scope("engine.final"):
        y = jnp.tanh(y) * 2.0
    return y + x


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(step)
    # A few ms of device work: longer than the ~1 ms by which the device
    # and host clocks of a trace can disagree.
    x = jnp.ones((1024, 1024), jnp.float32) * 0.01
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("tick"):
        with jax.profiler.TraceAnnotation("svc.research"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("svc.respond"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())
