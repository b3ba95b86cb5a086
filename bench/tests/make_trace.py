"""Record the small trace that ``test_trace_reduce.py`` reads.

    python bench/tests/make_trace.py <out.xplane.pb>

On the chip: three annotated requests, each a jitted program and a host
sleep, so the trace has device operations, idle gaps with known host
annotations open, and one named module.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


@jax.jit
def step(x):
    return jnp.tanh(x @ x) + 1.0


def main(out: str) -> None:
    x = jnp.ones((1024, 1024), jnp.float32)
    step(x).block_until_ready()
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench:req{i}"):
            step(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
