"""Record the small trace of the engine's spans that ``test_trace_spans.py``
reads.

    PYTHONPATH=src python bench/tests/make_span_trace.py <out.xplane.pb>

On the chip: the M2Bench e-commerce data at sf 1, an engine with a
telemetry session that fences device work (as a traced benchmark run has
it), G3 run once to compile, then two G3 requests recorded, each in a
``bench:G3`` annotation. G3 runs its pattern on the device
(``DeviceMatchPattern`` with its four phases), so the trace holds the
engine's ``gredo:`` annotations and the chain program's device operations
on one clock.
"""
import glob
import os
import shutil
import sys
import tempfile

import jax

from repro.core import GredoEngine
from repro.core.telemetry import Telemetry
from repro.data import m2bench


def main(out: str) -> None:
    eng = GredoEngine(m2bench.generate(sf=1),
                      telemetry=Telemetry(fence_device=True))
    q = m2bench.q_g3()
    eng.query(q)
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench:G3"):
            eng.query(q)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
