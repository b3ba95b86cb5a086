"""Share of the roofline of the GCDA kernels, in percent: the least time
the chip could take for the window's MULTIPLY, SIMILARITY and REGRESSION
work (``bench/roofline/work.py`` over ``peaks.json``), over the device
seconds of the programs that run them, from the trace."""
from bench.roofline import work
from bench.trace.reduce import module_seconds

# The jitted programs that run the three GCDA kernels, by the names the
# device trace gives their modules.
PROGRAMS = ("jit_matmul", "jit_cosine_sim", "jit__regression_loop")


def read(run):
    if run.trace is None:
        return None
    tasks = [r["gcda"] for r in run.records if "gcda" in r]
    device_s = module_seconds(run.trace, PROGRAMS)
    if not tasks or device_s <= 0:
        return None
    least = sum(work.roofline_s(*work.work(t["op"], t["m"], t["k"],
                                            t["iters"]), run.device_kind)
                for t in tasks)
    return 100.0 * least / device_s
