"""Device milliseconds of the reach's BFS program per GCDI request that ran
the device reach, from the trace. IC1 (one frontier row) and IC13 (two, one
from each end) run the same program, compiled once for each block height,
so every module the trace names after it counts."""
from bench.trace.reduce import module_seconds

# The jitted BFS program, by the name the device trace gives its module.
PROGRAMS = ("jit_reach_program",)


def read(run):
    if run.trace is None:
        return None
    n = sum(1 for r in run.records
            if r["kind"] == "query" and "DeviceReach" in r.get("op_s", {}))
    device_s = module_seconds(run.trace, PROGRAMS)
    if not n or device_s <= 0:
        return None
    return 1e3 * device_s / n
