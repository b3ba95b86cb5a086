"""The window's milliseconds over the GCDIA tasks completed in it; a task
has completed when its output is ready on the device."""


def read(run):
    n = sum(1 for r in run.records if r["kind"] == "analyze")
    return run.window_s * 1e3 / n if n else None
