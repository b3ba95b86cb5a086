"""GCDI requests completed in the window over the window's seconds."""


def read(run):
    n = sum(1 for r in run.records if r["kind"] == "query")
    return n / run.window_s if n else None
