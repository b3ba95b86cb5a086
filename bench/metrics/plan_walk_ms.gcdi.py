"""Mean over GCDI requests of the engine's time outside its operators:
plan, optimise, lower, and the executor's walk (``last_stats.seconds``
minus the executed operators' self times)."""


def read(run):
    v = [(r["seconds"] - sum(r["op_s"].values())) * 1e3
         for r in run.records if r["kind"] == "query" and "op_s" in r]
    return sum(v) / len(v) if v else None
