"""Mean over GCDI requests of the self time of the executed host operators
(every operator but ``DeviceMatchPattern``)."""


def read(run):
    v = [sum(s for op, s in r["op_s"].items() if op != "DeviceMatchPattern")
         * 1e3 for r in run.records if r["kind"] == "query" and "op_s" in r]
    return sum(v) / len(v) if v else None
