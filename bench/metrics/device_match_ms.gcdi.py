"""Mean self time of ``DeviceMatchPattern`` over the GCDI requests that ran
one on the device: preparing the chain's tables, the program, and reading
its answer back."""


def read(run):
    v = [r["op_s"]["DeviceMatchPattern"] * 1e3 for r in run.records
         if r["kind"] == "query" and "DeviceMatchPattern" in r.get("op_s", {})]
    return sum(v) / len(v) if v else None
