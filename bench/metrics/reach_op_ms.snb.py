"""Mean self time of ``DeviceReach`` over the GCDI requests that ran one on
the device: lowering the reach, staging its end mask, the BFS program and
reading the distances back into the relation."""


def read(run):
    v = [r["op_s"]["DeviceReach"] * 1e3 for r in run.records
         if r["kind"] == "query" and "DeviceReach" in r.get("op_s", {})]
    return sum(v) / len(v) if v else None
