"""Mean over the reach requests of the self time of the executed host
operators (every operator but ``DeviceReach``: the projection of IC1's
reached persons, and a host ``Reach`` where one ran)."""


def read(run):
    v = [sum(s for op, s in r["op_s"].items() if op != "DeviceReach") * 1e3
         for r in run.records if r["kind"] == "query" and "op_s" in r]
    return sum(v) / len(v) if v else None
