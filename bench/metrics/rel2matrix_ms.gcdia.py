"""Mean self time per GCDIA task of the matrix builders (``Rel2Matrix`` and
``RandomAccessMatrix``), including the transfer to the device."""


def read(run):
    v = [sum(r["op_s"].get(op, 0.0) for op in ("Rel2Matrix",
                                                 "RandomAccessMatrix")) * 1e3
         for r in run.records if r["kind"] == "analyze" and "op_s" in r]
    return sum(v) / len(v) if v else None
