"""Share of the inter-buffer's lookups that missed, over the GCDIA tasks of
the traced window: misses / (hits + misses), from each task's own counters.
A miss share rather than a hit share, so that a store that keeps nothing
reads 1 and a later reading has a base to be compared with."""


def read(run):
    c = [r["interbuffer"] for r in run.records
         if r["kind"] == "analyze" and "interbuffer" in r]
    misses = sum(x["misses"] for x in c)
    looked = misses + sum(x["hits"] for x in c)
    return misses / looked if looked else None
