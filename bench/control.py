"""Readings that the limits in ``bench/limits/`` are set from.

    python bench/control.py --workload <name> --seconds <s> <seed> [<seed> ...]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(untraced), then the numbers ``check.py`` compares for the program's answers
and for the control's (``check.numbers(control=True)``, in the program's
place, on the same sampled requests). For a GCDI cell it also prints the
rows by which a float32 reference differs from the float64 one. Prints one
JSON line per seed. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    from bench import check, compile_cache, run
    _, w, _, _ = run.cell(args.workload)
    devices = run.require_chips(int(w["chips"]))
    log = compile_cache.CompileLog()
    log.install()
    for seed in args.seeds:
        m = run.measure(args.workload, seed, args.seconds, False,
                        devices=devices, log=log)
        kind = m.run.kind
        prog = check.numbers(kind, m.kept, m.raw, m.mix, m.failed, ref=m.ref)
        ctl = check.numbers(kind, m.kept, m.raw, m.mix, m.failed, ref=m.ref,
                            control=True)
        line = {"workload": args.workload, "seed": seed,
                "requests": len(m.run.records), "kept": len(m.kept),
                "program": prog, "control": ctl}
        if kind == "query":
            specs = {(e.template, e.index): e.spec for e, _, _ in m.kept}
            line["float32_rows_off"] = sum(
                m.ref.rows_off(m.ref.relation(m.raw, s, lower=True),
                               m.ref.relation(m.raw, s))
                for s in specs.values())
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = root
    sys.path.insert(1, os.path.join(root, "src"))
    sys.exit(main())
