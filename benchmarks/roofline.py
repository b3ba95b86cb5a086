"""Roofline analysis over the dry-run records (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), all in seconds-per-step per device:
    compute    = HLO_flops_per_device / PEAK_FLOPS
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_bytes_per_device / ICI_BW
(cost_analysis flops/bytes are per-partition in SPMD HLO; the collective
parser sums per-shard result bytes with while-loop multiplicity, 2x for
all-reduce ring cost.)

MODEL_FLOPS (useful work, global):
    LM train    6 * N_active * tokens        LM prefill  2 * N_active * tokens
    LM decode   2 * N_active * batch + 2 * kv_bytes/2 (attention reads)
    GNN train   6 * N_params * n_nodes  (convention; edge-dominated archs
                under-count — the ratio column carries the caveat)
    recsys      (6 if train else 2) * N_touched * batch

Usage:  PYTHONPATH=src python -m benchmarks.roofline [--dir experiments/dryrun]
        [--write experiments/roofline.md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PEAK_FLOPS = 197e12      # TPU v5e bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link


# ---------------------------------------------------------------------------
# Analytic per-device flops: the CPU backend lowers decode matvecs without
# `dot` ops and its cost_analysis counts while bodies once, so the honest
# TPU compute term is derived from the model configs. Components that are
# REPLICATED over the 'model' axis (attention when heads % tp != 0) divide
# by dp only; sharded components divide by all devices.
# ---------------------------------------------------------------------------


def _lm_analytic_flops_dev(arch: str, shape: str, mesh: str) -> float:
    from repro import configs
    cfg = configs.get(arch).config()
    spec = configs.get(arch).SHAPES[shape]
    n_dev = 512 if mesh.startswith("2x") else 256
    tp = 16
    dp_total = n_dev // tp
    B, S = spec["batch"], spec["seq"]
    kind = spec["kind"]
    d, h, kv, dh, f, V, L = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, cfg.d_ff, cfg.vocab, cfg.n_layers)
    n_mats = 3 if cfg.mlp == "swiglu" else 2

    tokens = B * (1 if kind == "decode" else S)
    s_kv = S if kind == "decode" else S / 2          # causal average
    # per-token per-layer flop components (x2 for MAC)
    qkvo = 2 * (2 * d * h * dh + 2 * d * kv * dh)
    attn = 4 * h * dh * s_kv
    if cfg.is_moe:
        mlp = 2 * n_mats * d * f * cfg.top_k * cfg.capacity_factor
    else:
        mlp = 2 * n_mats * d * f
    head_f = 2 * d * V

    mult = 4.0 if kind == "train" else 1.0           # fwd+bwd+remat-fwd
    heads_sharded = (h % tp == 0)
    experts_sharded = (not cfg.is_moe) or cfg.n_experts % tp == 0

    f_sharded = tokens * L * mlp * mult + tokens * head_f * mult
    f_attn = tokens * L * (qkvo + attn) * mult
    dev = f_sharded / (n_dev if experts_sharded else dp_total)
    dev += f_attn / (n_dev if heads_sharded else dp_total)
    return dev


def _gnn_analytic_flops_dev(arch: str, shape: str, mesh: str) -> float:
    from repro import configs
    mod = configs.get(arch)
    spec = mod.SHAPES[shape]
    n_dev = 512 if mesh.startswith("2x") else 256
    if spec["kind"] == "molecule":
        N = spec["batch"] * spec["n_nodes"]
        E = spec["batch"] * spec["n_edges"]
    else:
        N, E = spec["n_nodes"], spec["n_edges"]
    mult = 3.0  # fwd + bwd
    if arch == "gatedgcn":
        cfg = mod.config()
        d, L = 70, cfg.n_layers
        per = L * (5 * N * d * d * 2 + 8 * E * d)
    elif arch == "pna":
        cfg = mod.config()
        d, L = 75, cfg.n_layers
        per = L * (E * (2 * d * d + d * d) * 2 + N * 13 * d * d * 2)
    elif arch == "mace":
        cfg = mod.config()
        C, L = cfg.channels, cfg.n_layers
        paths = 15
        cg_edge = E * paths * 27 * C * 2             # A-basis CG x radial
        cg_node = 2 * N * paths * 27 * C * 2         # B2 + B3 products
        radial = E * (8 * 64 + 64 * paths * C) * 2
        mix = N * 3 * C * C * 2 * 9
        per = L * (cg_edge + cg_node + radial + mix)
    else:  # equiformer_v2
        cfg = mod.config()
        C, L, dim = cfg.channels, cfg.n_layers, (cfg.l_max + 1) ** 2
        wigner = 2 * E * dim * dim * C * 2           # rotate + unrotate
        so2 = E * sum((cfg.l_max + 1 - m) ** 2 * C * C * (2 if m else 1) * 2
                      for m in range(cfg.m_max + 1)) * 2
        ffn = N * (cfg.l_max + 1) * 9 * C * C * 2
        per = L * (wigner + so2 + E * 3 * C * C * 2 + ffn)
    return per * mult / n_dev


def _recsys_analytic_flops_dev(shape: str, mesh: str) -> float:
    from repro import configs
    spec = configs.get("wide_deep").SHAPES[shape]
    n_dev = 512 if mesh.startswith("2x") else 256
    B = spec["batch"]
    d_in = 40 * 32 + 13
    mlp = (d_in * 1024 + 1024 * 512 + 512 * 256 + 256) * 2
    mult = 3.0 if spec["kind"] == "train" else 1.0
    flops = B * mlp * mult
    if spec["kind"] == "retrieval":
        flops += spec["n_candidates"] * 256 * 2 + B * 256 * 256 * 2
    return flops / n_dev


def analytic_flops_dev(rec: dict) -> float:
    arch, shape, mesh = rec["arch"], rec["shape"], rec["mesh"]
    kind = rec.get("kind", "")
    try:
        if kind in ("train", "prefill", "decode"):
            return _lm_analytic_flops_dev(arch, shape, mesh)
        if kind == "gnn_train":
            return _gnn_analytic_flops_dev(arch, shape, mesh)
        if kind.startswith("recsys"):
            return _recsys_analytic_flops_dev(shape, mesh)
    except Exception:
        return 0.0
    return 0.0


def model_flops(rec: dict) -> float:
    meta = rec.get("meta", {})
    kind = rec.get("kind", "")
    if kind in ("train", "prefill", "decode"):
        n = meta["n_active"]
        toks = meta["tokens"]
        if kind == "train":
            return 6.0 * n * toks
        if kind == "prefill":
            return 2.0 * n * toks
        return 2.0 * n * toks  # decode: tokens == batch
    if kind == "gnn_train":
        return 6.0 * meta["n_params"] * meta["n_nodes"]
    if kind.startswith("recsys"):
        # embedding rows touched + dense mlp per example
        dense = meta["n_params"] - 40 * 1_000_000 * 32 - 1_000_000
        touched = 40 * 32 + max(dense, 0)
        mult = 6.0 if kind == "recsys_train" else 2.0
        return mult * touched * meta.get("batch", 1)
    return 0.0


def analyze(rec: dict) -> dict:
    n_dev = rec["n_devices"]
    # analytic compute term (CPU HLO hides matvec dots / loop trip counts);
    # dot_flops_per_device (trip-corrected HLO dots) kept as cross-check
    flops_dev = analytic_flops_dev(rec) or rec.get(
        "dot_flops_per_device", rec["flops_per_device"])
    bytes_dev = rec.get("hbm_bytes_per_device", rec["bytes_per_device"])
    coll_dev = rec["collectives"]["total_bytes"]
    t_c = flops_dev / PEAK_FLOPS
    t_m = bytes_dev / HBM_BW
    t_x = coll_dev / ICI_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    mf = model_flops(rec)
    hlo_global = flops_dev * n_dev
    bound = max(t_c, t_m, t_x)
    useful_t = (mf / n_dev) / PEAK_FLOPS if mf else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "variant": rec.get("perf_variant", ""),
        "t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
        "dominant": dom[0], "bound_s": bound,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "dot_flops_dev": rec.get("dot_flops_per_device", 0.0),
        "useful_ratio": (mf / hlo_global) if hlo_global else 0.0,
        "roofline_frac": (useful_t / bound) if bound else 0.0,
    }


def what_would_help(row: dict) -> str:
    if row["dominant"] == "collective":
        return "cut collective bytes: bf16 collectives, reduce-scatter " \
               "instead of all-reduce, or reshard to remove the gather"
    if row["dominant"] == "memory":
        return "cut HBM traffic: fuse/smaller dtypes, shard the dominant " \
               "resident tensor (KV cache / node features) over more axes"
    return "raise MXU utilization: larger effective matmul tiles, less " \
           "remat recompute, drop replicated compute"


def load(dry_dir: str, include_variants: bool = False) -> list[dict]:
    rows = []
    for f in sorted(glob.glob(os.path.join(dry_dir, "*.json"))):
        rec = json.load(open(f))
        if not rec.get("ok"):
            continue
        if rec.get("perf_variant") and not include_variants:
            continue
        rows.append(analyze(rec))
    return rows


def to_markdown(rows: list[dict]) -> str:
    out = ["| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | MODEL_FLOPS | useful ratio | roofline frac |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']}"
            f"{('/' + r['variant']) if r['variant'] else ''} "
            f"| {r['t_compute']:.3e} | {r['t_memory']:.3e} "
            f"| {r['t_collective']:.3e} | **{r['dominant']}** "
            f"| {r['model_flops']:.3e} | {r['useful_ratio']:.3f} "
            f"| {r['roofline_frac']:.3f} |")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--write", default="")
    ap.add_argument("--variants", action="store_true")
    args = ap.parse_args()
    rows = load(args.dir, include_variants=args.variants)
    md = to_markdown(rows)
    print(md)
    print()
    for r in rows:
        if r["roofline_frac"] < 0.05 or r["dominant"] == "collective":
            print(f"* {r['arch']}/{r['shape']}/{r['mesh']}: "
                  f"{r['dominant']}-bound, frac={r['roofline_frac']:.3f} -> "
                  + what_would_help(r))
    if args.write:
        with open(args.write, "w") as f:
            f.write(md + "\n")


if __name__ == "__main__":
    main()
