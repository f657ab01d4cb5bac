"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms per (arch x shape x mesh) cell, all per-device (the dry-run records
per-device HLO stats from the SPMD-partitioned module):

    compute term    = HLO_FLOPs / peak_FLOPs
    memory term     = HLO_bytes / HBM_bw
    collective term = collective_bytes / link_bw

with the peaks of the cell's ``device_kind`` from ``PEAKS`` (a kind that is
not in the table is an error, never a default).

plus MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference; N active for MoE) and the
useful-compute ratio MODEL_FLOPS / HLO_FLOPs. For scanned train cells the
dry-run records depth-extrapolated HLO costs (cost_extrapolated) because XLA
cost analysis does not descend into while bodies.

Usage:
    PYTHONPATH=src python -m repro.launch.roofline            # print table
    PYTHONPATH=src python -m repro.launch.roofline --markdown # EXPERIMENTS block
"""
from __future__ import annotations

import argparse
import glob
import json
import os

# per-chip peaks keyed by jax's ``device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# inter-chip interconnect ~ 4 links of ~50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s, HBM B/s and per-link B/s of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}")
    return PEAKS[device_kind]

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")


def load_cells(results_dir=RESULTS_DIR):
    cells = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        cells.append(json.load(open(f)))
    return cells


def analyze(cell: dict) -> dict | None:
    if cell.get("status") != "ok":
        return None
    cost = cell.get("cost_extrapolated") or cell.get("cost") or {}
    coll = cell.get("collectives_extrapolated") or cell.get("collectives") or {}
    pk = peaks(cell.get("device_kind"))
    peak_flops = pk["flops"]
    flops = cost.get("flops_per_device", 0.0)
    bts = cost.get("bytes_accessed_per_device", 0.0)
    coll_b = sum(v for k, v in coll.items() if k != "count")
    t_c = flops / peak_flops
    t_m = bts / pk["hbm_bw"]
    t_l = coll_b / pk["link_bw"]
    # Analytic memory FLOOR: every input byte read + output byte written once
    # (params/opt-state/KV-cache traffic). The XLA "bytes accessed" figure is
    # an UNFUSED upper bound from the CPU backend — fusion on TPU collapses
    # most intermediate traffic, so the truth lies between floor and bound.
    mem = cell.get("memory", {})
    floor_b = mem.get("argument_bytes", 0) + mem.get("output_bytes", 0)
    t_m_floor = floor_b / pk["hbm_bw"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_l, "collective"))[1]
    dom_floor = max((t_c, "compute"), (t_m_floor, "memory"), (t_l, "collective"))[1]
    chips = cell.get("chips", 256)
    useful = cell.get("model_flops_total", 0.0) / chips
    out = {
        "arch": cell["arch"],
        "shape": cell["shape"],
        "mesh": cell["mesh"],
        "compute_s": t_c,
        "memory_s": t_m,
        "memory_floor_s": t_m_floor,
        "collective_s": t_l,
        "dominant": dom,
        "dominant_floor": dom_floor,
        "model_flops_per_device": useful,
        "hlo_flops_per_device": flops,
        "useful_ratio": (useful / flops) if flops else 0.0,
        "mem_gib_per_device": cell.get("memory", {}).get("total_per_device_bytes", 0) / 2**30,
        "fits_16g": cell.get("memory", {}).get("total_per_device_bytes", 0) < 16 * 2**30,
        # roofline fraction: useful compute time / total modeled time (no overlap)
        "roofline_fraction": (useful / peak_flops) / max(t_c + t_m + t_l, 1e-30),
        # with perfect compute/comm overlap the bound is the max term instead
        "roofline_fraction_overlap": (useful / peak_flops) / max(t_c, t_m, t_l, 1e-30),
        # floor accounting: memory term from the analytic floor (TPU-fused view)
        "roofline_fraction_floor": (useful / peak_flops)
        / max(t_c, t_m_floor, t_l, 1e-30),
    }
    return out


def suggestion(row: dict) -> str:
    if row["dominant"] == "compute":
        if row["useful_ratio"] < 0.5:
            return "cut non-useful FLOPs (replicated attention / remat recompute)"
        return "raise MRA block budget utilization / MXU-align tiles"
    if row["dominant"] == "memory":
        return "bf16 intermediates + fuse MRA gathers (Pallas kernel on TPU)"
    return "reshard to cut collectives (a2a MoE dispatch, overlap with compute)"


def table(cells, markdown=False):
    rows = [r for r in (analyze(c) for c in cells) if r]
    skips = [c for c in cells if c.get("status") == "skipped"]
    errs = [c for c in cells if c.get("status") == "error"]
    hdr = ["arch", "shape", "mesh", "compute_s", "memory_s", "mem_floor_s",
           "collective_s", "dom", "dom_floor", "useful_ratio", "mem_GiB",
           "rf_sum", "rf_overlap", "rf_floor"]
    lines = []
    sep = " | " if markdown else "  "
    if markdown:
        lines.append("| " + " | ".join(hdr) + " |")
        lines.append("|" + "---|" * len(hdr))
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        vals = [r["arch"], r["shape"], r["mesh"],
                f"{r['compute_s']:.3e}", f"{r['memory_s']:.3e}",
                f"{r['memory_floor_s']:.3e}",
                f"{r['collective_s']:.3e}", r["dominant"], r["dominant_floor"],
                f"{r['useful_ratio']:.2f}", f"{r['mem_gib_per_device']:.1f}",
                f"{r['roofline_fraction']:.3f}",
                f"{r['roofline_fraction_overlap']:.3f}",
                f"{r['roofline_fraction_floor']:.3f}"]
        lines.append(("| " if markdown else "") + sep.join(vals) + (" |" if markdown else ""))
    for c in skips:
        lines.append(f"{'| ' if markdown else ''}{c['arch']}{sep}{c['shape']}{sep}{c['mesh']}"
                     f"{sep}SKIPPED: {c['reason']}{' |' if markdown else ''}")
    for c in errs:
        lines.append(f"{'| ' if markdown else ''}{c['arch']}{sep}{c['shape']}{sep}{c['mesh']}"
                     f"{sep}ERROR: {c['error'][:90]}{' |' if markdown else ''}")
    return "\n".join(lines), rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    cells = load_cells()
    txt, rows = table(cells, markdown=args.markdown)
    print(txt)
    if rows:
        print("\nPer-dominant-term counts:",
              {d: sum(1 for r in rows if r["dominant"] == d)
               for d in ("compute", "memory", "collective")})
        worst = sorted(rows, key=lambda r: r["roofline_fraction"])[:3]
        print("Worst roofline fractions:",
              [(r["arch"], r["shape"], r["mesh"], round(r["roofline_fraction"], 4))
               for r in worst])
        collb = sorted(rows, key=lambda r: -r["collective_s"])[:3]
        print("Most collective-bound:",
              [(r["arch"], r["shape"], r["mesh"], f"{r['collective_s']:.2e}s")
               for r in collb])
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
