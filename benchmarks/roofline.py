"""§Roofline aggregator: reads experiments/dryrun/*.json, emits the full
per-(arch x shape x mesh) table with the three terms, dominant bottleneck,
MODEL_FLOPS/HLO ratio, TPU-adjusted HBM fit, and a what-would-help note.

Robust memory adjustment: adjusted = max(raw - upcast_buffers,
args + out - alias + 0.15 * temp) — upcast buffer sums are estimates from
HLO text (buffer reuse is invisible there), so the floor prevents
over-subtraction.
"""
from __future__ import annotations

import glob
import json
from pathlib import Path

EXPERIMENTS = Path(__file__).resolve().parents[1] / "experiments"
DRYRUN = EXPERIMENTS / "dryrun_v2"


def _advice(row):
    dom = row["dominant"]
    if dom == "compute_s":
        if row["useful_flops_ratio"] and row["useful_flops_ratio"] < 0.7:
            return "cut remat recompute (selective checkpoint policy)"
        return "compute-bound: near roofline; tune MXU tile shapes"
    if dom == "memory_s":
        return ("Pallas flash/SSD kernels keep score tiles in VMEM "
                "(jnp path materializes f32 S x block tensors)")
    return "reduce TP psums: sequence-sharded activations / fewer microbatch weight regathers"


def load_rows():
    rows = []
    for f in sorted(glob.glob(str(DRYRUN / "*.json"))):
        d = json.load(open(f))
        if d.get("variant", "baseline") != "baseline":
            continue
        ma = d["memory_analysis"]
        r = d["roofline"]
        raw = ma["peak_hbm_per_device_bytes"]
        up = ma.get("cpu_upcast_buffer_bytes", 0.0)
        floor = (ma["argument_bytes"] + ma["output_bytes"]
                 - ma["alias_bytes"] + 0.15 * ma["temp_bytes"])
        adjusted = max(raw - up, floor)
        row = {
            "arch": d["arch"], "shape": d["shape"], "mesh": d["mesh"],
            "attn_mode": d["attn_mode"],
            "compute_s": r["compute_s"], "memory_s": r["memory_s"],
            "collective_s": r["collective_s"], "dominant": r["dominant"],
            "model_flops_6ND": r["model_flops_global_6ND"],
            "useful_flops_ratio": r["useful_flops_ratio"],
            "roofline_fraction": r["roofline_fraction"],
            "hbm_adjusted_gb": adjusted / 1e9,
            "fits_16gb": adjusted < 16e9,
            "microbatches": d.get("microbatches"),
        }
        row["advice"] = _advice(row)
        rows.append(row)
    return rows


def to_markdown(rows) -> str:
    hdr = ("| arch | shape | mesh | attn | compute_s | memory_s | coll_s | "
           "dominant | HBM/dev GB | fits 16GB | 6ND/HLO | roofline | note |")
    sep = "|" + "---|" * 13
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['attn_mode']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | {r['dominant'].replace('_s','')} "
            f"| {r['hbm_adjusted_gb']:.1f} | {'Y' if r['fits_16gb'] else 'N'} "
            f"| {(r['useful_flops_ratio'] or 0):.2f} "
            f"| {100*(r['roofline_fraction'] or 0):.2f}% | {r['advice']} |")
    return "\n".join(lines)


def main(fast: bool = False):
    rows = load_rows()
    md = to_markdown(rows)
    out = EXPERIMENTS / "roofline.md"
    out.write_text(md + "\n")
    doms = {}
    for r in rows:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    return {"cells": len(rows), "dominant_histogram": doms,
            "fits_all": all(r["fits_16gb"] for r in rows),
            "table_path": str(out)}


if __name__ == "__main__":
    print(json.dumps(main(), indent=2))
