"""§Roofline: aggregate the dry-run JSONs into the per-(arch x shape x mesh)
three-term roofline table (compute / memory / collective seconds, dominant
bottleneck, 6ND model-FLOPs ratio) and emit a markdown table for
EXPERIMENTS.md.

Usage: python -m benchmarks.roofline [--mesh 16x16] [--markdown out.md]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro import config

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def load(mesh: str = None):
    recs = []
    for f in sorted(DRYRUN_DIR.glob("*.json")):
        if f.name.startswith("BASELINE_"):
            continue  # pre-§Perf snapshots live beside the finals
        r = json.loads(f.read_text())
        if mesh and r.get("mesh") != mesh:
            continue
        recs.append(r)
    return recs


def fraction(r):
    """Roofline fraction: useful model FLOP-time over the dominant term.

    Peak FLOP/s comes from ``config.PEAK_FLOPS`` keyed by the record's
    ``device_kind``; records without one are dry runs of v5e meshes
    (``launch/dryrun.py``) and resolve to the v5e row."""
    if "roofline" not in r or "model_flops_per_device" not in r:
        return None
    dom = max(r["roofline"]["compute_s"], r["roofline"]["memory_s"],
              r["roofline"]["collective_s"])
    t_model = r["model_flops_per_device"] / config.peak_flops(
        r.get("device_kind", "TPU v5 lite"))
    return t_model / dom if dom > 0 else None


def advice(r):
    dom = r["roofline"]["dominant"]
    if dom == "memory":
        return "cut HBM traffic: bf16 attention probs / fuse / larger arithmetic intensity per pass"
    if dom == "collective":
        return "cut comms: reduce-scatter grads, overlap TP psum with compute, shard KV differently"
    return "raise MFU: larger per-chip tiles, fewer remat passes"


def table(recs, out):
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':8s} {'comp_s':>9s} {'mem_s':>9s} "
           f"{'coll_s':>9s} {'dom':>10s} {'6ND/HLO':>8s} {'frac':>7s}")
    out(hdr)
    out("-" * len(hdr))
    for r in recs:
        if r.get("skipped"):
            out(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
                f"{'SKIP':>9s}  ({r['skipped'][:60]}...)")
            continue
        ro = r["roofline"]
        ur = r.get("useful_flops_ratio") or 0
        fr = fraction(r) or 0
        out(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:8s} "
            f"{ro['compute_s']:9.3f} {ro['memory_s']:9.3f} {ro['collective_s']:9.3f} "
            f"{ro['dominant']:>10s} {ur:8.3f} {fr:7.3f}")


def markdown(recs) -> str:
    lines = ["| arch | shape | mesh | compute (s) | memory (s) | collective (s) "
             "| dominant | 6ND/HLO | roofline frac | next lever |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if r.get("skipped"):
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — "
                         f"| skipped | — | — | {r['skipped'][:70]} |")
            continue
        ro = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {ro['compute_s']:.3f} "
            f"| {ro['memory_s']:.3f} | {ro['collective_s']:.3f} | {ro['dominant']} "
            f"| {(r.get('useful_flops_ratio') or 0):.3f} | {(fraction(r) or 0):.3f} "
            f"| {advice(r)} |")
    return "\n".join(lines)


def run(out):
    recs = load(mesh="16x16")
    if not recs:
        out("roofline: no dry-run records found (run repro.launch.dryrun)")
        return
    table(recs, out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--markdown", default=None)
    args = ap.parse_args()
    recs = load(args.mesh)
    table(recs, print)
    if args.markdown:
        Path(args.markdown).write_text(markdown(recs))
        print(f"wrote {args.markdown}")


if __name__ == "__main__":
    main()
