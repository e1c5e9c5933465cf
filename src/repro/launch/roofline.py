"""Roofline analysis over dry-run artifacts (§Roofline of EXPERIMENTS.md).

Hardware model (TPU v5e):
    peak compute   197 TFLOP/s bf16 per chip
    HBM bandwidth  819 GB/s per chip
    ICI link       ~50 GB/s per chip (aggregate effective, single direction)
    PCIe link      ~16 GB/s host->device (gen4 x16 effective)

Terms (seconds per step, per chip -- dry-run numbers are per-device already):
    compute    = HLO_FLOPs / peak
    memory     = HLO_bytes_accessed / HBM_bw
    collective = sum(collective result bytes) / ICI_bw

The roofline *fraction* reported is ideal/achievable:
    ideal      = MODEL_FLOPS / (chips * peak)          (the 6*N*D floor)
    achievable = max(compute, memory, collective)      (the dominant wall)
so fraction == 1.0 means the step is pure useful matmul at peak.  The
MODEL_FLOPS/HLO_FLOPs ratio separately exposes remat/attention/overhead
compute that the 6ND convention does not count.

**ETL mode** (``--etl BENCH_*.json``) puts the mapping-engine configurations
from a benchmark artifact (:mod:`benchmarks.bench_mapping` via
``benchmarks/run.py --artifact``) on the same chart.  A consume chunk does
no meaningful FLOPs, so the engine walls are

    transfer = host->device bytes per chunk / PCIe_bw
    memory   = device bytes touched per chunk / HBM_bw
    launch   = dispatches per chunk * kernel launch overhead (~6 us)

and the interesting spread is WHERE each engine sits: fused host-densify
is transfer-bound (the dense (B, n_in_pad) payload is mostly-zero PCIe
traffic), and fused device-densify is the only configuration whose transfer term shrinks to the
raw columnar items -- on accelerator hardware that moves the wall from the
PCIe link to the (far faster) HBM, which is the tentpole's 2x at ETL chunk
sizes.  Events/s ceilings reported per engine are chunk_events / wall.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
PCIE_BW = 16e9
LAUNCH_S = 6e-6  # per-dispatch host->device kernel launch overhead

__all__ = [
    "analyze",
    "analyze_dir",
    "render_table",
    "analyze_etl",
    "render_etl_table",
]


def analyze(rec: Dict) -> Optional[Dict]:
    if not rec.get("ok") or rec.get("skipped"):
        return None
    cost = rec.get("cost") or rec.get("cost_scanned")
    if not cost:
        return None
    n = rec["n_devices"]
    coll = sum((rec.get("collectives") or {}).values())
    compute_t = cost["flops"] / PEAK_FLOPS
    memory_t = cost["bytes_accessed"] / HBM_BW
    coll_t = coll / ICI_BW
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    bottleneck = max(terms, key=terms.get)
    ideal = rec["model_flops_global"] / (n * PEAK_FLOPS)
    achievable = max(terms.values())
    frac = ideal / achievable if achievable > 0 else 0.0
    useful = rec["model_flops_global"] / (cost["flops"] * n) if cost["flops"] else 0.0
    hints = {
        "compute": "reduce non-model FLOPs (remat policy, attention flops, "
        "fused CE) or raise MODEL_FLOPS share per step",
        "memory": "raise arithmetic intensity: fuse elementwise chains, "
        "bf16 intermediates, larger per-chip tiles",
        "collective": "cut resharding: head-aligned TP, hoist/overlap FSDP "
        "gathers, reduce-scatter grads instead of all-reduce",
    }
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "bottleneck": bottleneck,
        "ideal_s": ideal,
        "roofline_fraction": frac,
        "useful_flops_ratio": useful,
        "hbm_gb": (rec.get("memory") or {}).get("temp_bytes", 0) / 1e9
        + (rec.get("memory") or {}).get("argument_bytes", 0) / 1e9,
        "hint": hints[bottleneck],
    }


def analyze_dir(path: str, mesh: Optional[str] = None) -> List[Dict]:
    rows = []
    for fn in sorted(glob.glob(os.path.join(path, "*.json"))):
        with open(fn) as f:
            rec = json.load(f)
        if mesh and rec.get("mesh") != mesh:
            continue
        row = analyze(rec)
        if row:
            rows.append(row)
    return rows


def render_table(rows: List[Dict]) -> str:
    hdr = (
        "| arch | shape | mesh | compute s | memory s | collective s | "
        "bottleneck | ideal s | roofline frac | useful-FLOPs | HBM GB/dev |\n"
        "|---|---|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3e} | {r['memory_s']:.3e} | {r['collective_s']:.3e} "
            f"| **{r['bottleneck']}** | {r['ideal_s']:.3e} "
            f"| {r['roofline_fraction']:.2f} | {r['useful_flops_ratio']:.2f} "
            f"| {r['hbm_gb']:.1f} |"
        )
    return hdr + "\n".join(lines) + "\n"


def analyze_etl(artifact: Dict) -> List[Dict]:
    """Place every engine configuration recorded in a benchmark artifact
    (``BENCH_*.json``, see :mod:`benchmarks.run`) on the ETL roofline.

    Each entry of ``artifact["engines"]`` carries per-chunk facts measured
    by the benchmark: ``dispatches`` (device launches), ``host_bytes``
    (host->device operand traffic), ``device_bytes`` (device-side bytes the
    dispatch touches), ``chunk_events``, and the measured ``events_per_s``
    on the benchmark backend.  The model walls (transfer / memory / launch,
    module docstring) give the accelerator-hardware ceiling
    ``roof_events_per_s`` -- on CPU the measured number reflects host
    python/numpy instead, which is exactly why both are reported.
    """
    rows = []
    for e in artifact.get("engines", []):
        transfer_t = e["host_bytes"] / PCIE_BW
        memory_t = e["device_bytes"] / HBM_BW
        launch_t = e["dispatches"] * LAUNCH_S
        terms = {"transfer": transfer_t, "memory": memory_t, "launch": launch_t}
        bottleneck = max(terms, key=terms.get)
        wall = max(terms.values())
        rows.append(
            {
                "engine": e["engine"],
                "chunk_events": e["chunk_events"],
                "dispatches": e["dispatches"],
                "host_bytes": e["host_bytes"],
                "device_bytes": e["device_bytes"],
                "transfer_s": transfer_t,
                "memory_s": memory_t,
                "launch_s": launch_t,
                "bottleneck": bottleneck,
                "roof_events_per_s": e["chunk_events"] / wall if wall > 0 else 0.0,
                "measured_events_per_s": e.get("events_per_s"),
            }
        )
    return rows


def render_etl_table(rows: List[Dict]) -> str:
    hdr = (
        "| engine | disp/chunk | host B/chunk | device B/chunk | "
        "transfer s | memory s | launch s | bottleneck | roof ev/s | "
        "measured ev/s |\n"
        "|---|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        meas = (
            f"{r['measured_events_per_s']:.0f}"
            if r.get("measured_events_per_s")
            else "-"
        )
        lines.append(
            f"| {r['engine']} | {r['dispatches']} | {r['host_bytes']} "
            f"| {r['device_bytes']} | {r['transfer_s']:.2e} "
            f"| {r['memory_s']:.2e} | {r['launch_s']:.2e} "
            f"| **{r['bottleneck']}** | {r['roof_events_per_s']:.3e} | {meas} |"
        )
    return hdr + "\n".join(lines) + "\n"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--etl", default=None, metavar="BENCH_JSON",
                    help="ETL mode: roofline the engine configurations in a "
                         "benchmark artifact (BENCH_*.json) instead of the "
                         "dry-run directory")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.etl:
        with open(args.etl) as f:
            artifact = json.load(f)
        rows = analyze_etl(artifact)
        if args.json:
            print(json.dumps(rows, indent=1))
        else:
            print(render_etl_table(rows))
            for r in rows:
                print(f"- {r['engine']}: {r['bottleneck']}-bound")
        return
    rows = analyze_dir(args.dir, args.mesh)
    if args.json:
        print(json.dumps(rows, indent=1))
    else:
        print(render_table(rows))
        for r in rows:
            print(f"- {r['arch']}/{r['shape']}/{r['mesh']}: {r['bottleneck']}-bound; {r['hint']}")


if __name__ == "__main__":
    main()
