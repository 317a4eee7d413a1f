"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) record in results/dryrun_all.json:

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory_s     = HLO_bytes_per_device / HBM_bandwidth
  collective_s = collective_bytes_per_device / ICI_link_bandwidth

(cost_analysis() on the SPMD-partitioned module reports PER-DEVICE numbers;
collective bytes are summed from per-device shard shapes in the compiled
HLO — both verified in EXPERIMENTS.md §Dry-run.)

Also derives MODEL_FLOPS/HLO_FLOPs (useful-compute fraction: catches remat
and dispatch waste) and the roofline fraction

  fraction = useful_compute_s / max(compute_s, memory_s, collective_s)

which is the §Perf score. Emits results/roofline.md + CSV rows.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

# Per-chip peaks keyed by jax ``Device.device_kind``. v5e: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# ICI per chip = 4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
# The dry-run compiles for v5e meshes; its records carry no device kind.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown device is an error, never a
    default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def analyze_record(r: Dict) -> Optional[Dict]:
    if r.get("status") != "ok":
        return None
    peak = peaks_for(r.get("device_kind", DRYRUN_DEVICE_KIND))
    n_dev = r["n_devices"]
    flops_dev = r["hlo_flops_per_device"]
    bytes_dev = r["hlo_bytes_per_device"]
    coll_dev = r.get("collective_total_bytes", 0)
    compute_s = flops_dev / peak["flops"]
    memory_s = bytes_dev / peak["hbm_bw"]
    coll_s = coll_dev / peak["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    model_flops_dev = r.get("model_flops", 0.0) / n_dev
    useful_s = model_flops_dev / peak["flops"]
    bound_s = max(terms.values())
    return {
        "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
        "variant": r.get("variant", "base"),
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dominant,
        "useful_ratio": (model_flops_dev / flops_dev) if flops_dev else 0.0,
        "roofline_fraction": (useful_s / bound_s) if bound_s else 0.0,
        "hbm_peak_gib": r["memory"]["peak_estimate_bytes"] / 2**30,
        "state_gib": r["memory"].get("state_bytes_exact", 0) / 2**30,
    }


def bottleneck_advice(row: Dict) -> str:
    d = row["dominant"]
    if d == "collective":
        return "cut collective bytes (dedup/compress/reshard)"
    if d == "memory":
        return "raise arithmetic intensity (fuse, bigger tiles, bf16 traffic)"
    return "compute-bound: good; reduce recompute (useful_ratio)"


def render_markdown(rows: List[Dict], mesh: str) -> str:
    out = [f"### Roofline — mesh {mesh}\n",
           "| arch | shape | compute s | memory s | collective s | dominant "
           "| MODEL/HLO | roofline frac | HBM GiB | next lever |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        if r["mesh"] != mesh:
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_fraction']:.3f} | "
            f"{r['hbm_peak_gib']:.1f} | {bottleneck_advice(r)} |")
    return "\n".join(out) + "\n"


def run(path: str = "results/dryrun_all.json") -> List[Dict]:
    if not os.path.exists(path):
        return [{"name": "roofline", "us_per_call": 0.0,
                 "derived": f"SKIPPED: {path} missing (run launch.dryrun first)"}]
    with open(path) as f:
        records = json.load(f)
    rows = [a for a in (analyze_record(r) for r in records) if a]
    md = "\n".join(render_markdown(rows, mesh) for mesh in ("16x16", "2x16x16"))
    os.makedirs("results", exist_ok=True)
    with open("results/roofline.md", "w") as f:
        f.write(md)

    out = []
    for r in rows:
        out.append({
            "name": f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}_{r['variant']}",
            "us_per_call": max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            "derived": (f"dom={r['dominant']} frac={r['roofline_fraction']:.3f} "
                        f"useful={r['useful_ratio']:.2f} hbm={r['hbm_peak_gib']:.1f}GiB"),
        })
    return out
