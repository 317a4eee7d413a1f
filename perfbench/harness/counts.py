"""Byte counts of the embedding update, and the chips' peaks.

A model's FLOPs per example are its reference module's
``train_flops_per_example`` (``perfbench/reference/<model>.py``): the
matrix products of the forward and backward passes of its dense parts.
Embedding lookups, elementwise work and the optimizer count none.
"""

from __future__ import annotations

from typing import Dict

# Per-chip peaks keyed by jax ``Device.device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def embed_update_bytes(cfg: Dict, n_unique: float) -> float:
    """Least HBM bytes to write a step's updated working set back into
    the table: per unique row, read its new row and accumulator from the
    working set and write both into the table."""
    return 2.0 * n_unique * (4 * cfg["embed_dim"] + 4)
