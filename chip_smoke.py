"""Smoke run of the streaming trainer on a TPU, at DCN-v2's published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 2x2 mesh against a one-chip reference

One chip: ``repro.launch.train.main`` writes 8 shards x 4096 rows of
synthetic ad logs (fixed seed) and trains 8 steps of ``dcn-v2`` at its
published config (26 Criteo-1TB tables, 187,767,808 padded rows x 16 fp32)
through the normal path: shards -> StreamingLoader -> FeaturePlan -> arena
feed -> ModelFeed -> dedup'd working-set step -> PipelinedRunner, after the
``--check`` preflight (whose AL204 runs the compiled Pallas allocator).

``--chips 4``: the same shards and steps, first on one chip (the
reference), then on a 2x2 mesh with the table row-sharded over four chips.
Both read the shards in plan order (``--fault-tolerant``) so they train on
the same batches, and their losses must agree within fp32 tolerance. They
train at ``--lr 1e-5``: at the default 1e-3 this model's loss swings by
two orders of magnitude from one step to the next, and such a trajectory
grows the two layouts' fp32 reduction-order differences past what a
per-step tolerance is meant to measure.

Everything runs in this one process (a chip belongs to one process). It
exits non-zero, printing no result, when JAX finds no TPU or any phase
fails. The last line of its output is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ARCH = "dcn-v2"
PUBLISHED_ROWS = 187_767_808   # CRITEO_1TB_VOCABS padded to row_align 512
STEPS = 8
ROWS = 4096                    # rows per shard = rows per step
MESH_RTOL = 2e-5               # tests/test_mesh.py::test_mesh_2x4_matches_single_device


def _train_args(data_dir: str, *extra: str):
    return ["--arch", ARCH, "--full", "--spec", "dlrm", "--data-dir", data_dir,
            "--batch", str(ROWS), "--steps", str(STEPS), *extra]


class _CompileMeter:
    """XLA compile time and persistent-cache hits, from JAX's own events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compile: {self.seconds:.3f} s in XLA backend compiles "
                f"(persistent cache hits={self.hits} misses={self.misses})")


def _check_run(summary, *, label: str):
    losses = summary["losses"]
    print(f"{label} losses: {losses}")
    if summary["steps"] != STEPS or len(losses) != STEPS:
        raise SystemExit(f"{label}: ran {summary['steps']} steps, "
                         f"expected {STEPS}")
    if not all(math.isfinite(v) for v in losses):
        raise SystemExit(f"{label}: non-finite loss in {losses}")
    cfg = summary["config"]
    if cfg.padded_rows != PUBLISHED_ROWS:
        raise SystemExit(f"{label}: table has {cfg.padded_rows} rows, "
                         f"expected {PUBLISHED_ROWS}")
    shards = summary["table_shards"]
    platforms = sorted({p for p, _, _ in shards})
    print(f"{label} table: {len(shards)} shard(s) on {platforms} devices "
          f"{sorted(d for _, d, _ in shards)}, rows per shard "
          f"{[r for _, _, r in shards]}")
    if platforms != ["tpu"] or sum(r for _, _, r in shards) != cfg.padded_rows:
        raise SystemExit(f"{label}: table not resident on the TPU: {shards}")
    print(f"{label} dedup capacity: {cfg.dedup_capacity} rows")
    steady = sorted(summary["step_seconds"][1:])
    print(f"{label} smoke timing, not a benchmark: first step "
          f"{summary['step_seconds'][0]:.3f} s (compile included), then "
          f"median {steady[len(steady) // 2] * 1e3:.3f} ms/step")
    return losses, shards


def _peak_line(devices) -> str:
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices]
    return f"peak_bytes_in_use per device: {peaks}"


def run_one_chip(jax, main, meter, data_dir):
    summary = main(_train_args(data_dir, "--gen-shards", str(STEPS),
                               "--device-feed", "arena", "--check"))
    print(meter.line())
    _check_run(summary, label="1-chip")
    print(_peak_line(jax.devices()[:1]))


def run_four_chips(jax, main, meter, data_dir):
    import numpy as np

    if len(jax.devices()) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                         f"{len(jax.devices())}")
    ordered = ("--fault-tolerant", "--device-feed", "off", "--lr", "1e-5")
    ref = main(_train_args(data_dir, "--gen-shards", str(STEPS), *ordered))
    ref_losses, _ = _check_run(ref, label="reference (1 chip)")
    del ref  # host values only: the train state died with main's frame
    gc.collect()
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"live device bytes after the reference: {live}")
    if live > 2**30:
        raise SystemExit("the reference's train state was not freed")

    mesh = main(_train_args(data_dir, *ordered, "--mesh", "2x2",
                            "--compress", "off"))
    print(meter.line())
    mesh_losses, shards = _check_run(mesh, label="mesh 2x2")
    print(_peak_line(jax.devices()[:4]))
    rows = {r for _, _, r in shards}
    if len({d for _, d, _ in shards}) != 4 or rows != {PUBLISHED_ROWS // 4}:
        raise SystemExit(f"table not split over 4 devices: {shards}")
    rel = [abs(a - b) / abs(a) for a, b in zip(ref_losses, mesh_losses)]
    print(f"mesh vs reference: relative loss difference per step {rel} "
          f"(limit {MESH_RTOL})")
    np.testing.assert_allclose(mesh_losses, ref_losses, rtol=MESH_RTOL)


def main_cli(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 2x2 mesh run and its one-chip "
                         "reference")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                         f"nothing was run")
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import main

    print(f"compile cache: {enable_compile_cache()}")
    meter = _CompileMeter(jax)
    cfg = get_arch(ARCH).config
    print(f"config {cfg.name}: {cfg.padded_rows} padded rows x "
          f"{cfg.embed_dim} fp32, table {cfg.padded_rows * cfg.embed_dim * 4} "
          f"bytes")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        data_dir = os.path.join(tmp, "shards")
        if args.chips == 4:
            run_four_chips(jax, main, meter, data_dir)
        else:
            run_one_chip(jax, main, meter, data_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main_cli()
