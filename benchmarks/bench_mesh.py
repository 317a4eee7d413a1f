"""Mesh scale-out: collective bytes/step and simulated-device scaling.

Two kinds of rows:

* **Analytic, gated** — the CommPlan byte model for the dlrm smoke config
  on a 2x4 ('pod', 'data') mesh. Deterministic (pure counting, no
  timing), so the rows gate on ``metric`` and hold on any machine. The
  headline acceptance row is the hierarchical-compressed / flat inter-pod
  byte ratio: bf16 must cut allreduce bytes by at least pod_size x 2
  (psum_scatter divides the wire by the pod's device count, bf16 halves
  the itemsize) — asserted here so the suite FAILS (exit 1) if the model
  ever stops beating ``flat_psum``.
* **Measured scaling** — wall-clock us/step of the sharded train step on
  1 -> 8 simulated host devices (subprocess per mesh shape; jax locks the
  device count at first init). Simulated devices share one CPU, so these
  document step-time behavior of the lowering, not real speedup; they are
  reported ungated. The children always run on the CPU backend
  (``JAX_PLATFORMS=cpu``), so they never need the chip a parent process
  may hold; on a TPU host the rows say so.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List

ROWS = 256          # batch rows for the byte model and the timed step
MESH_SHAPES = [(1, 1), (1, 2), (2, 2), (2, 4)]
STEPS = 10

_TIMING_SCRIPT = """
import time
import numpy as np, jax, jax.numpy as jnp
import repro.models.recsys as R
from repro.configs import get_arch
from repro.fe.modelfeed import dedup_capacity_hint
from repro.launch.mesh import make_train_mesh
from repro.train.optimizer import adamw
import dataclasses

pods, data, B, steps = {pods}, {data}, {rows}, {steps}
cfg = get_arch("dlrm-mlperf").smoke()
cfg = dataclasses.replace(cfg, dedup_capacity=dedup_capacity_hint(cfg, B))
mesh = make_train_mesh(pods, data)
n_dev = pods * data
step, init, _ = R.make_mesh_train_step(
    cfg, adamw(1e-3), mesh=mesh, compress={codec!r},
    local_dedup_capacity=dedup_capacity_hint(cfg, max(1, B // n_dev)))
params = R.init_params(cfg, jax.random.PRNGKey(0))
p, o = R.shard_train_state(mesh, params, init(params))
r = np.random.default_rng(0)
batch = {{
    "dense": jnp.asarray(r.normal(size=(B, cfg.n_dense)).astype(np.float32)),
    "sparse": jnp.asarray(np.stack(
        [r.integers(0, v, B) for v in cfg.vocab_sizes], 1).astype(np.int32)),
    "label": jnp.asarray(r.integers(0, 2, B).astype(np.float32)),
}}
jstep = jax.jit(step)
p, o, m = jstep(p, o, batch)            # compile + first step
jax.block_until_ready(m["loss"])
t0 = time.perf_counter()
for _ in range(steps):
    p, o, m = jstep(p, o, batch)
jax.block_until_ready(m["loss"])
print("US_PER_STEP", (time.perf_counter() - t0) / steps * 1e6)
print("LOSS", float(m["loss"]))
"""


def _timed_row(pods: int, data: int, *, codec, n_sim: int) -> Dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # simulated devices; never the chip
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_sim}"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    code = _TIMING_SCRIPT.format(pods=pods, data=data, rows=ROWS,
                                 steps=STEPS, codec=codec)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(
            f"mesh {pods}x{data} timing failed:\n{out.stderr[-2000:]}")
    us = float(next(ln.split()[1] for ln in out.stdout.splitlines()
                    if ln.startswith("US_PER_STEP")))
    loss = float(next(ln.split()[1] for ln in out.stdout.splitlines()
                      if ln.startswith("LOSS")))
    tag = codec or "off"
    return {"name": f"mesh.step.{pods}x{data}.{tag}", "us_per_call": us,
            "derived": f"{ROWS} rows on {pods * data} simulated CPU devices "
                       f"codec={tag} loss={loss:.4f}"}


def run() -> List[Dict]:
    import dataclasses

    from repro.configs import get_arch
    from repro.fe.modelfeed import dedup_capacity_hint
    from repro.models import recsys as R
    from repro.train.compression import CommPlan

    cfg = get_arch("dlrm-mlperf").smoke()
    cfg = dataclasses.replace(cfg, dedup_capacity=dedup_capacity_hint(cfg, ROWS))
    pods, inner = 2, 4
    n_dev = pods * inner
    rows_dev = ROWS // n_dev

    def plan_for(codec):
        return CommPlan.for_step(
            n_pods=pods, inner=inner, compress=codec, hierarchical=True,
            capacity=cfg.dedup_capacity, embed_dim=cfg.embed_dim,
            n_dense_elems=R.dense_param_elems(cfg),
            local_capacity=dedup_capacity_hint(cfg, rows_dev),
            ids_per_device=R.batch_id_count(cfg, rows_dev))

    rows: List[Dict] = []
    flat = plan_for(None)
    rows.append({
        "name": "mesh.bytes.flat_psum", "us_per_call": 0.0, "gate": True,
        "metric": flat.interpod_bytes_per_step_flat,
        "derived": f"{pods}x{inner} flat fp32 all-reduce + raw-id exchange, "
                   f"{flat.interpod_bytes_per_step_flat} B/step inter-pod"})
    for codec in (None, "bf16", "int8"):
        plan = plan_for(codec)
        tag = codec or "off"
        ratio = (plan.interpod_bytes_per_step
                 / max(plan.interpod_bytes_per_step_flat, 1))
        rows.append({
            "name": f"mesh.bytes.hier.{tag}", "us_per_call": 0.0,
            "gate": True, "metric": plan.interpod_bytes_per_step,
            "derived": f"hierarchical codec={tag} "
                       f"{plan.interpod_bytes_per_step} B/step inter-pod "
                       f"(x{plan.interpod_reduction:.1f} less than flat)"})
        rows.append({
            "name": f"mesh.bytes.ratio.{tag}", "us_per_call": 0.0,
            "gate": True, "metric": round(ratio, 5),
            "derived": f"hier/flat inter-pod byte ratio, lower is better"})
        if codec is not None:
            # the acceptance bar: compressed hierarchical reduction must
            # beat flat_psum on the dense allreduce by >= pod_size x 2
            # (1% slack for the ceil-padding of the scattered block)
            assert plan.allreduce_reduction >= 2 * inner * 0.99, (
                codec, plan.allreduce_reduction)
    bf16 = plan_for("bf16")
    rows.append({
        "name": "mesh.allreduce_reduction.bf16", "us_per_call": 0.0,
        "gate": True, "metric": round(1.0 / bf16.allreduce_reduction, 5),
        "derived": f"inverse allreduce byte reduction vs flat "
                   f"(x{bf16.allreduce_reduction:.2f} less; acceptance "
                   f">= pod_size x 2 = {2 * inner})"})
    rows.append({
        "name": "mesh.dedup.exchange_bytes", "us_per_call": 0.0,
        "gate": True, "metric": flat.dedup_interpod_bytes,
        "derived": f"two-stage id pool crossing pods: "
                   f"{flat.dedup_interpod_bytes} B/step vs "
                   f"{flat.dedup_interpod_bytes_flat} B raw flat ids"})

    # ---- measured scaling curve, 1 -> 8 simulated devices (ungated)
    import jax
    if jax.default_backend() != "cpu":
        print(f"mesh: the mesh.step.* rows time simulated CPU devices in "
              f"child processes, not this {jax.default_backend()} backend",
              file=sys.stderr)
    for pods_, data_ in MESH_SHAPES:
        rows.append(_timed_row(pods_, data_, codec=None, n_sim=8))
    rows.append(_timed_row(2, 4, codec="bf16", n_sim=8))
    return rows
