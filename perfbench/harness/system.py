"""The system under test, wired as the program's streaming driver wires it.

``repro.launch.train.run_streaming`` builds, for a recsys arch: a compiled
``FeaturePlan``, its ``ModelFeed`` with the working-set capacity tuned from
the rows hint, the dedup'd sparse step (or the row-sharded mesh step), the
train state made in one jit in its final placement, the device feed, and a
``PipelinedRunner``. This module builds the same objects from the
benchmark's configuration and traffic files, with three differences that
only concern the harness: the weights' key comes from ``--seed``, the batch
source is the traffic generator's (a cycled pool of pre-extracted batches,
or shards written before the window), and the runner's step callback is the
harness's window, which keeps timestamps and takes no reading.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
from typing import Any, Callable, Dict, Iterator, List

import numpy as np

from . import traffic


@dataclasses.dataclass
class System:
    cfg: Any                      # the program's RecsysConfig, capacity tuned
    step: Callable                # ModelFeed's compiled boundary step
    state: Dict[str, Any]         # {"params", "opt"} on the device(s)
    layers: List[Any]             # the FE layers the runner's worker runs
    feeder: Any                   # the device feed, or None (mesh)
    source: Callable[[], Iterator]
    split: bool                   # per-field id vectors (else packed)
    close: Callable[[], None]


def recsys_config(c: Dict):
    """The program's ``RecsysConfig`` from every key of the configuration
    file that names one of its fields (lists become tuples, the ``dtype``
    name its ``jax.numpy`` type)."""
    import jax.numpy as jnp
    from repro.models.recsys import RecsysConfig
    names = {f.name for f in dataclasses.fields(RecsysConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in c.items() if k in names}
    if "dtype" in kw:
        kw["dtype"] = getattr(jnp, kw["dtype"])
    return RecsysConfig(**kw)


def program_key(seed: int):
    import jax
    return jax.random.PRNGKey(weight_seed(seed))


def weight_seed(seed: int) -> int:
    """The 31-bit key seed of the weights, drawn from ``--seed``."""
    return int(np.random.SeedSequence(int(seed) % 2**63).generate_state(1)[0] >> 1)


def _to_program_views(views: Dict[str, Dict[str, np.ndarray]]):
    from repro.fe.colstore import RaggedColumn
    out = {}
    for name, cols in views.items():
        cols = dict(cols)
        for key in [k for k in cols if k.endswith("_values")]:
            base = key[:-len("_values")]
            cols[base] = RaggedColumn(values=cols.pop(key).astype(np.int64),
                                      lengths=cols.pop(base + "_lengths"))
        out[name] = cols
    return out


def build(cfg_json: Dict, mix: Dict, seed: int, tmp_dir: str) -> System:
    from repro.core import DeviceFeeder
    from repro.fe import featureplan, get_spec
    from repro.fe.modelfeed import dedup_capacity_hint
    from repro.models import recsys as R
    from repro.train.optimizer import adamw

    o = cfg_json["optimizer"]
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                clip_norm=o["clip_norm"])
    step_kw = dict(embed_lr=o["embed_lr"], embed_eps=o["embed_eps"])
    cfg = recsys_config(cfg_json)
    plan = featureplan.compile(get_spec(cfg_json["fe_spec"]))
    key = program_key(seed)
    rows = mix["rows_per_step"]
    mesh_shape = mix.get("mesh")
    close: Callable[[], None] = lambda: None

    if mix["kind"] == "raw_log":
        from repro.io.convert import write_view_shards
        from repro.io.dataset import ShardDataset
        from repro.io.stream import StreamingLoader
        data_dir = os.path.join(tmp_dir, "shards")
        write_view_shards(data_dir, (
            _to_program_views(traffic.raw_views(mix, seed, i))
            for i in range(mix["pool_steps"])))
        loader = StreamingLoader(
            ShardDataset(data_dir), workers=mix["reader_workers"],
            prefetch=mix["reader_prefetch"], epochs=mix["epochs"],
            shuffle=True, seed=weight_seed(seed), columns=plan.required_columns)
        rows_hint = loader.rows_hint
        iters: List[Any] = []

        def source():
            it = iter(loader)
            iters.append(it)
            return it

        def close():
            for it in iters:
                try:
                    it.close()
                except ValueError:  # the FE worker still holds it
                    pass
            loader.close()
    else:
        rows_hint = rows
        vocab = cfg_json["vocab_sizes"]
        pool = [traffic.preextracted_batch(mix, vocab, seed, i)
                for i in range(mix["pool_steps"])]
        if mesh_shape:
            for b in pool:
                b["batch_sparse"] = np.stack(
                    [b.pop(f"batch_field_{f:02d}") for f in range(len(vocab))], axis=1)

        def source():
            return itertools.cycle(pool)

    split = not mesh_shape
    mf = plan.model_feed(cfg, split_sparse_fields=split, rows_hint=rows_hint)
    cfg = mf.config
    feeder = None
    layers: List[Any] = []
    if mesh_shape:
        from repro.launch.mesh import make_train_mesh
        pods, data = mesh_shape
        n_dev = pods * data
        mesh = make_train_mesh(pods, data)
        local_cap = dedup_capacity_hint(cfg, max(1, rows_hint // n_dev))
        raw_step, init_opt, _ = R.make_mesh_train_step(
            cfg, opt, mesh=mesh, compress=mix.get("compress", "off"),
            local_dedup_capacity=local_cap, **step_kw)
        params, opt_state = R.init_train_state(cfg, key, init_opt, mesh=mesh)
    else:
        raw_step, init_opt, _ = R.make_sparse_train_step(cfg, opt, **step_kw)
        params, opt_state = R.init_train_state(cfg, key, init_opt)
        if mix["kind"] == "raw_log":
            ab = plan.arena_binding(split_sparse_fields=True)
            layers, feeder = ab.layers, ab.make_feeder(rows_hint=rows_hint)
        else:
            feeder = DeviceFeeder(plan.feed_layout(split_sparse_fields=True),
                                  rows_hint=rows_hint)
    step = mf.make_step(raw_step, fused=True, donate=True,
                        fence_cb=feeder.donation_fence if feeder else None)

    return System(cfg=cfg, step=step, state={"params": params, "opt": opt_state},
                  layers=layers, feeder=feeder, source=source, split=split,
                  close=close)


def model_ids(env: Dict[str, Any], cfg_json: Dict, split: bool) -> np.ndarray:
    """The model's per-field ids of a staged batch, as the configuration
    maps spec fields to tables: field ``f`` reads spec field ``f mod n``,
    modulo the table's vocabulary (host copy)."""
    vocab = np.asarray(cfg_json["vocab_sizes"], np.int64)
    if split:
        n = sum(1 for k in env if k.startswith("batch_field_"))
        spec = np.stack([np.asarray(env[f"batch_field_{f % n:02d}"])
                         for f in range(len(vocab))], axis=1)
    else:
        packed = np.asarray(env["batch_sparse"])
        spec = packed[:, np.arange(len(vocab)) % packed.shape[1]]
    return spec.astype(np.int64) % vocab[None, :]
