"""Config substrate: per-(arch x shape) dry-run cells.

Each architecture file exports an :class:`ArchSpec`; ``cells_for`` turns an
(arch, shape, mesh) triple into a :class:`Cell` — the jit-able step function,
abstract inputs (ShapeDtypeStruct, no allocation), and shardings — consumed
by ``launch/dryrun.py`` and the roofline analysis.

Variants (``--variant``) select paper-faithful vs optimized configurations
for §Perf (e.g. recsys embedding lookup with/without the FeatureBox dedup).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import gnn as G
from repro.models import recsys as R
from repro.models import transformer as T
from repro.train import optimizer as opt_lib


@dataclasses.dataclass
class Cell:
    """One dry-run unit: fn + abstract args + shardings + roofline metadata."""

    arch_id: str
    shape_name: str
    fn: Callable
    args: Tuple[Any, ...]
    in_shardings: Any
    out_shardings: Any = None
    donate_argnums: Tuple[int, ...] = ()
    model_flops: float = 0.0          # analytic 6·N·D (train) / 2·N·D (serve)
    skip: Optional[str] = None
    static_argnames: Tuple[str, ...] = ()


@dataclasses.dataclass
class ArchSpec:
    arch_id: str
    family: str                        # "lm" | "recsys" | "gnn"
    shapes: Tuple[str, ...]
    build_cell: Callable[..., Cell]    # (shape, mesh, dp, variant) -> Cell
    smoke: Callable[[], Any]           # reduced config for CPU runs and tests
    describe: str = ""
    # the published config (``--full``); None where it differs per shape
    config: Any = None


def _shard_tree(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def dp_axes_for(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def count_params(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


# =============================================================== LM family
def lm_active_params(cfg: T.LMConfig) -> float:
    """Active (per-token) parameter count for 6·N·D (MoE counts top-k only)."""
    shapes = T.param_shapes(cfg)
    total = 0.0
    for group, v in shapes.items():
        if isinstance(v, dict):
            for name, s in v.items():
                n = float(np.prod(s))
                if name.startswith("moe_w") and cfg.moe:
                    n *= cfg.moe.top_k / cfg.moe.n_experts
                total += n
        else:
            if group == "embed":
                continue  # lookup, not matmul
            total += float(np.prod(v))
    return total


LM_SHAPES = {
    "train_4k": {"kind": "train", "seq": 4096, "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32768, "batch": 32},
    "decode_32k": {"kind": "decode", "seq": 32768, "batch": 128},
    "long_500k": {"kind": "long_decode", "seq": 524288, "batch": 1},
}


def lm_cell(cfg: T.LMConfig, shape: str, mesh: Mesh, *, variant: str = "base") -> Cell:
    info = LM_SHAPES[shape]
    dp = dp_axes_for(mesh)
    # §Perf variants (hypothesis-driven; see EXPERIMENTS.md §Perf).
    # Combine with '+': e.g. "accum8+cf100".
    tp = "model"
    for v in variant.split("+"):
        if v == "puredp":
            # pure ZeRO-DP mapping of the same mesh: batch over ALL axes,
            # no TP (dense models only; the whole layer fits one chip)
            if cfg.moe is not None:
                raise ValueError("puredp applies to dense LMs only")
            tp = None
            cfg = dataclasses.replace(cfg, grad_accum=1)
        elif v.startswith("accum"):
            cfg = dataclasses.replace(cfg, grad_accum=int(v[len("accum"):]))
        elif v.startswith("lchunk"):
            cfg = dataclasses.replace(cfg, loss_chunk=int(v[len("lchunk"):]))
        elif v.startswith("qb"):
            qb = int(v[2:])
            cfg = dataclasses.replace(cfg, q_block=qb, kv_block=qb)
        elif v.startswith("cf"):
            assert cfg.moe, "capacity-factor variant needs MoE"
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe,
                                             capacity_factor=float(v[2:]) / 100))
        elif v != "base":
            raise ValueError(f"unknown LM variant {v!r}")
    if info["kind"] == "long_decode":
        return Cell(
            arch_id=cfg.name, shape_name=shape, fn=None, args=(),
            in_shardings=None,
            skip=("full-attention architecture: 524k decode requires "
                  "sub-quadratic attention (DESIGN.md §4)"),
        )

    if tp is None:
        if info["kind"] == "decode":
            # puredp targets train/prefill; decode keeps the standard
            # mapping (its cache shards head_dim over 'model')
            tp = "model"
        else:
            dp = dp + ("model",)  # flatten: batch/weights over every axis
    params = T.abstract_params(cfg)
    pspecs = T.param_specs(cfg, dp=dp, tp=tp)
    psh = _shard_tree(mesh, pspecs)
    seq, batch = info["seq"], info["batch"]
    n_active = lm_active_params(cfg)

    if info["kind"] == "train":
        huge = count_params(params) > 5e10
        moment_dtype = jnp.bfloat16 if huge else jnp.float32
        optimizer = opt_lib.adamw(
            1e-4, moment_dtype=moment_dtype,
            compute_dtype=jnp.bfloat16 if huge else jnp.float32)
        opt_state = optimizer.abstract_state(params)
        osh = {
            "m": psh, "v": psh,
            "step": NamedSharding(mesh, P()),
        }
        batch_sds = {
            "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        }
        bsh = {
            "tokens": NamedSharding(mesh, P(dp, None)),
            "labels": NamedSharding(mesh, P(dp, None)),
        }
        fn = T.make_train_step(cfg, optimizer, mesh=mesh, dp=dp, tp=tp)
        return Cell(
            arch_id=cfg.name, shape_name=shape, fn=fn,
            args=(params, opt_state, batch_sds),
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
            model_flops=6.0 * n_active * batch * seq,
        )

    if info["kind"] == "prefill":
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        return Cell(
            arch_id=cfg.name, shape_name=shape,
            fn=lambda params, tokens: T.prefill(params, tokens, cfg,
                                                mesh=mesh, dp=dp, tp=tp),
            args=(params, tokens),
            in_shardings=(psh, NamedSharding(mesh, P(dp, None))),
            model_flops=2.0 * n_active * batch * seq,
        )

    # decode: one new token against a seq-long cache
    cache = T.make_cache(cfg, batch, seq, abstract=True)
    csh = _shard_tree(mesh, T.cache_specs(cfg, dp=dp))
    token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    cache_len = jax.ShapeDtypeStruct((), jnp.int32)
    fn = lambda params, token, cache, cache_len: T.serve_step(
        params, token, cache, cache_len, cfg, mesh=mesh, dp=dp)
    return Cell(
        arch_id=cfg.name, shape_name=shape, fn=fn,
        args=(params, token, cache, cache_len),
        in_shardings=(psh, NamedSharding(mesh, P(dp, None)), csh,
                      NamedSharding(mesh, P())),
        out_shardings=(None, csh),
        donate_argnums=(2,),
        model_flops=2.0 * n_active * batch,  # one token per sequence
    )


# ============================================================ RecSys family
RECSYS_SHAPES = {
    "train_batch": {"kind": "train", "batch": 65536},
    "serve_p99": {"kind": "serve", "batch": 512},
    "serve_bulk": {"kind": "serve", "batch": 262144},
    "retrieval_cand": {"kind": "retrieval", "batch": 1,
                       "candidates": 1_000_000},
}


def recsys_dedup_cap(c: R.RecsysConfig, n_rows_per_field: int,
                     seq_rows: int = 0) -> int:
    """Exact upper bound on unique ids: sum over fields of min(B, vocab)."""
    cap = sum(min(n_rows_per_field, v) for v in c.vocab_sizes)
    cap += min(seq_rows, c.vocab_sizes[c.item_field])
    return int(cap)


def recsys_batch_sds(c: R.RecsysConfig, batch: int) -> Dict[str, Any]:
    sds = {
        "sparse": jax.ShapeDtypeStruct((batch, c.n_sparse), jnp.int32),
        "label": jax.ShapeDtypeStruct((batch,), jnp.float32),
    }
    if c.n_dense:
        sds["dense"] = jax.ShapeDtypeStruct((batch, c.n_dense), jnp.float32)
    if c.kind == "bst":
        sds["seq"] = jax.ShapeDtypeStruct((batch, c.seq_len), jnp.int32)
    return sds


def recsys_dense_flops(c: R.RecsysConfig) -> float:
    """Per-example dense-net forward FLOPs (2·params of the towers)."""
    n = 0.0
    for name, s in R.param_shapes(c).items():
        if name != "embed" and len(s) == 2:
            n += float(np.prod(s))
    return 2.0 * n


def recsys_cell(cfg: R.RecsysConfig, shape: str, mesh: Mesh, *,
                variant: str = "base") -> Cell:
    info = RECSYS_SHAPES[shape]
    dp = dp_axes_for(mesh)
    all_axes = dp + ("model",)
    flags = set(variant.split("+"))
    unknown = flags - {"base", "nodedup", "cap_expected", "batchall", "hierdedup"}
    if unknown:
        raise ValueError(f"unknown recsys variant parts {unknown}")
    if "nodedup" in flags:
        cfg = dataclasses.replace(cfg, dedup_lookup=False)
    batch_axes = all_axes if "batchall" in flags else dp

    batch = info.get("batch", 1)
    seq_rows = batch * (cfg.seq_len + 1) if cfg.kind == "bst" else 0
    if "cap_expected" in flags:
        # expected-unique capacity (x1.15 safety) instead of the worst-case
        # sum(min(B, v)) — the same E[unique] model the streaming driver's
        # dedup_capacity_hint(mode="expected") uses
        from repro.embedding.dedup import expected_unique
        exp = sum(expected_unique(batch, v) for v in cfg.vocab_sizes)
        if cfg.kind == "bst":
            exp += expected_unique(seq_rows, cfg.vocab_sizes[cfg.item_field])
        cap = int(exp * 1.15)
    else:
        cap = recsys_dedup_cap(cfg, batch, seq_rows)
    # round capacity to device-count multiple for clean sharding
    nd = int(np.prod(list(mesh.shape.values())))
    cap = (cap + nd - 1) // nd * nd
    cfg = dataclasses.replace(cfg, dedup_capacity=cap)

    params = R.abstract_params(cfg)
    pspecs = R.param_specs(cfg, dp=dp)
    psh = _shard_tree(mesh, pspecs)
    flops1 = recsys_dense_flops(cfg)

    if info["kind"] == "train":
        sds = recsys_batch_sds(cfg, batch)
        bsh = {k: NamedSharding(mesh, P(batch_axes) if v.ndim == 1
                                else P(batch_axes, None))
               for k, v in sds.items()}
        if "nodedup" in flags:
            # pre-FeatureBox baseline: dense embedding grads + full-table
            # optimizer state/update (what [37]'s working-set scheme removes)
            optimizer = opt_lib.adamw(1e-3)
            step = R.make_train_step(cfg, optimizer)
            opt_state = optimizer.abstract_state(params)
            osh = {"m": psh, "v": psh, "step": NamedSharding(mesh, P())}
        else:
            dense_opt = opt_lib.adamw(1e-3)
            hier_kw = {}
            if "hierdedup" in flags:
                n_shards = int(np.prod([mesh.shape[a] for a in batch_axes]))
                b_loc = batch // n_shards
                seq_loc = b_loc * (cfg.seq_len + 1) if cfg.kind == "bst" else 0
                local_cap = recsys_dedup_cap(cfg, b_loc, seq_loc)
                hier_kw = {"mesh": mesh, "batch_axes": batch_axes,
                           "local_dedup_capacity": local_cap}
            step, init_st, abstract_st = R.make_sparse_train_step(
                cfg, dense_opt, **hier_kw)
            opt_state = abstract_st(params)
            dense_psh = {k: v for k, v in psh.items() if k != "embed"}
            osh = {
                "dense": {
                    "m": dense_psh, "v": dense_psh,
                    "step": NamedSharding(mesh, P()),
                },
                "embed_accum": NamedSharding(mesh, P(all_axes)),
            }
        return Cell(
            arch_id=cfg.name, shape_name=shape, fn=step,
            args=(params, opt_state, sds),
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, None),
            donate_argnums=(0, 1),
            model_flops=6.0 * flops1 / 2.0 * batch,  # 3x fwd cost, fwd=2*p
        )

    if info["kind"] == "serve":
        sds = recsys_batch_sds(cfg, batch)
        sds.pop("label")
        bsh = {k: NamedSharding(mesh, P(batch_axes) if v.ndim == 1
                                else P(batch_axes, None))
               for k, v in sds.items()}
        fn = lambda params, batch_: R.serve_step(params, cfg, batch_)
        return Cell(
            arch_id=cfg.name, shape_name=shape, fn=fn,
            args=(params, sds),
            in_shardings=(psh, bsh),
            model_flops=flops1 * batch,
        )

    # retrieval: one user, 10^6 candidates (candidate axis sharded over dp)
    n_cand = info["candidates"]
    cfg = dataclasses.replace(
        cfg, dedup_capacity=recsys_dedup_cap(cfg, 1, seq_rows) + min(
            n_cand, cfg.vocab_sizes[cfg.item_field]))
    user = recsys_batch_sds(cfg, 1)
    user.pop("label")
    ush = {k: NamedSharding(mesh, P(None) if v.ndim == 1 else P(None, None))
           for k, v in user.items()}
    cands = jax.ShapeDtypeStruct((n_cand,), jnp.int32)
    fn = lambda params, user_, cands_: R.retrieval_score(params, cfg, user_, cands_)
    return Cell(
        arch_id=cfg.name, shape_name=shape, fn=fn,
        args=(params, user, cands),
        in_shardings=(psh, ush, NamedSharding(mesh, P(dp))),
        model_flops=flops1 * n_cand,
    )


# =============================================================== GNN family
GNN_SHAPES = {
    "full_graph_sm": {"kind": "full", "n_nodes": 2708, "n_edges": 10556,
                      "d_feat": 1433, "n_classes": 7},
    "minibatch_lg": {"kind": "sampled", "seeds": 1024, "fanout": (15, 10),
                     "d_feat": 602, "n_classes": 41},
    "ogb_products": {"kind": "full", "n_nodes": 2449029, "n_edges": 61859140,
                     "d_feat": 100, "n_classes": 47},
    "molecule": {"kind": "graphs", "n_graphs": 128, "nodes_per": 30,
                 "edges_per": 64, "d_feat": 28, "n_classes": 2},
}


def gnn_config_for(base_name: str, shape: str, *, n_layers=4, d_hidden=75) -> G.PNAConfig:
    info = GNN_SHAPES[shape]
    return G.PNAConfig(
        name=f"{base_name}", n_layers=n_layers, d_in=info["d_feat"],
        d_hidden=d_hidden, n_classes=info["n_classes"],
        graph_level=(info["kind"] == "graphs"),
    )


def gnn_cell(base_name: str, shape: str, mesh: Mesh, *, variant: str = "base") -> Cell:
    info = GNN_SHAPES[shape]
    dp = dp_axes_for(mesh)
    all_axes = dp + ("model",)
    cfg = gnn_config_for(base_name, shape)
    if variant == "halo_bf16":
        cfg = dataclasses.replace(cfg, halo_bf16=True)
    elif variant != "base":
        raise ValueError(f"unknown gnn variant {variant!r}")
    params = G.abstract_params(cfg)
    psh = _shard_tree(mesh, G.param_specs(cfg))

    if info["kind"] == "sampled":
        n_nodes = info["seeds"] * (1 + info["fanout"][0] * (1 + info["fanout"][1]))
        n_edges = info["seeds"] * info["fanout"][0] * (1 + info["fanout"][1])
    elif info["kind"] == "graphs":
        n_nodes = info["n_graphs"] * info["nodes_per"]
        n_edges = info["n_graphs"] * info["edges_per"]
    else:
        n_nodes, n_edges = info["n_nodes"], info["n_edges"]
    # pad the edge list to a device-count multiple: padding edges carry
    # dst = n_nodes (out of range), which segment ops drop — zero contribution
    nd = int(np.prod(list(mesh.shape.values())))
    n_edges = (n_edges + nd - 1) // nd * nd

    # node tensors: replicate small graphs; shard (and pad) big ones —
    # the (N, 12D) PNA aggregates replicated are ~9 GB/layer at ogb scale
    shard_nodes = n_nodes > 100_000
    node_axes = all_axes if shard_nodes else None
    if shard_nodes:
        n_nodes = (n_nodes + nd - 1) // nd * nd
    node_spec = P(all_axes, None) if shard_nodes else P(None, None)
    node_spec1 = P(all_axes) if shard_nodes else P(None)

    sds = {
        "features": jax.ShapeDtypeStruct((n_nodes, info["d_feat"]), jnp.float32),
        "src": jax.ShapeDtypeStruct((n_edges,), jnp.int32),
        "dst": jax.ShapeDtypeStruct((n_edges,), jnp.int32),
    }
    bsh = {
        "features": NamedSharding(mesh, node_spec),
        "src": NamedSharding(mesh, P(all_axes)),          # edges sharded
        "dst": NamedSharding(mesh, P(all_axes)),
    }
    if info["kind"] == "graphs":
        sds["graph_ids"] = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
        sds["labels"] = jax.ShapeDtypeStruct((info["n_graphs"],), jnp.int32)
        bsh["graph_ids"] = NamedSharding(mesh, P(None))
        bsh["labels"] = NamedSharding(mesh, P(None))
    else:
        sds["labels"] = jax.ShapeDtypeStruct((n_nodes,), jnp.int32)
        bsh["labels"] = NamedSharding(mesh, node_spec1)
        if shard_nodes:  # padded nodes are masked out of the loss
            sds["label_mask"] = jax.ShapeDtypeStruct((n_nodes,), jnp.float32)
            bsh["label_mask"] = NamedSharding(mesh, node_spec1)

    optimizer = opt_lib.adamw(1e-3)
    opt_state = optimizer.abstract_state(params)
    osh = {
        "m": psh, "v": psh, "step": NamedSharding(mesh, P()),
    }

    step_fn = G.make_train_step(cfg, optimizer, mesh=mesh, node_axes=node_axes)
    if info["kind"] == "graphs":
        def fn(params, opt_state, batch):
            batch = dict(batch)
            batch["n_graphs"] = info["n_graphs"]
            return step_fn(params, opt_state, batch)
    else:
        fn = step_fn

    # model flops: messages/updates dominate — 2 flops per weight per unit
    per_edge = 2.0 * 2 * cfg.d_hidden * cfg.d_hidden          # msg MLP
    per_node = 2.0 * (cfg.d_hidden * 13) * cfg.d_hidden       # update MLP
    fwd = cfg.n_layers * (per_edge * n_edges + per_node * n_nodes)
    return Cell(
        arch_id=base_name, shape_name=shape, fn=fn,
        args=(params, opt_state, sds),
        in_shardings=(psh, osh, bsh),
        out_shardings=(psh, osh, None),
        donate_argnums=(0, 1),
        model_flops=3.0 * fwd,
    )
