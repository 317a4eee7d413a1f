"""CTR/recsys models on the embedding substrate: DLRM, DCN-v2, AutoInt, BST.

These are the paper's own workload class (FeatureBox trains CTR models with
10^12-dim sparse inputs on a hierarchical GPU parameter server). All four
share:

* one packed :class:`~repro.embedding.table.MultiTable` for all sparse fields
  (rows sharded over the flattened ('data','model') axes at scale);
* ``lookup_dedup`` (the working-set path) or plain ``lookup`` — switchable so
  §Perf can measure the dedup win;
* sigmoid BCE training, batched serving, and a vectorized 10^6-candidate
  retrieval scorer (batched dot, not a loop).

The DLRM pairwise-dot interaction has a Pallas kernel
(``kernels/interaction_dot``) used on TPU; under dry-run/pjit the pure-jnp
form (same math) lowers through XLA.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.embedding.table import MultiTable, TableSpec, lookup, lookup_dedup
from repro.obs import phases
from repro.models.common import (
    dense as dense_layer,
    he_init,
    layer_norm,
    mlp,
    sigmoid_bce,
)

Params = Dict[str, Any]

# MLPerf DLRM (Criteo 1TB) per-field vocabulary sizes [arXiv:1906.00091].
CRITEO_1TB_VOCABS: Tuple[int, ...] = (
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36,
)


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                       # "dlrm" | "dcnv2" | "autoint" | "bst"
    n_dense: int
    n_sparse: int
    embed_dim: int
    vocab_sizes: Tuple[int, ...]
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    n_cross_layers: int = 0
    n_attn_layers: int = 0
    n_heads: int = 0
    d_attn: int = 0
    seq_len: int = 0                # BST behavior-sequence length
    n_blocks: int = 0               # BST transformer blocks
    dtype: Any = jnp.float32
    dedup_lookup: bool = True       # FeatureBox working-set path
    dedup_capacity: int = 0         # 0 -> batch*fields (safe upper bound)
    # which sparse field holds the candidate item (retrieval scoring)
    item_field: int = 0
    # physical row padding so the packed table shards evenly on any mesh
    row_align: int = 512

    def multi_table(self) -> MultiTable:
        specs = [TableSpec(f"f{i}", v, self.embed_dim)
                 for i, v in enumerate(self.vocab_sizes)]
        return MultiTable.build(specs)

    @property
    def padded_rows(self) -> int:
        rows = self.multi_table().total_rows
        return (rows + self.row_align - 1) // self.row_align * self.row_align


# ------------------------------------------------------------------ params
def _mlp_shapes(dims: Sequence[int], d_in: int, prefix: str) -> Dict[str, Tuple[int, ...]]:
    shapes = {}
    prev = d_in
    for i, d in enumerate(dims):
        shapes[f"{prefix}_w{i}"] = (prev, d)
        shapes[f"{prefix}_b{i}"] = (d,)
        prev = d
    return shapes


def param_shapes(c: RecsysConfig) -> Dict[str, Tuple[int, ...]]:
    shapes: Dict[str, Tuple[int, ...]] = {"embed": (c.padded_rows, c.embed_dim)}
    if c.kind == "dlrm":
        shapes.update(_mlp_shapes(c.bot_mlp, c.n_dense, "bot"))
        n_fields = c.n_sparse + 1
        d_inter = n_fields * (n_fields - 1) // 2 + c.bot_mlp[-1]
        shapes.update(_mlp_shapes(c.top_mlp, d_inter, "top"))
    elif c.kind == "dcnv2":
        d0 = c.n_dense + c.n_sparse * c.embed_dim
        for i in range(c.n_cross_layers):
            shapes[f"cross_w{i}"] = (d0, d0)
            shapes[f"cross_b{i}"] = (d0,)
        shapes.update(_mlp_shapes(tuple(c.top_mlp) + (1,), d0, "deep"))
    elif c.kind == "autoint":
        d = c.embed_dim
        for i in range(c.n_attn_layers):
            d_out = c.d_attn * c.n_heads
            shapes[f"attn{i}_wq"] = (d, d_out)
            shapes[f"attn{i}_wk"] = (d, d_out)
            shapes[f"attn{i}_wv"] = (d, d_out)
            shapes[f"attn{i}_wres"] = (d, d_out)
            d = d_out
        shapes["out_w"] = (c.n_sparse * d, 1)
        shapes["out_b"] = (1,)
    elif c.kind == "bst":
        d = c.embed_dim
        shapes["pos_embed"] = (c.seq_len + 1, d)
        for i in range(c.n_blocks):
            shapes[f"blk{i}_wq"] = (d, d)
            shapes[f"blk{i}_wk"] = (d, d)
            shapes[f"blk{i}_wv"] = (d, d)
            shapes[f"blk{i}_wo"] = (d, d)
            shapes[f"blk{i}_ln1_w"] = (d,)
            shapes[f"blk{i}_ln1_b"] = (d,)
            shapes[f"blk{i}_ffn_w1"] = (d, 4 * d)
            shapes[f"blk{i}_ffn_b1"] = (4 * d,)
            shapes[f"blk{i}_ffn_w2"] = (4 * d, d)
            shapes[f"blk{i}_ffn_b2"] = (d,)
            shapes[f"blk{i}_ln2_w"] = (d,)
            shapes[f"blk{i}_ln2_b"] = (d,)
        d_in = (c.seq_len + 1) * d + (c.n_sparse - 1) * d
        shapes.update(_mlp_shapes(tuple(c.top_mlp) + (1,), d_in, "top"))
    else:
        raise ValueError(f"unknown recsys kind {c.kind!r}")
    return shapes


def abstract_params(c: RecsysConfig) -> Params:
    return {k: jax.ShapeDtypeStruct(s, c.dtype) for k, s in param_shapes(c).items()}


def init_params(c: RecsysConfig, key: jax.Array, *,
                include_embed: bool = True) -> Params:
    """Materialize params. ``include_embed=False`` skips the embedding table
    (the hierarchical-PS path keeps it on SSD/host, never in device memory)
    while leaving every dense param bitwise identical to the full init —
    the fold_in indices are enumeration positions over the *full* shape
    dict, not the filtered one."""
    params: Params = {}
    for i, (name, shape) in enumerate(param_shapes(c).items()):
        if name == "embed" and not include_embed:
            continue
        k = jax.random.fold_in(key, i)
        if name == "embed":
            scale = 1.0 / np.sqrt(c.embed_dim)
            params[name] = jax.random.uniform(k, shape, c.dtype, -scale, scale)
        elif name.endswith(tuple(f"_b{j}" for j in range(10))) or name.endswith("_b"):
            params[name] = jnp.zeros(shape, c.dtype)
        elif "ln" in name and name.endswith("_w"):
            params[name] = jnp.ones(shape, c.dtype)
        elif "ln" in name and name.endswith("_b"):
            params[name] = jnp.zeros(shape, c.dtype)
        elif len(shape) == 1:
            params[name] = jnp.zeros(shape, c.dtype)
        else:
            params[name] = he_init(k, shape, c.dtype)
    return params


def param_specs(c: RecsysConfig, *, dp: Tuple[str, ...] = ("data",), tp: str = "model"):
    """Embedding rows sharded over every device; small dense nets replicated."""
    specs = {}
    for name, shape in param_shapes(c).items():
        if name == "embed":
            specs[name] = P(dp + (tp,), None)
        else:
            specs[name] = P(*(None,) * len(shape))
    return specs


# ----------------------------------------------------------------- lookups
def collect_gids(c: RecsysConfig, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """All packed global row ids this batch will look up, keyed by site.

    Shared by the in-graph lookup paths and by the sparse working-set train
    step (which gathers the working set OUTSIDE the differentiated region —
    the hierarchical-PS training scheme of [37]/FeatureBox).
    """
    mt = c.multi_table()
    gids: Dict[str, jax.Array] = {}
    if c.kind == "bst":
        seq_plus = jnp.concatenate(
            [batch["seq"], batch["sparse"][:, c.item_field][:, None]], axis=1)
        gids["seq"] = seq_plus.astype(jnp.int32) + int(mt.offsets[c.item_field])
        other = jnp.delete(batch["sparse"], c.item_field, axis=1,
                           assume_unique_indices=True)
        other_offs = jnp.asarray(
            np.delete(np.asarray(mt.offsets), c.item_field), jnp.int32)
        gids["other"] = other.astype(jnp.int32) + other_offs[None, :]
    else:
        gids["sparse"] = mt.global_ids(batch["sparse"])
    return gids


def _embed_fields(params: Params, c: RecsysConfig, field_ids: jax.Array,
                  mt: MultiTable) -> jax.Array:
    """(B, F) per-field ids -> (B, F, D) rows via packed global ids."""
    gids = mt.global_ids(field_ids)
    if c.dedup_lookup:
        cap = c.dedup_capacity or int(np.prod(gids.shape))
        return lookup_dedup(params["embed"], gids, capacity=cap)
    return lookup(params["embed"], gids)


# ----------------------------------------------------------------- forward
def _dlrm_forward(params, c, batch, mt):
    dense_x = batch["dense"].astype(c.dtype)
    emb = batch.get("_rows_sparse")
    if emb is None:
        emb = _embed_fields(params, c, batch["sparse"], mt)      # (B, F, D)
    n_bot = len(c.bot_mlp)
    bot = mlp(dense_x,
              [params[f"bot_w{i}"] for i in range(n_bot)],
              [params[f"bot_b{i}"] for i in range(n_bot)],
              act=jax.nn.relu, final_act=jax.nn.relu)            # (B, D)
    fields = jnp.concatenate([bot[:, None, :], emb], axis=1)     # (B, F+1, D)
    f = fields.shape[1]
    scores = jnp.einsum("bfd,bgd->bfg", fields, fields)
    rows, cols = np.tril_indices(f, k=-1)
    inter = scores[:, rows, cols]                                # (B, P)
    top_in = jnp.concatenate([bot, inter], axis=1)
    n_top = len(c.top_mlp)
    logit = mlp(top_in,
                [params[f"top_w{i}"] for i in range(n_top)],
                [params[f"top_b{i}"] for i in range(n_top)])
    return logit[:, 0]


def _dcnv2_forward(params, c, batch, mt):
    emb = batch.get("_rows_sparse")
    if emb is None:
        emb = _embed_fields(params, c, batch["sparse"], mt)
    b = emb.shape[0]
    x0 = jnp.concatenate([batch["dense"].astype(c.dtype), emb.reshape(b, -1)], axis=1)
    x = x0
    for i in range(c.n_cross_layers):
        xw = dense_layer(x, params[f"cross_w{i}"], params[f"cross_b{i}"])
        x = x0 * xw + x                                           # DCN-v2 cross
    n_deep = len(c.top_mlp) + 1
    logit = mlp(x,
                [params[f"deep_w{i}"] for i in range(n_deep)],
                [params[f"deep_b{i}"] for i in range(n_deep)])
    return logit[:, 0]


def _autoint_forward(params, c, batch, mt):
    emb = batch.get("_rows_sparse")
    if emb is None:
        emb = _embed_fields(params, c, batch["sparse"], mt)      # (B, F, D)
    x = emb
    for i in range(c.n_attn_layers):
        q = dense_layer(x, params[f"attn{i}_wq"])
        k = dense_layer(x, params[f"attn{i}_wk"])
        v = dense_layer(x, params[f"attn{i}_wv"])
        b, f, _ = q.shape
        qh = q.reshape(b, f, c.n_heads, c.d_attn)
        kh = k.reshape(b, f, c.n_heads, c.d_attn)
        vh = v.reshape(b, f, c.n_heads, c.d_attn)
        scores = jnp.einsum("bfhd,bghd->bhfg", qh, kh) / np.sqrt(c.d_attn)
        attn = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhfg,bghd->bfhd", attn, vh).reshape(b, f, -1)
        x = jax.nn.relu(out + dense_layer(x, params[f"attn{i}_wres"]))
    b = x.shape[0]
    logit = dense_layer(x.reshape(b, -1), params["out_w"], params["out_b"])
    return logit[:, 0]


def _bst_forward(params, c, batch, mt):
    # sparse field 0 = target item; remaining fields = user/context features
    seq = batch["seq"]                                            # (B, L) item ids
    target = batch["sparse"][:, c.item_field]
    other = jnp.delete(batch["sparse"], c.item_field, axis=1,
                       assume_unique_indices=True)
    b, l = seq.shape
    # behavior sequence + target share the item table (field 0 id space)
    x = batch.get("_rows_seq")
    if x is None:
        seq_plus = jnp.concatenate([seq, target[:, None]], axis=1)  # (B, L+1)
        gids = seq_plus.astype(jnp.int32) + int(mt.offsets[c.item_field])
        if c.dedup_lookup:
            cap = c.dedup_capacity or int(np.prod(gids.shape))
            x = lookup_dedup(params["embed"], gids, capacity=cap)
        else:
            x = lookup(params["embed"], gids)                     # (B, L+1, D)
    x = x.astype(c.dtype) + params["pos_embed"][None, :, :].astype(c.dtype)
    for i in range(c.n_blocks):
        q = dense_layer(x, params[f"blk{i}_wq"])
        k = dense_layer(x, params[f"blk{i}_wk"])
        v = dense_layer(x, params[f"blk{i}_wv"])
        d_h = c.embed_dim // c.n_heads
        qh = q.reshape(b, l + 1, c.n_heads, d_h)
        kh = k.reshape(b, l + 1, c.n_heads, d_h)
        vh = v.reshape(b, l + 1, c.n_heads, d_h)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(d_h)
        attn = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", attn, vh).reshape(b, l + 1, -1)
        h = layer_norm(x + dense_layer(out, params[f"blk{i}_wo"]),
                       params[f"blk{i}_ln1_w"], params[f"blk{i}_ln1_b"])
        ff = dense_layer(
            jax.nn.relu(dense_layer(h, params[f"blk{i}_ffn_w1"], params[f"blk{i}_ffn_b1"])),
            params[f"blk{i}_ffn_w2"], params[f"blk{i}_ffn_b2"])
        x = layer_norm(h + ff, params[f"blk{i}_ln2_w"], params[f"blk{i}_ln2_b"])
    other_emb = batch.get("_rows_other")
    if other_emb is None:
        other_offs = jnp.asarray(np.delete(np.asarray(mt.offsets), c.item_field),
                                 jnp.int32)
        other_gids = other.astype(jnp.int32) + other_offs[None, :]
        if c.dedup_lookup:
            cap = c.dedup_capacity or int(np.prod(other_gids.shape))
            other_emb = lookup_dedup(params["embed"], other_gids, capacity=cap)
        else:
            other_emb = lookup(params["embed"], other_gids)       # (B, F-1, D)
    feat = jnp.concatenate([x.reshape(b, -1), other_emb.reshape(b, -1)], axis=1)
    n_top = len(c.top_mlp) + 1
    logit = mlp(feat,
                [params[f"top_w{i}"] for i in range(n_top)],
                [params[f"top_b{i}"] for i in range(n_top)])
    return logit[:, 0]


_FORWARDS: Dict[str, Callable] = {
    "dlrm": _dlrm_forward,
    "dcnv2": _dcnv2_forward,
    "autoint": _autoint_forward,
    "bst": _bst_forward,
}


def forward(params: Params, c: RecsysConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    """Batch -> CTR logits (B,)."""
    return _FORWARDS[c.kind](params, c, batch, c.multi_table())


def loss_fn(params: Params, c: RecsysConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    logits = forward(params, c, batch)
    return sigmoid_bce(logits, batch["label"]).mean()


def make_train_step(c: RecsysConfig, optimizer):
    """Dense train step: differentiates the whole tree (small-table path)."""
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, c, batch))(params)
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, {"loss": loss}
    return train_step


def make_sparse_train_step(c: RecsysConfig, dense_optimizer, *,
                           embed_lr: float = 0.01, embed_eps: float = 1e-10,
                           mesh=None, batch_axes=None,
                           local_dedup_capacity: int = 0):
    """Hierarchical-PS train step ([37]/FeatureBox): working-set embeddings.

    1. dedup the batch's global ids into a fixed working set (OUTSIDE grad);
    2. gather working rows + their Adagrad accumulators (the only table
       traffic — proportional to unique ids, not batch x fields x dim);
    3. differentiate w.r.t. (working rows, dense params);
    4. Adagrad the working rows, Adam/whatever the dense params;
    5. scatter updated rows + accumulators back.

    The optimizer state carries a per-row Adagrad accumulator ``embed_accum``
    (f32[V_total]) next to the dense optimizer's state.
    """
    from repro.embedding.dedup import dedup

    def init(params):
        dense_params = {k: v for k, v in params.items() if k != "embed"}
        return {
            "dense": dense_optimizer.init(dense_params),
            "embed_accum": jnp.full((params["embed"].shape[0],), 0.1, jnp.float32),
        }

    def abstract_state(params):
        dense_params = {k: v for k, v in params.items() if k != "embed"}
        return {
            "dense": dense_optimizer.abstract_state(dense_params),
            "embed_accum": jax.ShapeDtypeStruct((params["embed"].shape[0],), jnp.float32),
        }

    def train_step(params, opt_state, batch):
        with jax.named_scope(phases.DEDUP):
            gids = collect_gids(c, batch)
            sites = sorted(gids.keys())
            flat_all = jnp.concatenate([gids[s].reshape(-1) for s in sites])
            cap = c.dedup_capacity or int(flat_all.shape[0])
            if mesh is not None and batch_axes is not None and local_dedup_capacity:
                # two-stage dedup: shrink the globally-sorted pool (§Perf pair 1)
                from repro.embedding.dedup import dedup_hierarchical
                unique, inverse, n_unique = dedup_hierarchical(
                    flat_all, capacity=cap, mesh=mesh, axes=batch_axes,
                    local_capacity=local_dedup_capacity)
            else:
                unique, inverse, n_unique = dedup(flat_all, capacity=cap)
        with jax.named_scope(phases.LOOKUP):
            safe = jnp.where(unique == jnp.int32(2**31 - 1), 0, unique)
            working = jnp.take(params["embed"], safe, axis=0)    # (cap, D)

        # split inverse back per call site
        inv_by_site = {}
        off = 0
        with jax.named_scope(phases.DEDUP):
            for s in sites:
                n = int(np.prod(gids[s].shape))
                inv_by_site[s] = inverse[off: off + n].reshape(gids[s].shape)
                off += n

        dense_params = {k: v for k, v in params.items() if k != "embed"}

        def local_loss(dense_p, working_rows):
            rows = {f"_rows_{s}": jnp.take(working_rows, inv_by_site[s], axis=0)
                    for s in sites}
            b2 = dict(batch)
            b2.update(rows)
            p2 = dict(dense_p)
            p2["embed"] = params["embed"]  # untouched by grad (rows injected)
            logits = forward(p2, c, b2)
            return sigmoid_bce(logits, batch["label"]).mean()

        with jax.named_scope(phases.FWD_BWD):
            loss, (gd, gw) = jax.value_and_grad(local_loss, argnums=(0, 1))(
                dense_params, working)

        with jax.named_scope(phases.DENSE_UPDATE):
            new_dense, new_dense_state = dense_optimizer.update(
                dense_params, gd, opt_state["dense"])

        # Adagrad on working rows only
        with jax.named_scope(phases.EMBED_UPDATE):
            gw = gw.astype(jnp.float32)
            valid = (unique != jnp.int32(2**31 - 1)).astype(jnp.float32)[:, None]
            gw = gw * valid
            gsq = jnp.sum(gw * gw, axis=-1)
            accum_rows = jnp.take(opt_state["embed_accum"], safe) + gsq
            scale = embed_lr / (jnp.sqrt(accum_rows) + embed_eps)
            new_rows = (working.astype(jnp.float32) - scale[:, None] * gw)
            # mode="drop": FILL ids are out of bounds, so padded slots write
            # nothing. Scattering via ``safe`` would alias every pad slot onto
            # row 0 and could clobber row 0's real update (duplicate-index
            # scatter order is unspecified) — observed as a one-row divergence
            # from the hierarchical-PS path, which pads host-side and never
            # pushes pad slots.
            embed = params["embed"].at[unique].set(
                new_rows.astype(params["embed"].dtype), mode="drop")
            accum = opt_state["embed_accum"].at[unique].set(accum_rows, mode="drop")

        new_params = dict(new_dense)
        new_params["embed"] = embed
        # "unique"/"n_ids" feed the train-feed tier's dedup accounting
        # (TrainFeedStats.unique_ratio: collective traffic is proportional
        # to unique, not batch x fields — the [37]/FeatureBox win).
        metrics = {"loss": loss, "unique": n_unique,
                   "n_ids": jnp.int32(flat_all.shape[0])}
        return new_params, {"dense": new_dense_state, "embed_accum": accum}, metrics

    return train_step, init, abstract_state


def dense_param_elems(c: RecsysConfig) -> int:
    """Total element count of the dense (non-embedding) parameter tree —
    the gradient volume the mesh step's cross-pod all-reduce carries."""
    return int(sum(np.prod(s) for k, s in param_shapes(c).items()
                   if k != "embed"))


def batch_id_count(c: RecsysConfig, rows: int) -> int:
    """Flat id count :func:`collect_gids` yields for ``rows`` examples
    (the comm plan's per-device raw-id volume)."""
    if c.kind == "bst":
        return rows * (c.seq_len + 1) + rows * (c.n_sparse - 1)
    return rows * c.n_sparse


def make_mesh_train_step(c: RecsysConfig, dense_optimizer, *,
                         mesh, embed_lr: float = 0.01,
                         embed_eps: float = 1e-10,
                         local_dedup_capacity: int = 0,
                         compress: Any = None, hierarchical: bool = True,
                         pod_axis: str = "pod", data_axis: str = "data"):
    """Data-parallel working-set train step on a ('pod', 'data') mesh.

    The scale-out form of :func:`make_sparse_train_step` — same arithmetic,
    distributed per the FeatureBox authors' recipe (arXiv 2201.05500 +
    2003.05622): the packed table and its Adagrad accumulators are
    **row-sharded** over the flattened mesh (``P(('pod','data'), None)``),
    the batch is row-split the same way, and each device runs this body
    under ``shard_map``:

    1. **two-stage dedup** — local ``jnp.unique`` bounds the pooled sort to
       ``n_devices x local_capacity`` ids, then a global unique of the
       all-gathered pool; each id's working-set slot composes the two
       sorts' inverses, with no search over the global uniques
       (:func:`repro.embedding.dedup.dedup_two_stage_local`);
    2. **working-set exchange** — each device contributes the unique rows +
       accumulators it owns (out-of-shard slots zeroed), one fp32
       hierarchical reduction replicates the working set everywhere;
    3. forward/backward on the local batch rows against the replicated
       working set (identical ``local_loss`` to the single-device step);
    4. **gradient reduction** — working-set grads and the flattened dense
       grads each go through :func:`repro.train.compression.hierarchical_psum`
       (reduce-scatter in-pod, *compressed* wire + fp32 accumulation
       across pods, all-gather in-pod) or :func:`flat_psum` when
       ``hierarchical=False``. The dense reduction carries the codec's
       error-feedback residual in ``opt_state["comm_residual"]``
       (``f32[n_pods, padded_dense_elems]``, sharded so each device owns
       exactly its reduce-scattered shard's residual). Working-set grads
       are compressed statelessly: their slots map to *different* rows
       every step, so a carried residual would mix rows.
    5. replicated Adagrad on the working set, each device scattering back
       only the rows it owns (``mode="drop"``); dense update replicated.

    On a **1x1 mesh with compression off** every collective is an
    identity and every pad/slice is a no-op, so losses, params, and
    optimizer state are bitwise-identical to
    :func:`make_sparse_train_step` (asserted in ``tests/test_mesh.py``).
    ``metrics["local_unique"]`` adds the summed stage-1 unique counts (the
    pooled-exchange volume the ``comm`` tier reports).
    """
    from repro.embedding.dedup import FILL, dedup_two_stage_local
    from repro.train.compression import (
        codec_name, flat_psum, hierarchical_psum)

    axes = (pod_axis, data_axis)
    n_pods = int(mesh.shape[pod_axis])
    inner = int(mesh.shape[data_axis])
    n_dev = n_pods * inner
    codec = codec_name(compress)
    if c.padded_rows % n_dev:
        raise ValueError(
            f"padded table rows {c.padded_rows} do not shard evenly over "
            f"{n_dev} devices — raise RecsysConfig.row_align")

    n_dense = dense_param_elems(c)
    npad_dense = -(-n_dense // inner) * inner  # reduce-scatter granularity

    def _pad_to_inner(v):
        n = int(v.shape[0])
        npad = -(-n // inner) * inner
        if npad == n:
            return v
        return jnp.concatenate([v, jnp.zeros((npad - n,), v.dtype)])

    def _reduce(vec, *, codec=None, residual=None):
        """All-reduce a 1-D fp32 vector over the whole mesh."""
        if hierarchical:
            return hierarchical_psum(vec, pod_axis=pod_axis,
                                     inner_axis=data_axis,
                                     compress=codec, residual=residual)
        return flat_psum(vec, pod_axis=pod_axis, inner_axis=data_axis), residual

    def init(params):
        dense_params = {k: v for k, v in params.items() if k != "embed"}
        st = {
            "dense": dense_optimizer.init(dense_params),
            "embed_accum": jnp.full((params["embed"].shape[0],), 0.1,
                                    jnp.float32),
        }
        if codec is not None:
            st["comm_residual"] = jnp.zeros((n_pods, npad_dense), jnp.float32)
        return st

    def abstract_state(params):
        dense_params = {k: v for k, v in params.items() if k != "embed"}
        st = {
            "dense": dense_optimizer.abstract_state(dense_params),
            "embed_accum": jax.ShapeDtypeStruct((params["embed"].shape[0],),
                                                jnp.float32),
        }
        if codec is not None:
            st["comm_residual"] = jax.ShapeDtypeStruct(
                (n_pods, npad_dense), jnp.float32)
        return st

    def _device_step(params, opt_state, batch):
        embed_shard = params["embed"]                   # (rows/n_dev, D)
        accum_shard = opt_state["embed_accum"]          # (rows/n_dev,)
        shard_rows = int(embed_shard.shape[0])
        dense_params = {k: v for k, v in params.items() if k != "embed"}
        with jax.named_scope(phases.EXCHANGE):
            dev = (jax.lax.axis_index(pod_axis) * inner
                   + jax.lax.axis_index(data_axis))
            lo = dev * shard_rows                       # first owned row

        with jax.named_scope(phases.DEDUP):
            gids = collect_gids(c, batch)               # local batch shard
            sites = sorted(gids.keys())
            flat_local = jnp.concatenate([gids[s].reshape(-1) for s in sites])
            n_local = int(flat_local.shape[0])
            cap = c.dedup_capacity or n_local * n_dev
            local_cap = local_dedup_capacity or min(cap, n_local)
            if n_dev == 1:
                # stage 1 must never overflow when it IS the whole dedup
                local_cap = min(cap, n_local)

            unique, inverse, n_unique, local_count = dedup_two_stage_local(
                flat_local, capacity=cap, local_capacity=local_cap,
                gather_axes=axes)

        # -------- working-set exchange: each device contributes owned rows
        with jax.named_scope(phases.EXCHANGE):
            local_idx = unique - lo                     # FILL -> huge
            owned = (local_idx >= 0) & (local_idx < shard_rows)
            idx = jnp.clip(local_idx, 0, shard_rows - 1)
            contrib = jnp.where(owned[:, None],
                                jnp.take(embed_shard, idx, axis=0), 0.0)
            acc_contrib = jnp.where(owned, jnp.take(accum_shard, idx), 0.0)
            packed = jnp.concatenate([
                contrib.astype(jnp.float32).reshape(-1), acc_contrib])
            red, _ = _reduce(_pad_to_inner(packed))     # fp32, never quantized
            working = red[:cap * c.embed_dim].reshape(cap, c.embed_dim)
            accum_rows0 = red[cap * c.embed_dim: cap * c.embed_dim + cap]

        inv_by_site = {}
        off = 0
        with jax.named_scope(phases.DEDUP):
            for s in sites:
                n = int(np.prod(gids[s].shape))
                inv_by_site[s] = inverse.reshape(-1)[off: off + n].reshape(
                    gids[s].shape)
                off += n

        def local_loss(dense_p, working_rows):
            rows = {f"_rows_{s}": jnp.take(working_rows, inv_by_site[s],
                                           axis=0)
                    for s in sites}
            b2 = dict(batch)
            b2.update(rows)
            logits = forward(dict(dense_p), c, b2)
            return sigmoid_bce(logits, batch["label"]).mean()

        with jax.named_scope(phases.FWD_BWD):
            loss, (gd, gw) = jax.value_and_grad(local_loss, argnums=(0, 1))(
                dense_params, working.astype(c.dtype))

        # -------- gradient reduction (the compressed inter-pod wire)
        with jax.named_scope(phases.GRAD_REDUCE):
            gw = gw.astype(jnp.float32)
            valid = (unique != FILL).astype(jnp.float32)[:, None]
            gw = gw * valid
            # stateless codec: working-set slots alias different rows each step
            gw_red, _ = _reduce(_pad_to_inner(gw.reshape(-1)), codec=codec)
            gw = gw_red[:cap * c.embed_dim].reshape(cap, c.embed_dim)

            gd_leaves, gd_def = jax.tree.flatten(gd)
            gd_flat = jnp.concatenate(
                [l.reshape(-1).astype(jnp.float32) for l in gd_leaves])
            residual = (opt_state["comm_residual"][0]
                        if codec is not None else None)
            gd_red, new_residual = _reduce(_pad_to_inner(gd_flat), codec=codec,
                                           residual=residual)
            if n_dev > 1:
                inv_ndev = np.float32(1.0 / n_dev)
                loss = jax.lax.psum(loss, axes) * inv_ndev
                gw = gw * inv_ndev
                gd_red = gd_red * inv_ndev
                local_count = jax.lax.psum(local_count, axes)
            parts, off = [], 0
            for leaf in gd_leaves:
                n = int(np.prod(leaf.shape))
                parts.append(gd_red[off: off + n].reshape(leaf.shape)
                             .astype(leaf.dtype))
                off += n
            gd = jax.tree.unflatten(gd_def, parts)

        # -------- replicated updates, sharded write-back
        with jax.named_scope(phases.DENSE_UPDATE):
            new_dense, new_dense_state = dense_optimizer.update(
                dense_params, gd, opt_state["dense"])

        with jax.named_scope(phases.EMBED_UPDATE):
            gsq = jnp.sum(gw * gw, axis=-1)
            accum_rows = accum_rows0 + gsq
            scale = embed_lr / (jnp.sqrt(accum_rows) + embed_eps)
            new_rows = working - scale[:, None] * gw
            # scatter only the rows this shard owns; everything else (other
            # shards' rows AND FILL pad slots) routes out of bounds -> dropped
            target = jnp.where(owned, local_idx, shard_rows)
            embed_shard = embed_shard.at[target].set(
                new_rows.astype(embed_shard.dtype), mode="drop")
            accum_shard = accum_shard.at[target].set(accum_rows, mode="drop")

        new_params = dict(new_dense)
        new_params["embed"] = embed_shard
        new_opt = {"dense": new_dense_state, "embed_accum": accum_shard}
        if codec is not None:
            new_opt["comm_residual"] = new_residual[None]
        metrics = {"loss": loss, "unique": n_unique,
                   "n_ids": jnp.int32(n_local * n_dev),
                   "local_unique": local_count}
        return new_params, new_opt, metrics

    def train_step(params, opt_state, batch):
        rows = int(batch["label"].shape[0])
        if rows % n_dev:
            raise ValueError(
                f"batch of {rows} rows does not split over {n_dev} mesh "
                f"devices — pick a batch size divisible by the mesh")
        pspec = {k: (P(axes, None) if k == "embed" else P())
                 for k in params}
        ospec = {
            "dense": jax.tree.map(lambda _: P(), opt_state["dense"]),
            "embed_accum": P(axes),
        }
        if codec is not None:
            ospec["comm_residual"] = P(pod_axis, data_axis)
        bspec = {k: P(axes) for k in batch}
        mspec = {"loss": P(), "unique": P(), "n_ids": P(),
                 "local_unique": P()}
        fn = jax.shard_map(_device_step, mesh=mesh,
                           in_specs=(pspec, ospec, bspec),
                           out_specs=(pspec, ospec, mspec),
                           check_vma=False)
        return fn(params, opt_state, batch)

    return train_step, init, abstract_state


def train_state_shardings(mesh, params: Params, opt_state: Dict[str, Any], *,
                          pod_axis: str = "pod", data_axis: str = "data"):
    """The mesh step's sharding contract for (params, opt_state): embedding
    rows + Adagrad accumulators split over the flattened mesh, the dense
    tree replicated, the codec residual (when present) split so each device
    owns its reduce-scattered shard. Leaves may be arrays or shapes."""
    from jax.sharding import NamedSharding

    axes = (pod_axis, data_axis)

    def sh(spec):
        return NamedSharding(mesh, spec)

    p_sh = {k: sh(P(axes, None) if k == "embed" else P()) for k in params}
    o_sh: Dict[str, Any] = {
        "dense": jax.tree.map(lambda _: sh(P()), opt_state["dense"]),
        "embed_accum": sh(P(axes)),
    }
    if "comm_residual" in opt_state:
        o_sh["comm_residual"] = sh(P(pod_axis, data_axis))
    return p_sh, o_sh


def shard_train_state(mesh, params: Params, opt_state: Dict[str, Any], **kw):
    """Place existing (params, opt_state) per :func:`train_state_shardings`
    (the resume path: restored host arrays onto the current mesh)."""
    return jax.device_put((params, opt_state),
                          train_state_shardings(mesh, params, opt_state, **kw))


def init_train_state(c: RecsysConfig, key: jax.Array, init_opt: Callable, *,
                     mesh=None, include_embed: bool = True):
    """Materialize ``(params, init_opt(params))`` in one jit, born in place.

    One program keeps only its outputs and small temporaries live; eager
    init would dispatch op by op, and at published widths the table's
    random bits and their float copy would both sit on one device. With
    ``mesh`` the outputs are created in the :func:`train_state_shardings`
    layout, so no device ever holds the whole table. Threefry is
    partitionable, so the values equal the single-device init's.
    """
    def _init(k):
        params = init_params(c, k, include_embed=include_embed)
        return params, init_opt(params)

    if mesh is None:
        return jax.jit(_init)(key)
    shapes = jax.eval_shape(_init, key)
    return jax.jit(_init, out_shardings=train_state_shardings(
        mesh, *shapes))(key)


def gid_site_shapes(c: RecsysConfig, batch: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    """Shapes of :func:`collect_gids`'s per-site id arrays, without tracing
    the id arithmetic. Shared by the hierarchy train step (which splits a
    host-computed inverse back per site) and its host twin
    :func:`repro.embedding.psfeed.collect_gids_np` — the flat concat order
    is ``sorted(sites)`` in both."""
    if c.kind == "bst":
        b, l = batch["seq"].shape
        return {"other": (b, c.n_sparse - 1), "seq": (b, l + 1)}
    return {"sparse": tuple(batch["sparse"].shape)}


def make_hierarchy_train_step(c: RecsysConfig, dense_optimizer, *,
                              embed_lr: float = 0.01, embed_eps: float = 1e-10):
    """Working-set train step for the hierarchical PS backend.

    Same arithmetic as :func:`make_sparse_train_step`, but the working set
    arrives *in the batch* (pulled host-side by
    :class:`repro.embedding.psfeed.HierarchyFeed`) instead of being gathered
    from a device-resident table:

    * ``_ws_rows``    f32[cap, D]  pulled working rows (FILL slots padded);
    * ``_ws_accum``   f32[cap]     pulled Adagrad accumulators;
    * ``_ws_unique``  int32[cap]   unique global ids, FILL-padded;
    * ``_ws_inverse`` int32[N]     flat inverse over the sorted-site concat.

    ``params`` carries the dense tree only (no ``"embed"``); the updated
    rows/accumulators come back in the metrics (``ws_rows``/``ws_accum``)
    for the async write-back ``push()``. For valid (non-FILL) slots the
    loss and row updates are bitwise-identical to the in-memory step as
    long as the pulled rows/accumulators match the table — asserted in
    ``tests/test_hierarchy.py``.
    """
    FILL = jnp.int32(2**31 - 1)

    def init(params):
        return {"dense": dense_optimizer.init(params)}

    def abstract_state(params):
        return {"dense": dense_optimizer.abstract_state(params)}

    def train_step(params, opt_state, batch):
        working = batch["_ws_rows"]
        unique = batch["_ws_unique"]
        inverse = batch["_ws_inverse"]
        shapes = gid_site_shapes(c, batch)
        sites = sorted(shapes)

        inv_by_site = {}
        off = 0
        for s in sites:
            n = int(np.prod(shapes[s]))
            inv_by_site[s] = inverse[off: off + n].reshape(shapes[s])
            off += n

        def local_loss(dense_p, working_rows):
            rows = {f"_rows_{s}": jnp.take(working_rows, inv_by_site[s], axis=0)
                    for s in sites}
            b2 = dict(batch)
            b2.update(rows)
            logits = forward(dict(dense_p), c, b2)
            return sigmoid_bce(logits, batch["label"]).mean()

        loss, (gd, gw) = jax.value_and_grad(local_loss, argnums=(0, 1))(
            params, working)

        new_dense, new_dense_state = dense_optimizer.update(
            params, gd, opt_state["dense"])

        # Adagrad on working rows only (same math as the in-memory step;
        # padded FILL slots carry zero grads and keep their pulled values).
        gw = gw.astype(jnp.float32)
        valid = (unique != FILL).astype(jnp.float32)[:, None]
        gw = gw * valid
        gsq = jnp.sum(gw * gw, axis=-1)
        accum_rows = batch["_ws_accum"] + gsq
        scale = embed_lr / (jnp.sqrt(accum_rows) + embed_eps)
        new_rows = (working.astype(jnp.float32) - scale[:, None] * gw)
        new_rows = jnp.where(valid > 0, new_rows, working)

        metrics = {"loss": loss,
                   "unique": jnp.sum(unique != FILL).astype(jnp.int32),
                   "n_ids": jnp.int32(inverse.shape[0]),
                   "ws_rows": new_rows, "ws_accum": accum_rows}
        return new_dense, {"dense": new_dense_state}, metrics

    return train_step, init, abstract_state


def serve_step(params: Params, c: RecsysConfig, batch: Dict[str, jax.Array]) -> jax.Array:
    """Online/offline scoring: batch -> pCTR (B,)."""
    return jax.nn.sigmoid(forward(params, c, batch))


def retrieval_score(params: Params, c: RecsysConfig, user_batch: Dict[str, jax.Array],
                    candidate_ids: jax.Array) -> jax.Array:
    """Score ONE user context against many candidates (batched, no loop).

    User-side features (batch size 1) are broadcast across the candidate
    axis; the candidate id replaces the item field. This is full-model
    scoring at candidate batch size — the `retrieval_cand` shape.
    """
    n = candidate_ids.shape[0]
    batch: Dict[str, jax.Array] = {}
    for key, v in user_batch.items():
        if key == "label":
            continue
        batch[key] = jnp.broadcast_to(v, (n,) + v.shape[1:])
    sparse = batch["sparse"].at[:, c.item_field].set(candidate_ids.astype(jnp.int32))
    batch["sparse"] = sparse
    return jax.nn.sigmoid(forward(params, c, batch))
