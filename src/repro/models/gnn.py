"""PNA (Principal Neighbourhood Aggregation, arXiv:2004.05718).

Message passing is built on ``jax.ops.segment_sum/max/min`` over an
edge-index (src -> dst) scatter — JAX has no sparse SpMM path for this, so
the segment-op formulation IS the system (kernel taxonomy §GNN).

PNA layer (degree-general):
  m_ij   = M([h_i ; h_j])                      per-edge message (pre-MLP)
  agg    = [mean | max | min | std]_j m_ij     4 aggregators
  scaled = [agg ; agg*amp(d_i) ; agg*att(d_i)] 3 degree scalers
  h_i'   = U([h_i ; scaled])                   post-MLP update

Shapes served: full-graph training (Cora/ogbn-products scale), fanout-sampled
mini-batching (Reddit scale — the sampler is a host op in the FeatureBox
pipeline sense), and batched small molecule graphs (graph-level readout).

Distribution: edges sharded over all mesh axes; nodes replicated; each edge
shard scatter-adds into the full node accumulator and XLA all-reduces the
partials (DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import he_init, softmax_xent

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PNAConfig:
    name: str
    n_layers: int
    d_in: int
    d_hidden: int
    n_classes: int
    delta: float = 2.5          # avg log-degree normalizer (dataset statistic)
    graph_level: bool = False   # molecule: mean-pool readout + graph labels
    dtype: Any = jnp.float32
    halo_bf16: bool = False     # compress the halo all-gather to bf16 (§Perf)


N_AGG = 4     # mean, max, min, std
N_SCALE = 3   # identity, amplification, attenuation


def param_shapes(c: PNAConfig) -> Dict[str, Tuple[int, ...]]:
    shapes: Dict[str, Tuple[int, ...]] = {"in_w": (c.d_in, c.d_hidden), "in_b": (c.d_hidden,)}
    for i in range(c.n_layers):
        shapes[f"l{i}_msg_w"] = (2 * c.d_hidden, c.d_hidden)
        shapes[f"l{i}_msg_b"] = (c.d_hidden,)
        shapes[f"l{i}_upd_w"] = (c.d_hidden * (1 + N_AGG * N_SCALE), c.d_hidden)
        shapes[f"l{i}_upd_b"] = (c.d_hidden,)
    shapes["out_w"] = (c.d_hidden, c.n_classes)
    shapes["out_b"] = (c.n_classes,)
    return shapes


def abstract_params(c: PNAConfig) -> Params:
    return {k: jax.ShapeDtypeStruct(s, c.dtype) for k, s in param_shapes(c).items()}


def init_params(c: PNAConfig, key: jax.Array) -> Params:
    params = {}
    for i, (name, shape) in enumerate(param_shapes(c).items()):
        k = jax.random.fold_in(key, i)
        params[name] = (jnp.zeros(shape, c.dtype) if name.endswith("_b")
                        else he_init(k, shape, c.dtype))
    return params


def pna_layer(params: Params, i: int, h: jax.Array, src: jax.Array, dst: jax.Array,
              c: PNAConfig, n_nodes: int) -> jax.Array:
    """One PNA layer over edge lists (src -> dst)."""
    msg_in = jnp.concatenate([h[dst], h[src]], axis=-1)          # (E, 2D)
    m = jax.nn.relu(msg_in @ params[f"l{i}_msg_w"] + params[f"l{i}_msg_b"])

    ones = jnp.ones((m.shape[0],), m.dtype)
    deg = jax.ops.segment_sum(ones, dst, num_segments=n_nodes)   # (N,)
    deg_safe = jnp.maximum(deg, 1.0)

    s = jax.ops.segment_sum(m, dst, num_segments=n_nodes)
    mean = s / deg_safe[:, None]
    mx = jax.ops.segment_max(m, dst, num_segments=n_nodes)
    mx = jnp.where(deg[:, None] > 0, mx, 0.0)
    mn = jax.ops.segment_min(m, dst, num_segments=n_nodes)
    mn = jnp.where(deg[:, None] > 0, mn, 0.0)
    sq = jax.ops.segment_sum(m * m, dst, num_segments=n_nodes) / deg_safe[:, None]
    std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0) + 1e-5)

    agg = jnp.concatenate([mean, mx, mn, std], axis=-1)          # (N, 4D)
    logd = jnp.log1p(deg)[:, None]
    amp = logd / c.delta
    att = c.delta / jnp.maximum(logd, 1e-5)
    scaled = jnp.concatenate([agg, agg * amp, agg * att], axis=-1)  # (N, 12D)

    upd_in = jnp.concatenate([h, scaled], axis=-1)
    return jax.nn.relu(upd_in @ params[f"l{i}_upd_w"] + params[f"l{i}_upd_b"])


def forward(params: Params, c: PNAConfig, batch: Dict[str, jax.Array],
            *, mesh=None, node_axes=None) -> jax.Array:
    """batch: features (N, d_in), edge src/dst (E,), [graph_ids (N,)].

    Returns per-node logits (N, n_classes) or per-graph logits if graph_level.

    At scale (ogb_products: 2.45M nodes) node tensors are sharded over
    ``node_axes`` and each layer is rematerialized — otherwise the (N, 12D)
    aggregate concat saved for backward is ~9 GB/layer replicated.
    """
    feats, src, dst = batch["features"], batch["src"], batch["dst"]
    n_nodes = feats.shape[0]

    constrain = lambda x: x
    if mesh is not None and node_axes is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def constrain(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(node_axes, None)))

    h = constrain(jax.nn.relu(feats.astype(c.dtype) @ params["in_w"] + params["in_b"]))
    for i in range(c.n_layers):
        h = jax.checkpoint(
            lambda h, i=i: constrain(pna_layer(params, i, h, src, dst, c, n_nodes))
        )(h)
    if c.graph_level:
        gid = batch["graph_ids"]
        n_graphs = batch["n_graphs"]
        pooled = jax.ops.segment_sum(h, gid, num_segments=n_graphs)
        cnt = jax.ops.segment_sum(jnp.ones((n_nodes,), h.dtype), gid,
                                  num_segments=n_graphs)
        h = pooled / jnp.maximum(cnt, 1.0)[:, None]
    return h @ params["out_w"] + params["out_b"]


def loss_fn(params: Params, c: PNAConfig, batch: Dict[str, jax.Array],
            *, mesh=None, node_axes=None) -> jax.Array:
    if mesh is not None and node_axes is not None and not c.graph_level:
        logits = forward_sharded(params, c, batch, mesh=mesh, node_axes=node_axes)
    else:
        logits = forward(params, c, batch, mesh=mesh, node_axes=node_axes)
    labels = batch["labels"]
    mask = batch.get("label_mask")
    ce = softmax_xent(logits, labels)
    if mask is not None:
        return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce.mean()


def make_train_step(c: PNAConfig, optimizer, *, mesh=None, node_axes=None):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, c, batch, mesh=mesh, node_axes=node_axes))(params)
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, {"loss": loss}
    return train_step


def param_specs(c: PNAConfig, *, dp=("data",), tp: str = "model"):
    """Small model: replicate params; edges are the sharded quantity."""
    from jax.sharding import PartitionSpec as P
    return {k: P(*(None,) * len(s)) for k, s in param_shapes(c).items()}


# ------------------------------------------------- distributed (shard_map)
def partition_edges(src: np.ndarray, dst: np.ndarray, n_nodes: int,
                    n_shards: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """Partition edges by dst node range (host-side, part of the FE pipeline).

    Shard k receives edges whose dst lies in [k*rows, (k+1)*rows); every
    shard is padded to the max shard size with OOB edges (dst = n_nodes)
    that segment ops drop. Returns (src_p, dst_p, per_shard).
    """
    rows = (n_nodes + n_shards - 1) // n_shards
    owner = dst // rows
    order = np.argsort(owner, kind="stable")
    src_s, dst_s, owner_s = src[order], dst[order], owner[order]
    counts = np.bincount(owner_s, minlength=n_shards)
    per_shard = int(counts.max())
    src_p = np.zeros((n_shards, per_shard), src.dtype)
    dst_p = np.full((n_shards, per_shard), n_nodes, dst.dtype)  # OOB padding
    start = 0
    for k in range(n_shards):
        c = counts[k]
        src_p[k, :c] = src_s[start:start + c]
        dst_p[k, :c] = dst_s[start:start + c]
        start += c
    return src_p.reshape(-1), dst_p.reshape(-1), per_shard


def _pna_layer_local(params_i: Dict[str, jax.Array], h_full: jax.Array,
                     h_local: jax.Array, src: jax.Array, dst_local: jax.Array,
                     c: PNAConfig, local_rows: int) -> jax.Array:
    """One PNA layer over a local edge shard writing a local node range."""
    msg_w, msg_b, upd_w, upd_b = (params_i["msg_w"], params_i["msg_b"],
                                  params_i["upd_w"], params_i["upd_b"])
    dst_clamped = jnp.minimum(dst_local, local_rows)  # OOB -> dropped below
    h_dst = jnp.take(h_local, jnp.minimum(dst_clamped, local_rows - 1), axis=0)
    h_src = jnp.take(h_full, src, axis=0)
    m = jax.nn.relu(jnp.concatenate([h_dst, h_src], -1) @ msg_w + msg_b)

    ones = jnp.where(dst_local < local_rows, 1.0, 0.0).astype(m.dtype)
    m = m * ones[:, None]
    deg = jax.ops.segment_sum(ones, dst_local, num_segments=local_rows)
    deg_safe = jnp.maximum(deg, 1.0)
    s = jax.ops.segment_sum(m, dst_local, num_segments=local_rows)
    mean = s / deg_safe[:, None]
    mx = jnp.where(deg[:, None] > 0,
                   jax.ops.segment_max(m, dst_local, num_segments=local_rows), 0.0)
    mn = jnp.where(deg[:, None] > 0,
                   jax.ops.segment_min(m, dst_local, num_segments=local_rows), 0.0)
    sq = jax.ops.segment_sum(m * m, dst_local, num_segments=local_rows) / deg_safe[:, None]
    std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0) + 1e-5)
    agg = jnp.concatenate([mean, mx, mn, std], -1)
    logd = jnp.log1p(deg)[:, None]
    scaled = jnp.concatenate(
        [agg, agg * logd / c.delta, agg * c.delta / jnp.maximum(logd, 1e-5)], -1)
    upd_in = jnp.concatenate([h_local, scaled], -1)
    return jax.nn.relu(upd_in @ upd_w + upd_b)


def forward_sharded(params: Params, c: PNAConfig, batch: Dict[str, jax.Array],
                    *, mesh, node_axes: Tuple[str, ...]) -> jax.Array:
    """Distributed PNA: node tensors sharded, edges dst-partitioned.

    Per layer (inside shard_map): all-gather h (the halo exchange), compute
    messages for the local edge shard, segment-reduce into the LOCAL node
    range only. Node-sharded aggregates never replicate — the structure that
    makes 2.4M-node full-batch training fit (see dry-run ogb_products).
    """
    from jax.sharding import PartitionSpec as P

    feats, src, dst = batch["features"], batch["src"], batch["dst"]
    n_nodes = feats.shape[0]
    n_shards = int(np.prod([mesh.shape[a] for a in node_axes]))
    local_rows = n_nodes // n_shards
    h0 = jax.nn.relu(feats.astype(c.dtype) @ params["in_w"] + params["in_b"])

    def layer_fn(i, h_shard, src_l, dst_l):
        def f(h_loc, src_loc, dst_loc):
            idx = jax.lax.axis_index(node_axes)
            if c.halo_bf16:
                # halo exchange in bf16: halves the dominant collective term
                h_wire = jax.lax.all_gather(
                    h_loc.astype(jnp.bfloat16), node_axes, axis=0, tiled=True)
                h_full = h_wire.astype(h_loc.dtype)
            else:
                h_full = jax.lax.all_gather(h_loc, node_axes, axis=0, tiled=True)
            dst_local = dst_loc - idx * local_rows
            dst_local = jnp.where(
                (dst_local >= 0) & (dst_local < local_rows), dst_local, local_rows)
            lp = {k.split("_", 1)[1]: v for k, v in params.items()
                  if k.startswith(f"l{i}_")}
            return _pna_layer_local(lp, h_full, h_loc, src_loc, dst_local,
                                    c, local_rows)

        return jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(node_axes, None), P(node_axes), P(node_axes)),
            out_specs=P(node_axes, None),
            check_vma=False,
        )(h_shard, src_l, dst_l)

    h = h0
    for i in range(c.n_layers):
        h = jax.checkpoint(lambda h, i=i: layer_fn(i, h, src, dst))(h)
    return h @ params["out_w"] + params["out_b"]


# ----------------------------------------------------------- host sampler
class NeighborSampler:
    """Fanout neighbor sampler over a CSR adjacency (host op, numpy).

    GraphSAGE-style [arXiv:1706.02216]: for each seed, sample ``fanout[0]``
    neighbors, then ``fanout[1]`` of each of those, etc. Returns the union
    subgraph with node ids remapped densely — note the remap IS a dedup
    (the FeatureBox working-set construction applied to graph nodes).
    """

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, *, seed: int = 0):
        self.indptr = indptr
        self.indices = indices
        self.rng = np.random.default_rng(seed)

    @staticmethod
    def from_edges(n_nodes: int, src: np.ndarray, dst: np.ndarray, **kw) -> "NeighborSampler":
        order = np.argsort(dst, kind="stable")
        src_sorted = src[order]
        counts = np.bincount(dst, minlength=n_nodes)
        indptr = np.concatenate([[0], np.cumsum(counts)])
        return NeighborSampler(indptr.astype(np.int64), src_sorted.astype(np.int64), **kw)

    def sample(self, seeds: np.ndarray, fanout: Tuple[int, ...]):
        """Returns (node_ids, src_local, dst_local, seed_local)."""
        nodes = list(seeds)
        node_set = {int(n): i for i, n in enumerate(seeds)}
        src_l: List[int] = []
        dst_l: List[int] = []
        frontier = list(seeds)
        for f in fanout:
            nxt: List[int] = []
            for u in frontier:
                lo, hi = self.indptr[u], self.indptr[u + 1]
                neigh = self.indices[lo:hi]
                if len(neigh) == 0:
                    continue
                take = neigh if len(neigh) <= f else self.rng.choice(neigh, f, replace=False)
                for v in take:
                    v = int(v)
                    if v not in node_set:
                        node_set[v] = len(nodes)
                        nodes.append(v)
                        nxt.append(v)
                    src_l.append(node_set[v])
                    dst_l.append(node_set[int(u)])
            frontier = nxt
        return (np.asarray(nodes, np.int64), np.asarray(src_l, np.int32),
                np.asarray(dst_l, np.int32),
                np.arange(len(seeds), dtype=np.int32))


def random_graph(n_nodes: int, n_edges: int, d_feat: int, n_classes: int,
                 *, seed: int = 0) -> Dict[str, np.ndarray]:
    """Synthetic graph batch for smokes/benches."""
    rng = np.random.default_rng(seed)
    return {
        "features": rng.normal(size=(n_nodes, d_feat)).astype(np.float32),
        "src": rng.integers(0, n_nodes, n_edges).astype(np.int32),
        "dst": rng.integers(0, n_nodes, n_edges).astype(np.int32),
        "labels": rng.integers(0, n_classes, n_nodes).astype(np.int32),
    }
