"""Mixture-of-Experts FFN (DeepSeek-style: shared + fine-grained routed).

Dispatch is sort-based with per-expert capacity (GShard-style dropping, no
giant one-hot einsum): tokens' (token, k) assignments are sorted by expert,
positions within each expert come from the sorted order, and tokens beyond
capacity are dropped. The heavy compute is two grouped einsums on the MXU.

Distribution (DESIGN.md §5): experts are sharded over the ``model`` mesh axis
(EP), tokens over ``data``(+``pod``). Inside ``shard_map`` each model rank
routes its replicated token shard, builds ONLY its local experts' dispatch
buffer, runs the expert FFN, scatters partial outputs back to token order and
``psum``s over ``model``. For very large expert weights (DeepSeek-V2) the
hidden dim ``f`` is additionally sharded over ``data`` and all-gathered at
use (ZeRO-3); the gather shows up in the roofline's collective term.

The same math runs without a mesh (``mesh=None``) for CPU smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.common import dense


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    # sharding: experts over tp_axis; expert hidden dim over fsdp_axis (ZeRO-3)
    shard_ff_over_data: bool = False


def moe_params_shape(d_model: int, c: MoEConfig) -> Dict[str, Tuple[int, ...]]:
    e, f = c.n_experts, c.d_ff_expert
    shapes = {
        "router": (d_model, e),
        "w1": (e, d_model, f),
        "w3": (e, d_model, f),
        "w2": (e, f, d_model),
    }
    if c.n_shared:
        fs = c.n_shared * f
        shapes.update({
            "sw1": (d_model, fs),
            "sw3": (d_model, fs),
            "sw2": (fs, d_model),
        })
    return shapes


def _route(x: jax.Array, router: jax.Array, c: MoEConfig):
    """Softmax routing + top-k with renormalized combine weights."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    top_p, top_e = jax.lax.top_k(probs, c.top_k)                # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style): E * sum_e f_e * P_e
    me = probs.mean(axis=0)
    onehot = jax.nn.one_hot(top_e[:, 0], c.n_experts, dtype=jnp.float32)
    fe = onehot.mean(axis=0)
    aux = c.n_experts * jnp.sum(fe * me)
    return top_e.astype(jnp.int32), top_p, aux


def _dispatch_indices(top_e: jax.Array, c: MoEConfig, capacity: int):
    """Sort-based dispatch plan: for each (token, k) -> (expert, slot, keep)."""
    t, k = top_e.shape
    flat_e = top_e.reshape(-1)                                   # (T*K,)
    order = jnp.argsort(flat_e, stable=True)                     # sort by expert
    sorted_e = flat_e[order]
    counts = jax.ops.segment_sum(
        jnp.ones_like(sorted_e), sorted_e, num_segments=c.n_experts)
    starts = jnp.cumsum(counts) - counts                         # expert offsets
    pos = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = pos < capacity
    token = (order // k).astype(jnp.int32)
    return order, sorted_e, pos, keep, token


def _expert_ffn(xe: jax.Array, w1, w3, w2) -> jax.Array:
    """Grouped SwiGLU over (E_loc, C, d) with weights (E_loc, d, f)/(E_loc, f, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1.astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w3.astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(xe.dtype))


def _moe_local(x, router, w1, w3, w2, c: MoEConfig, *, n_local: int,
               local_offset, fsdp_axis=None, tp_axis=None):
    """Token shard + local experts -> partial output (psum'd by caller)."""
    t, d = x.shape
    capacity = int(np.ceil(t * c.top_k / c.n_experts * c.capacity_factor))
    capacity = max(capacity, 1)
    top_e, top_p, aux = _route(x, router, c)
    order, sorted_e, pos, keep, token = _dispatch_indices(top_e, c, capacity)

    if fsdp_axis is not None:
        # ZeRO-3: expert hidden dim gathered at use
        w1 = jax.lax.all_gather(w1, fsdp_axis, axis=2, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axis, axis=2, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axis, axis=1, tiled=True)

    local_lo = local_offset * n_local
    is_local = keep & (sorted_e >= local_lo) & (sorted_e < local_lo + n_local)
    local_slot = (sorted_e - local_lo) * capacity + jnp.minimum(pos, capacity - 1)
    safe_slot = jnp.where(is_local, local_slot, n_local * capacity)

    gathered = x[token] * is_local[:, None].astype(x.dtype)      # (T*K, d)
    buf = jnp.zeros((n_local * capacity + 1, d), x.dtype)
    buf = buf.at[safe_slot].set(gathered)                         # unique slots
    xe = buf[:-1].reshape(n_local, capacity, d)

    ye = _expert_ffn(xe, w1, w3, w2)                              # (E_loc, C, d)
    ye_flat = ye.reshape(-1, d)
    back = ye_flat[jnp.minimum(safe_slot, n_local * capacity - 1)]
    back = back * is_local[:, None].astype(back.dtype)
    wsorted = top_p.reshape(-1)[order].astype(back.dtype)
    out = jax.ops.segment_sum(back * wsorted[:, None], token, num_segments=t)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)
        aux = jax.lax.pmean(aux, tp_axis)
    return out, aux


def moe_ffn(
    params: Dict[str, jax.Array],
    x: jax.Array,                     # (T, d) flattened tokens
    c: MoEConfig,
    *,
    mesh=None,
    dp_axes: Tuple[str, ...] = ("data",),
    tp_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over flattened tokens. Returns (out (T, d), aux loss scalar)."""
    if mesh is None:
        out, aux = _moe_local(
            x, params["router"], params["w1"], params["w3"], params["w2"], c,
            n_local=c.n_experts, local_offset=jnp.int32(0))
    else:
        n_tp = mesh.shape[tp_axis]
        if c.n_experts % n_tp:
            raise ValueError(f"{c.n_experts} experts not divisible by tp={n_tp}")
        n_local = c.n_experts // n_tp
        ff_spec = P(tp_axis, None, "data") if c.shard_ff_over_data else P(tp_axis, None, None)
        ff_spec_w2 = P(tp_axis, "data", None) if c.shard_ff_over_data else P(tp_axis, None, None)
        fsdp_axis = "data" if c.shard_ff_over_data else None

        def fn(xs, router, w1, w3, w2):
            return _moe_local(
                xs, router, w1, w3, w2, c,
                n_local=n_local,
                local_offset=jax.lax.axis_index(tp_axis),
                fsdp_axis=fsdp_axis,
                tp_axis=tp_axis,
            )

        out, aux = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(dp_axes, None), P(None, None), ff_spec, ff_spec, ff_spec_w2),
            out_specs=(P(dp_axes, None), P()),
            check_vma=False,
        )(x, params["router"], params["w1"], params["w3"], params["w2"])
        aux = aux.mean() if aux.ndim else aux

    if c.n_shared:
        h = jax.nn.silu(dense(x, params["sw1"])) * dense(x, params["sw3"])
        out = out + dense(h, params["sw2"])
    return out, aux


def moe_ffn_ref(params: Dict[str, jax.Array], x: jax.Array, c: MoEConfig) -> jax.Array:
    """Dense oracle: every expert computed for every token (tests only).

    Matches ``moe_ffn`` exactly when no token exceeds capacity.
    """
    top_e, top_p, _ = _route(x, params["router"], c)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, params["w1"].astype(x.dtype)))
    h = h * jnp.einsum("td,edf->tef", x, params["w3"].astype(x.dtype))
    ye = jnp.einsum("tef,efd->ted", h, params["w2"].astype(x.dtype))  # (T, E, d)
    combine = jnp.zeros((x.shape[0], c.n_experts), x.dtype)
    for k in range(c.top_k):
        combine = combine + jax.nn.one_hot(top_e[:, k], c.n_experts,
                                           dtype=x.dtype) * top_p[:, k:k + 1].astype(x.dtype)
    out = jnp.einsum("te,ted->td", combine, ye)
    if c.n_shared:
        hs = jax.nn.silu(dense(x, params["sw1"])) * dense(x, params["sw3"])
        out = out + dense(hs, params["sw2"])
    return out
