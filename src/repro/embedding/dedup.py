"""Per-batch working-set construction (dedup of sparse ids).

The hierarchical GPU parameter server's key observation ([37], §II-B): the
number of *referenced* parameters in a mini-batch fits device memory because
inputs are sparse. FeatureBox inherits this — before any table access, a
batch's ids are deduplicated and remapped to a dense local index space.

``dedup`` is jit-traceable (static working-set capacity); ``dedup_np`` is the
host twin used by the hierarchical PS pull path.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel for unused working-set slots (never a valid row id).
FILL = jnp.int32(2**31 - 1)

# Legal id range shared by the device and host dedup paths: ids must be in
# [0, 2**31 - 1). The upper bound is exclusive because FILL == 2**31 - 1 is
# the padding sentinel — an id equal to it would be indistinguishable from
# an unused slot.
MAX_ID = 2**31 - 1


def dedup(ids: jax.Array, *, capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Deduplicate a batch of sparse ids into a fixed-capacity working set.

    Args:
      ids: int[ ... ] arbitrary-shape batch of row ids. **Contract:** every
        id must be in ``[0, MAX_ID)`` (= ``[0, 2**31 - 1)``). This device
        path cannot check that inside the jit: ids >= 2**31 silently wrap
        negative under the ``astype(jnp.int32)`` cast, and an id equal to
        the ``FILL`` sentinel ``2**31 - 1`` would collide with the padding
        of unused working-set slots. Validate on the host before feeding
        (the host twin :func:`dedup_np` enforces the same bounds).
      capacity: static upper bound on unique ids (working-set size). Must be
        >= the true unique count; verify with ``count`` downstream.

    Returns:
      unique:  int32[capacity] unique ids, FILL-padded.
      inverse: int32[ids.shape] position of each id inside ``unique``.
      count:   int32[] true number of unique ids (<= capacity if valid).
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    unique, inverse = jnp.unique(
        flat, return_inverse=True, size=capacity, fill_value=FILL
    )
    count = jnp.sum(unique != FILL).astype(jnp.int32)
    return unique, inverse.reshape(ids.shape).astype(jnp.int32), count


def expected_unique(rows: int, vocab: int) -> float:
    """E[#unique] of ``rows`` uniform draws from a ``vocab``-id space:
    ``v (1 - (1 - 1/v)^n)``. The sizing heuristic for working-set
    capacities (``dedup(..., capacity=...)``) when the worst case
    ``min(rows, vocab)`` is too loose — shared by the dry-run cells'
    ``cap_expected`` variant and the train driver's
    :func:`repro.fe.modelfeed.dedup_capacity_hint`."""
    if rows <= 0 or vocab <= 0:
        return 0.0
    return vocab * (1.0 - (1.0 - 1.0 / vocab) ** rows)


def dedup_np(ids: np.ndarray, *, check_bounds: bool = True
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Host dedup (exact size): returns (unique ids, inverse).

    Enforces the id-range contract the device path (:func:`dedup`) can only
    document: ids must be in ``[0, 2**31 - 1)``. Out-of-range ids would wrap
    negative / collide with ``FILL`` on device, so they are rejected here,
    at the host boundary, with a clear error instead of silent corruption.
    """
    flat = ids.reshape(-1)
    if check_bounds and flat.size:
        lo = int(flat.min())
        hi = int(flat.max())
        if lo < 0 or hi >= MAX_ID:
            raise ValueError(
                f"sparse ids out of range: min={lo} max={hi}, legal range "
                f"is [0, {MAX_ID}) — ids >= 2**31 wrap negative under the "
                f"device path's int32 cast and {MAX_ID} collides with the "
                f"FILL padding sentinel")
    unique, inverse = np.unique(flat, return_inverse=True)
    return unique.astype(np.int64), inverse.reshape(ids.shape).astype(np.int32)


def undedup(rows: jax.Array, inverse: jax.Array) -> jax.Array:
    """Expand working-set rows back to per-slot rows: rows[inverse]."""
    return jnp.take(rows, inverse, axis=0)


def dedup_hierarchical(
    ids: jax.Array, *, capacity: int, mesh, axes, local_capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Two-stage dedup: per-shard local unique, then global unique of the
    pooled local uniques.

    The global distributed sort inside a flat ``jnp.unique`` over B x F ids is
    the measured bound of the recsys train step (EXPERIMENTS.md §Perf pair 1);
    deduping locally first shrinks the globally-sorted pool to
    n_shards x local_capacity (< the raw id count whenever shards see repeated
    ids). Semantics match :func:`dedup` (same unique set, FILL-padded).

    ``ids`` must be sharded over ``axes`` on its leading dim.
    """
    from jax.sharding import PartitionSpec as P

    flat = ids.reshape(-1).astype(jnp.int32)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))

    def local(stage_ids):
        u, inv = jnp.unique(stage_ids.reshape(-1), return_inverse=True,
                            size=local_capacity, fill_value=FILL)
        return u[None], inv[None].astype(jnp.int32)

    local_u, local_inv = jax.shard_map(
        local, mesh=mesh,
        in_specs=P(axes),
        out_specs=(P(axes, None), P(axes, None)),
        check_vma=False,
    )(flat)                                   # (n_shards, cap_loc), (n_shards, B_loc)

    pool = local_u.reshape(-1)                # (n_shards * cap_loc,)
    unique, inv_pool = jnp.unique(pool, return_inverse=True,
                                  size=capacity, fill_value=FILL)
    inv_pool = inv_pool.reshape(n_shards, local_capacity)
    # compose: element e of shard s -> local_inv[s, e] -> inv_pool[s, .]
    final_inv = jnp.take_along_axis(inv_pool, local_inv.astype(jnp.int32),
                                    axis=1).reshape(ids.shape)
    count = jnp.sum(unique != FILL).astype(jnp.int32)
    return unique, final_inv.astype(jnp.int32), count


def dedup_two_stage_local(
    local_ids: jax.Array, *, capacity: int, local_capacity: int,
    gather_axes,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Two-stage dedup *from inside* shard_map (the mesh train step's form).

    :func:`dedup_hierarchical` wraps shard_map around an unsharded caller;
    the mesh train step is already device-local when it needs the working
    set, so this is the per-device body: local unique (stage 1, bounds the
    pooled sort to ``n_devices x local_capacity`` ids) -> all-gather of the
    FILL-padded local uniques over ``gather_axes`` -> global unique of the
    pool (replicated compute, stage 2). The local inverse is composed from
    the two sorts' own inverses: stage 1 maps each id to its ``local_u``
    slot, stage 2 maps each pool slot to its ``unique`` slot, and this
    device's ``local_u`` fills pool slots ``[dev * local_capacity, (dev + 1)
    * local_capacity)``, ``dev`` being its row-major position over
    ``gather_axes`` (the tiled all-gather's order). Each id's slot is its
    position in the sorted global uniques, exactly :func:`jnp.unique`'s
    inverse, so on a 1x1 mesh the result is bitwise :func:`dedup`.

    Returns ``(unique, inverse, count, local_count)`` — ``unique``/``count``
    replicated, ``inverse`` for this device's ``local_ids``, ``local_count``
    this device's stage-1 unique count (the pooled-exchange size the comm
    stats report). ``local_capacity`` must bound this shard's true unique
    count or overflow drops the largest local ids (callers size it with
    :func:`repro.fe.modelfeed.dedup_capacity_hint` on the per-device rows).

    Overflow: an id dropped by either stage gets an inverse ``>= capacity``
    (out of range, so lookups fill and gradient scatters drop it); every
    in-range inverse points at the id's own slot. A stage-1 drop is filled
    with ``capacity`` itself, never read from another device's slice of the
    pool's inverse.
    """
    flat = local_ids.reshape(-1).astype(jnp.int32)
    local_u, local_inv = jnp.unique(flat, return_inverse=True,
                                    size=local_capacity, fill_value=FILL)
    pool = jax.lax.all_gather(local_u, gather_axes, axis=0, tiled=True)
    unique, pool_inv = jnp.unique(pool, return_inverse=True, size=capacity,
                                  fill_value=FILL)
    dev = jax.lax.axis_index(gather_axes)
    own_inv = jax.lax.dynamic_slice_in_dim(
        pool_inv, dev * local_capacity, local_capacity)
    inverse = jnp.take(own_inv, local_inv, mode="fill",
                       fill_value=capacity).astype(jnp.int32)
    count = jnp.sum(unique != FILL).astype(jnp.int32)
    local_count = jnp.sum(local_u != FILL).astype(jnp.int32)
    return unique, inverse.reshape(local_ids.shape), count, local_count


def scatter_unique_grads(
    grad_rows: jax.Array, inverse: jax.Array, capacity: int
) -> jax.Array:
    """Accumulate per-slot gradients onto the working set (transpose of undedup)."""
    flat = grad_rows.reshape(-1, grad_rows.shape[-1])
    seg = inverse.reshape(-1)
    return jax.ops.segment_sum(flat, seg, num_segments=capacity)
