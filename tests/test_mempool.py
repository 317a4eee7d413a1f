"""Alg. 1 allocator invariants: host pool, jnp planner, Pallas kernel agree."""

import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")

import jax.numpy as jnp
import numpy as np

from repro.core.mempool import ALIGN, ArenaPool, align_up, plan_offsets, required_capacity
from repro.kernels.mempool_alloc.ops import plan_allocation
from repro.kernels.mempool_alloc.ref import alloc_offsets_ref


@hypothesis.given(st.lists(st.integers(min_value=0, max_value=10_000),
                           min_size=1, max_size=500))
@hypothesis.settings(deadline=None, max_examples=80)
def test_allocator_invariants(sizes):
    pool = ArenaPool(capacity=align_up(sum(sizes) + ALIGN * len(sizes)))
    allocs = pool.alloc_block(sizes)
    # one offset per request, alignment, no overlap, ordered, within capacity
    assert len(allocs) == len(sizes)
    for a, size in zip(allocs, sizes):
        assert a.offset % ALIGN == 0
        assert a.size == size
    for prev, nxt in zip(allocs, allocs[1:]):
        assert prev.offset + prev.size <= nxt.offset
    assert pool.head <= pool.capacity
    assert pool.head == sum(align_up(s) for s in sizes)
    # O(1) reset (paper §V)
    pool.reset()
    assert pool.head == 0
    # allocations after reset reuse the same space deterministically
    again = pool.alloc_block(sizes)
    assert [a.offset for a in again] == [a.offset for a in allocs]


@hypothesis.given(st.lists(st.integers(min_value=0, max_value=5000),
                           min_size=1, max_size=300))
@hypothesis.settings(deadline=None, max_examples=40)
def test_kernel_matches_ref_and_pool(sizes):
    arr = jnp.asarray(np.asarray(sizes, np.int32))
    off_k, head_k = plan_allocation(arr)
    off_r, head_r = alloc_offsets_ref(arr)
    assert (np.asarray(off_k) == np.asarray(off_r)).all()
    assert int(head_k[0]) == int(head_r[0])
    pool = ArenaPool(capacity=max(align_up(int(head_k[0])), ALIGN))
    allocs = pool.alloc_block(sizes)
    assert [a.offset for a in allocs] == np.asarray(off_k).tolist()


@pytest.mark.parametrize("n,high", [(1024, 5000), (2500, 5000),
                                    (3000, 2**21)])
def test_kernel_matches_ref_across_blocks(n, high):
    """Several (8, 128) tiles with a partial tail: the bump pointer is
    carried from block to block, and int32 wrap-around (the last case's
    total passes 2**31) matches ``jnp.cumsum`` bitwise."""
    sizes = np.random.default_rng(n).integers(0, high, n).astype(np.int32)
    off_k, head_k = plan_allocation(jnp.asarray(sizes))
    off_r, head_r = alloc_offsets_ref(jnp.asarray(sizes))
    assert (np.asarray(off_k) == np.asarray(off_r)).all()
    assert int(head_k[0]) == int(head_r[0])
    if high * n < 2**31:
        pool = ArenaPool(capacity=align_up(int(head_k[0])))
        assert [a.offset for a in pool.alloc_block(sizes.tolist())] == \
            np.asarray(off_k).tolist()


def test_exhaustion_raises():
    pool = ArenaPool(capacity=ALIGN * 2)
    with pytest.raises(MemoryError):
        pool.alloc_block([ALIGN, ALIGN, 1])


def test_negative_size_rejected():
    pool = ArenaPool(capacity=ALIGN * 4)
    with pytest.raises(ValueError):
        pool.alloc_block([4, -1])


def test_plan_offsets_jit_matches_pool():
    sizes = jnp.asarray([5, 130, 1, 0, 257], jnp.int32)
    offs, total = plan_offsets(sizes)
    pool = ArenaPool(capacity=1 << 16)
    allocs = pool.alloc_block(np.asarray(sizes).tolist())
    assert [a.offset for a in allocs] == np.asarray(offs).tolist()
    assert pool.head == int(total)


def test_required_capacity_sizes_worst_layer():
    layers = [[100, 200], [5000], [1, 1, 1]]
    cap = required_capacity(layers)
    pool = ArenaPool(capacity=cap)
    for layer in layers:
        pool.alloc_block(layer)   # must fit with reset between layers
        pool.reset()


def test_high_water_tracks_peak():
    pool = ArenaPool(capacity=1 << 20)
    pool.alloc_block([1000])
    pool.reset()
    pool.alloc_block([10])
    assert pool.high_water == align_up(1000)
