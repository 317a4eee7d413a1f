"""Jitted public wrapper for the mempool allocator kernel.

Selects the Pallas kernel on TPU (compiled) and interpret mode elsewhere;
an empty block takes the jnp reference.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mempool import ALIGN, align_up
from repro.kernels.mempool_alloc.kernel import alloc_offsets
from repro.kernels.mempool_alloc.ref import alloc_offsets_ref

_INT32_MAX = np.iinfo(np.int32).max


def plan_allocation(sizes: jax.Array, *, align: int = ALIGN, use_kernel: bool = True):
    """Plan arena offsets for a block of allocation requests.

    Returns (offsets int32[N], head int32[1]). ``head`` is the post-bump
    ``idle_memory_head``; callers compare it against pool capacity before
    launching the consuming meta-kernel.
    """
    if sizes.ndim != 1:
        raise ValueError(f"sizes must be rank-1, got {sizes.shape}")
    if sizes.shape[0] == 0 or not use_kernel:
        return alloc_offsets_ref(sizes, align=align)
    return alloc_offsets(sizes, align=align, interpret=kernel_interpreted())


def kernel_interpreted() -> bool:
    """Whether the kernel runs in interpret mode: everywhere but on a TPU,
    where Mosaic compiles it."""
    return jax.default_backend() != "tpu"


def plan_block(sizes: Sequence[int], *, align: int = ALIGN,
               use_kernel: bool = True) -> Tuple[np.ndarray, int]:
    """Host-side sizing entry: plan a block of requests from plain ints.

    The bridge the device-feed tier uses to plan static arena placement at
    compile time: takes ordinary Python sizes, runs the allocator kernel
    (or its reference), and returns ``(offsets int64[N], total)`` ready for
    host bookkeeping. Oracle-equivalent to
    :meth:`repro.core.mempool.ArenaPool.alloc_block` — including on inputs
    the kernel's int32 offsets cannot represent: the pool raises there
    (ValueError on negative sizes, int64 capacity check), so this path
    raises too instead of silently wrapping at 2 GiB.
    """
    reqs = np.asarray(list(sizes), dtype=np.int64)
    if reqs.ndim != 1:
        raise ValueError(f"sizes must be rank-1, got {reqs.shape}")
    if (reqs < 0).any():
        raise ValueError("negative allocation size")
    head_bound = sum(int(align_up(s, align)) for s in reqs)
    if head_bound > _INT32_MAX:
        raise OverflowError(
            f"allocation block needs {head_bound} aligned bytes, which "
            f"overflows the kernel's int32 offsets (max {_INT32_MAX}); "
            f"split the block or plan with ArenaPool.alloc_block (int64)")
    arr = jnp.asarray(reqs, jnp.int32)
    offsets, head = plan_allocation(arr, align=align, use_kernel=use_kernel)
    total = int(np.asarray(head).reshape(-1)[0]) if arr.shape[0] else 0
    return np.asarray(offsets, dtype=np.int64), total
