"""Pallas TPU kernel for Alg. 1 (in-kernel dynamic memory allocation).

Paper semantics (per block of N threads):
  1. ``prefix = parallel_prefix_sum(sizes)``             (in-block scan)
  2. ``address = atomic_add(idle_memory_head, prefix_N)`` (one bump per block)
  3. ``offsets_i = address + prefix_i - prefix_1``         (per-thread offsets)

TPU adaptation: a Pallas grid step plays the role of a CUDA block and scans
one (8, 128) int32 tile, i.e. one vreg of 1024 requests in row-major order.
TPU grid steps execute **sequentially** on a core, so the global bump
pointer is carried in VMEM scratch across steps — the deterministic
equivalent of the atomic add. The in-block scan is a Hillis-Steele
log-step scan built from ``pltpu.roll`` and masked adds (Mosaic has no
``cumsum``): lanes first, then the row totals down the sublanes. All
arithmetic is int32 addition, so the result is bitwise equal to
``jnp.cumsum`` (wrap-around included). Sizes are aligned up to the
128-element lane width (the paper's 128-byte cache alignment, in TPU units).

Out-of-range tail lanes (N not a multiple of the tile) are masked to size 0,
so they consume no arena space and their offsets are harmless.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mempool import ALIGN

# One grid step scans one (ROWS, LANES) tile of requests (a "block" in the
# paper), in row-major order.
ROWS, LANES = 8, 128
BLOCK = ROWS * LANES


def _shifted_sum(x, axis: int, *, prefix: bool):
    """Log-step sum along ``axis`` (a power-of-two length) of a 2-D tile.

    ``prefix=True`` gives the inclusive prefix sum (Hillis-Steele: add the
    element ``shift`` back, where one exists); ``prefix=False`` gives the
    total in every position (cyclic butterfly all-reduce).
    """
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < n:
        moved = pltpu.roll(x, shift, axis)      # moved[i] = x[i - shift]
        x = x + (jnp.where(pos >= shift, moved, 0) if prefix else moved)
        shift *= 2
    return x


def _alloc_kernel(sizes_ref, offsets_ref, head_ref, carry_ref, *, n: int, align: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    base = carry_ref[...]  # "idle_memory_head" before this block's bump

    sizes = sizes_ref[...].astype(jnp.int32)
    # mask tail requests beyond n
    row = jax.lax.broadcasted_iota(jnp.int32, sizes.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, sizes.shape, 1)
    valid = (step * BLOCK + row * LANES + lane) < n
    sizes = jnp.where(valid, sizes, 0)

    aligned = (sizes + (align - 1)) // align * align
    in_row = _shifted_sum(aligned, 1, prefix=True)       # scan along lanes
    row_total = _shifted_sum(aligned, 1, prefix=False)   # row sum, every lane
    rows_before = _shifted_sum(row_total, 0, prefix=True) - row_total
    inclusive = in_row + rows_before
    exclusive = inclusive - aligned            # prefix_i - prefix_1
    offsets_ref[...] = base + exclusive        # address + (prefix_i - prefix_1)

    total = _shifted_sum(row_total, 0, prefix=False)     # prefix_N, broadcast
    carry_ref[...] = base + total              # atomic_add(head, prefix_N)
    head_ref[...] = base + total               # exposed head after this block


@functools.partial(jax.jit, static_argnames=("align", "interpret"))
def alloc_offsets(sizes: jax.Array, *, align: int = ALIGN, interpret: bool = True):
    """Run Alg. 1 over ``sizes`` (int32[N]); returns (offsets int32[N], head int32[1]).

    ``head[0]`` is the final ``idle_memory_head`` — total arena elements
    consumed. Resetting the pool (paper §V) is the caller dropping this value.
    """
    n = sizes.shape[0]
    n_pad = (n + BLOCK - 1) // BLOCK * BLOCK
    if n_pad != n:
        sizes = jnp.pad(sizes, (0, n_pad - n))
    tiles = sizes.astype(jnp.int32).reshape(n_pad // LANES, LANES)
    offsets, head = pl.pallas_call(
        functools.partial(_alloc_kernel, n=n, align=align),
        grid=(n_pad // BLOCK,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
            # one resident tile, rewritten each step: the head after the
            # last block is what lands in HBM
            pl.BlockSpec((ROWS, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((ROWS, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((ROWS, LANES), jnp.int32)],
        # the bump pointer is carried from one block to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tiles)
    return offsets.reshape(-1)[:n], head[0, :1]
