"""Pallas TPU kernel: segment row-sum (sparse gradient aggregation).

Owner-side frozen-window update hotspot: sum (L, D) gradient rows into
(S, D) per-key accumulators. Ids outside ``[0, S)`` (sentinel rows) are
dropped.

The wrapper sorts the rows by id first (one XLA sort + row gather), so the
rows of each ``s_tile``-wide output tile form ONE contiguous range of L.
The grid runs over output tiles; tile t visits only the ``block_l`` row
blocks that overlap its range (scalar-prefetched bounds), DMAs each block
from HBM and adds it with a one-hot matmul
(s_tile x block_l) @ (block_l x D) on the MXU. Work is O((L + S) * D *
s_tile) rather than the O(L * S * D) of a dense one-hot over all tiles.

The matmul runs at ``precision=HIGHEST``: the one-hot operand is exact in
any precision, but the default single bf16 pass would round the f32
gradient rows. The sum order within a tile is the MXU's, not XLA's
scatter order, so results agree with the scatter-add reference to f32
rounding (exactly, for sums that f32 represents exactly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import cdiv, round_up


def _segsum_kernel(lo_ref, hi_ref, ids_ref, vals_hbm, out_ref, vbuf, sem, *,
                   block_l: int, s_tile: int):
    t = pl.program_id(0)
    out_ref[...] = jnp.zeros_like(out_ref)
    seg = jax.lax.broadcasted_iota(jnp.int32, (s_tile, block_l), 0) + t * s_tile

    def add_block(b, c):
        copy = pltpu.make_async_copy(
            vals_hbm.at[pl.ds(pl.multiple_of(b * block_l, block_l), block_l)],
            vbuf, sem)
        copy.start()
        ids = ids_ref[pl.ds(b, 1), :]  # (1, block_l)
        onehot = (seg == ids).astype(jnp.float32)
        copy.wait()
        out_ref[...] += jax.lax.dot_general(
            onehot, vbuf[...], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return c

    jax.lax.fori_loop(lo_ref[t], hi_ref[t], add_block, 0)


@functools.partial(jax.jit, static_argnames=("num_segments", "block_l",
                                             "s_tile", "interpret"))
def segment_rowsum(
    grads: jax.Array,  # (L, D)
    ids: jax.Array,  # (L,) int; ids outside [0, num_segments) are dropped
    num_segments: int,
    *,
    block_l: int = 1024,
    s_tile: int = 256,
    interpret: bool,
) -> jax.Array:
    """(num_segments, D) f32 row sums of ``grads`` grouped by ``ids``."""
    l, d = grads.shape
    block_l = min(block_l, round_up(l, 128))
    s_pad = round_up(num_segments, s_tile)
    l_pad = round_up(l, block_l)
    d_pad = round_up(d, 128)
    ids = ids.astype(jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < num_segments), ids, s_pad)
    ids = jnp.pad(ids, (0, l_pad - l), constant_values=s_pad)
    ids, order = jax.lax.sort(
        (ids, jnp.arange(l_pad, dtype=jnp.int32)), num_keys=1, is_stable=True)
    vals = jnp.pad(grads.astype(jnp.float32), ((0, l_pad - l), (0, d_pad - d)))
    vals = jnp.take(vals, order, axis=0)

    # tile t's rows are ids[start[t]:start[t+1]]; visit blocks [lo, hi)
    n_tiles = s_pad // s_tile
    start = jnp.searchsorted(
        ids, jnp.arange(n_tiles + 1, dtype=jnp.int32) * s_tile, side="left")
    lo = (start[:-1] // block_l).astype(jnp.int32)
    hi = jnp.where(start[1:] > start[:-1], cdiv(start[1:], block_l), lo)
    hi = hi.astype(jnp.int32)

    n_blocks = l_pad // block_l
    vmem = 4 * (2 * n_blocks * block_l + 2 * s_tile * d_pad
                + block_l * d_pad + s_tile * block_l)
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block_l=block_l, s_tile=s_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((n_blocks, block_l), lambda t, lo, hi: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((s_tile, d_pad), lambda t, lo, hi: (t, 0)),
            scratch_shapes=[pltpu.VMEM((block_l, d_pad), jnp.float32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((s_pad, d_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(vmem + (4 << 20), 16 << 20)),
        interpret=interpret,
    )(lo, hi, ids.reshape(n_blocks, block_l), vals)
    return out[:num_segments, :d]
