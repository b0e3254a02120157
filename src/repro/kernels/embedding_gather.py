"""Pallas TPU kernel: embedding row gather by per-row DMA.

The hot loop of DBP's retrieval stage and the owner-side serve path: fetch
``idx``-indexed rows of a (rows, D) HBM-resident table into a compact
output. The table never leaves HBM (``memory_space=pl.ANY``): each grid
step issues one DMA per output row and only the requested rows move, so
the master table is never copied or re-laid-out around the call.

A TPU DMA moves whole (8, 128) tiles of a 32-bit array, so a single row
cannot be its own DMA. Each row's DMA instead fetches the 8-row aligned
tile that holds it into a VMEM scratch slot, and the row is then read out
of the slot at its sublane offset. A table whose row count is not a
multiple of 8 ends in a partial tile that no aligned DMA may read; those
last ``rows % 8`` rows arrive as a small VMEM block instead. Indices
arrive in SMEM blocks of ``BLOCK_ROWS`` (a whole index vector would
overflow SMEM at the engine's buffer capacities). 16-bit tables are
gathered as f32 (exact both ways).

Indices must be in ``[0, rows)``: the dispatch wrapper clamps sentinel
slots and masks them to zero afterwards, so the kernel stays branch-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import cdiv, round_up

TILE_ROWS = 8  # sublane height of a 32-bit (8, 128) tile
BLOCK_ROWS = 128  # output rows per grid step (DMAs in flight per step)


def as_tileable(table: jax.Array) -> jax.Array:
    """The 32-bit, lane-aligned, at-least-one-tile form the row DMA needs.

    A no-op for the engine's f32 tables at D % 128 == 0; other shapes pay
    one pass over the table (pad or upcast)."""
    rows, d = table.shape
    if jnp.dtype(table.dtype).itemsize != 4:
        table = table.astype(jnp.float32)
    pad_r, pad_d = max(TILE_ROWS - rows, 0), round_up(d, 128) - d
    if pad_r or pad_d:
        table = jnp.pad(table, ((0, pad_r), (0, pad_d)))
    return table


def tail_rows(table: jax.Array) -> jax.Array:
    """The table's last partial tile, zero-padded to one (8, D) tile (all
    zeros when the row count is a multiple of 8)."""
    rows = table.shape[0]
    tail = table[rows - rows % TILE_ROWS:]
    return jnp.pad(tail, ((0, TILE_ROWS - tail.shape[0]), (0, 0)))


def blocked_indices(idx: jax.Array) -> jax.Array:
    """(n,) -> (cdiv(n, BLOCK_ROWS), 1, BLOCK_ROWS) int32 for SMEM blocks."""
    nb = cdiv(idx.shape[0], BLOCK_ROWS)
    idx = jnp.pad(idx.astype(jnp.int32), (0, nb * BLOCK_ROWS - idx.shape[0]))
    return idx.reshape(nb, 1, BLOCK_ROWS)


def index_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, BLOCK_ROWS), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def table_specs(table: jax.Array):
    """in_specs of (table in HBM, its tail tile in VMEM)."""
    return [pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((TILE_ROWS, table.shape[1]), lambda i: (0, 0))]


def row_scratch(table: jax.Array):
    return [pltpu.VMEM((BLOCK_ROWS, TILE_ROWS, table.shape[1]), table.dtype),
            pltpu.SemaphoreType.DMA(())]


def fetch_rows(idx_ref, table_hbm, tail_ref, tiles, sem):
    """DMA the tile holding each of this block's rows into ``tiles``; returns
    ``row(r)``, which reads block row r (shape (1, D)) out of its tile, or
    out of ``tail_ref`` for a row of the table's last partial tile."""
    rows = table_hbm.shape[0]
    body = rows - rows % TILE_ROWS  # rows that aligned tiles cover; >= 8

    def tile_start(r):
        t = jnp.minimum(idx_ref[0, 0, r] // TILE_ROWS, body // TILE_ROWS - 1)
        return pl.multiple_of(t * TILE_ROWS, TILE_ROWS)

    def copy(r):
        return pltpu.make_async_copy(
            table_hbm.at[pl.ds(tile_start(r), TILE_ROWS)], tiles.at[r], sem)

    def start(r, c):
        copy(r).start()
        return c

    def wait(r, c):
        copy(r).wait()
        return c

    jax.lax.fori_loop(0, BLOCK_ROWS, start, 0)
    jax.lax.fori_loop(0, BLOCK_ROWS, wait, 0)

    def row(r):
        i = idx_ref[0, 0, r]
        off = jnp.minimum(i - tile_start(r), TILE_ROWS - 1)
        toff = jnp.clip(i - body, 0, TILE_ROWS - 1)
        return jnp.where(i >= body, tail_ref[pl.ds(toff, 1), :],
                         tiles[r, pl.ds(off, 1), :])

    return row


def _gather_kernel(idx_ref, table_hbm, tail_ref, out_ref, tiles, sem):
    row = fetch_rows(idx_ref, table_hbm, tail_ref, tiles, sem)

    def put(r, c):
        out_ref[pl.ds(r, 1), :] = row(r)
        return c

    jax.lax.fori_loop(0, BLOCK_ROWS, put, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_gather(
    table: jax.Array,  # (rows, D)
    idx: jax.Array,  # (n,) int32, values in [0, rows) — pre-clamped
    *,
    interpret: bool,
) -> jax.Array:
    """Gathered rows (n, D) in ``table.dtype``. ``interpret`` runs the
    kernel under the Pallas interpreter (CPU validation)."""
    n = idx.shape[0]
    d = table.shape[1]
    src = as_tileable(table)
    out = pl.pallas_call(
        _gather_kernel,
        grid=(cdiv(n, BLOCK_ROWS),),
        in_specs=[index_spec(), *table_specs(src)],
        out_specs=pl.BlockSpec((BLOCK_ROWS, src.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, src.shape[1]), src.dtype),
        scratch_shapes=row_scratch(src),
        interpret=interpret,
    )(blocked_indices(idx), src, tail_rows(src))
    return out[:, :d].astype(table.dtype)
