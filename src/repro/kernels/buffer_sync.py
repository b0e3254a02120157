"""Pallas TPU kernel: DBP dual-buffer intersection row copy.

The paper's "dedicated kernel" (§IV-B): given per-row source slots into the
active buffer (len(active) == miss), overwrite prefetch-buffer rows whose
key intersects the active buffer. The searchsorted intersection runs ahead
of time on compact key sets; this kernel performs the indexed row copy.

Active rows are fetched with the row-DMA gather of ``embedding_gather``
(the active buffer stays in HBM); the prefetch rows stream through VMEM in
``BLOCK_ROWS`` blocks and hit(src < rows_active) selects per row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..utils import cdiv
from .embedding_gather import (
    BLOCK_ROWS,
    as_tileable,
    blocked_indices,
    fetch_rows,
    index_spec,
    row_scratch,
    table_specs,
    tail_rows,
)


def _sync_kernel(clamped_ref, src_ref, active_hbm, active_tail, prefetch_ref,
                 out_ref, tiles, sem, *, rows_active: int):
    row = fetch_rows(clamped_ref, active_hbm, active_tail, tiles, sem)

    def put(r, c):
        hit = src_ref[0, 0, r] < rows_active
        keep = prefetch_ref[pl.ds(r, 1), :]
        out_ref[pl.ds(r, 1), :] = jnp.where(hit, row(r), keep)
        return c

    jax.lax.fori_loop(0, BLOCK_ROWS, put, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def buffer_sync_rows(
    active_rows: jax.Array,  # (Ka, D)
    prefetch_rows: jax.Array,  # (Kp, D)
    src: jax.Array,  # (Kp,) int32: slot in active or >= Ka for miss
    *,
    interpret: bool,
) -> jax.Array:
    ka, d = active_rows.shape
    kp = prefetch_rows.shape[0]
    active = as_tileable(active_rows)
    prefetch = as_tileable(prefetch_rows)
    # the unclamped src decides hit/miss; the clamped copy addresses the DMA
    src = src.astype(jnp.int32)
    clamped = jnp.clip(src, 0, ka - 1)
    dp = active.shape[1]
    out = pl.pallas_call(
        functools.partial(_sync_kernel, rows_active=ka),
        grid=(cdiv(kp, BLOCK_ROWS),),
        in_specs=[index_spec(), index_spec(), *table_specs(active),
                  pl.BlockSpec((BLOCK_ROWS, dp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, dp), prefetch.dtype),
        scratch_shapes=row_scratch(active),
        interpret=interpret,
    )(blocked_indices(clamped), blocked_indices(src), active,
      tail_rows(active), prefetch)
    return out[:, :d].astype(prefetch_rows.dtype)
