"""Device-local sparse-key routing primitives (static shapes, SPMD-safe).

All functions here operate on per-device local arrays and contain NO
collectives — the All2All exchange lives in ``engine.py``. Everything uses
fixed capacities with sentinel padding so the whole pipeline stays
shape-static under jit/shard_map, per DESIGN.md §7.

Key conventions
---------------
* ``SENTINEL`` marks an empty slot. Sentinel keys sort last (int32 max).
* Keys entering the engine are already *scrambled* (bijective affine mix,
  see ``table.py``) so contiguous row-range sharding is load-balanced.
* ``owner(k) = k // rows_per_shard``; ``local_row(k) = k - owner * rows_per_shard``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = jnp.iinfo(jnp.int32).max


class UniqueResult(NamedTuple):
    """Fixed-capacity deduplication of a local key multiset."""

    unique_keys: jax.Array  # (U_max,) int32, sorted ascending, SENTINEL-padded
    inverse: jax.Array  # (L,) int32: position -> unique slot (U_max for invalid)
    n_unique: jax.Array  # () int32
    overflow: jax.Array  # () int32: uniques dropped because U_max too small


class BucketResult(NamedTuple):
    """Owner-bucketed send layout for a unique key set."""

    send_keys: jax.Array  # (S, C) int32, SENTINEL-padded
    slot_of_unique: jax.Array  # (U_max,) int32: unique slot -> flat send slot (S*C for invalid)
    overflow: jax.Array  # () int32: keys dropped because C too small


def fixed_unique_window(keys: jax.Array, u_max: int) -> UniqueResult:
    """Window-fused sort-based dedup: N independent lookup units in ONE pass.

    ``keys``: (N, L) int32, may contain SENTINEL padding. One batched sort
    over the whole (N, L) block plus vectorized compaction produces, for
    every row independently, exactly what :func:`fixed_unique` produces for
    that row — leaves carry a leading N axis (``unique_keys`` (N, u_max),
    ``inverse`` (N, L), ``n_unique``/``overflow`` (N,)). Uniques beyond
    ``u_max`` are dropped per row (counted in ``overflow``).
    """
    n, L = keys.shape
    order = jnp.argsort(keys, axis=1)
    sk = jnp.take_along_axis(keys, order, axis=1)
    valid = sk != SENTINEL
    is_new = jnp.concatenate(
        [valid[:, :1], (sk[:, 1:] != sk[:, :-1]) & valid[:, 1:]], axis=1
    )
    uid_sorted = jnp.cumsum(is_new, axis=1) - 1  # unique id per sorted position
    n_unique = jnp.sum(is_new, axis=1).astype(jnp.int32)

    # Compact unique keys into the fixed per-row buffers via one flat scatter
    # (row r's slot u lives at r * u_max + u; out-of-capacity -> n * u_max,
    # which mode="drop" discards).
    row = jnp.arange(n, dtype=jnp.int32)[:, None]
    keep = is_new & (uid_sorted < u_max)
    dst = jnp.where(keep, row * u_max + uid_sorted, n * u_max)
    unique_keys = (
        jnp.full((n * u_max,), SENTINEL, jnp.int32)
        .at[dst.reshape(-1)]
        .set(sk.reshape(-1), mode="drop")
        .reshape(n, u_max)
    )

    # Inverse map back to original positions; invalid/overflowed -> u_max.
    inv_sorted = jnp.where(valid & (uid_sorted < u_max), uid_sorted, u_max)
    inverse = (
        jnp.zeros((n, L), jnp.int32).at[row, order].set(inv_sorted.astype(jnp.int32))
    )
    overflow = jnp.maximum(n_unique - u_max, 0).astype(jnp.int32)
    return UniqueResult(unique_keys, inverse, n_unique, overflow)


def fixed_unique(keys: jax.Array, u_max: int) -> UniqueResult:
    """Sort-based dedup into a fixed-size buffer.

    ``keys``: (L,) int32, may contain SENTINEL padding. Returns sorted unique
    keys padded to ``u_max`` and the inverse map for gathers. Uniques beyond
    ``u_max`` are dropped (counted in ``overflow``) — configure capacity so
    this never happens in production; tests assert overflow == 0.

    Single-row view of :func:`fixed_unique_window` (one implementation, two
    arities).
    """
    res = fixed_unique_window(keys[None], u_max)
    return UniqueResult(
        res.unique_keys[0], res.inverse[0], res.n_unique[0], res.overflow[0]
    )


def owner_of(keys: jax.Array, rows_per_shard: int, num_shards: int) -> jax.Array:
    """THE ownership hash: shard that owns each (scrambled) key.

    ``owner(k) = k // rows_per_shard`` (clamped to the last shard for the
    padding tail), sentinels -> the virtual shard ``num_shards``. Every
    owner-partitioned structure in the system — the All2All send buckets
    here, the per-shard slices of ``WindowPlan.buffer_keys``, and the
    ``core.store.ShardedStore`` DRAM-master shards — uses this one function.
    Host callers pass numpy arrays and STAY on numpy (the sharded tier
    calls this on its retrieve/commit path; bouncing host keys through a
    device round trip there would be exactly the host-stage latency the
    async executor works to hide).
    """
    xp = jnp if isinstance(keys, jax.Array) else np
    owner = xp.minimum(keys // rows_per_shard, num_shards - 1)
    return xp.where(keys != SENTINEL, owner, num_shards)


def owner_of_2d(
    keys: jax.Array, rows_per_shard: int, num_cols: int, num_rows: int
):
    """2D ownership: each (scrambled) key -> a ``(col_shard, row_shard)``
    mesh coordinate on a ``num_cols x num_rows`` sparse grid.

    The 2D owner is a pure factorization of the flat one —
    ``flat = owner_of(k, rows_per_shard, num_cols * num_rows)`` and
    ``(col, row) = (flat // num_rows, flat % num_rows)`` — so the column
    axis carves the scrambled key space into ``num_cols`` contiguous
    "table groups" (under the affine scramble each group holds a balanced
    slice of every logical table) and the row axis row-shards within a
    group. Column-major-over-row matches both ``EmbeddingEngine._shard_id``
    (axis-0-major flat device id over ``sparse_axes``) and the block order
    of ``PartitionSpec((ax0, ax1))``, which is what lets the stage-3 key
    exchange factor into a table-group All2All followed by a row-group
    All2All with bit-identical routing.

    ``owner_of`` is the degenerate 1-column case: with ``num_cols == 1``
    the returned ``row`` coordinate reproduces
    ``owner_of(keys, rows_per_shard, num_rows)`` bit for bit (sentinel
    handling included). Sentinels never acquire an owner: they map to the
    virtual coordinate ``(num_cols, num_rows)``. Numpy in -> numpy out,
    same as :func:`owner_of`.
    """
    xp = jnp if isinstance(keys, jax.Array) else np
    flat = owner_of(keys, rows_per_shard, num_cols * num_rows)
    valid = keys != SENTINEL
    col = xp.where(valid, flat // num_rows, num_cols)
    row = xp.where(valid, flat % num_rows, num_rows)
    return col, row


def bucket_by_owner_window(
    unique_keys: jax.Array, num_shards: int, capacity: int, rows_per_shard: int
) -> BucketResult:
    """Window-fused owner bucketing: (N, U) sorted-unique rows -> (N, S, C).

    Per-row semantics identical to :func:`bucket_by_owner`; leaves carry a
    leading N axis (``send_keys`` (N, S, C), ``slot_of_unique`` (N, U),
    ``overflow`` (N,)). Group starts come from a batched searchsorted (the
    rows are independently sorted, so owners are grouped within each row).
    """
    n, u_max = unique_keys.shape
    valid = unique_keys != SENTINEL
    owner = owner_of(unique_keys, rows_per_shard, num_shards)

    # group start of each owner within each sorted row
    shard_ids = jnp.arange(num_shards + 1)
    starts = jax.vmap(
        lambda o: jnp.searchsorted(o, shard_ids, side="left")
    )(owner)  # (N, S+1)
    pos_in_group = jnp.arange(u_max)[None, :] - jnp.take_along_axis(
        starts, jnp.minimum(owner, num_shards), axis=1
    )
    in_cap = pos_in_group < capacity
    dest = jnp.where(
        valid & in_cap, owner * capacity + pos_in_group, num_shards * capacity
    )

    # One flat scatter builds all N send buffers (row offset n*S*C drops).
    row = jnp.arange(n, dtype=jnp.int32)[:, None]
    flat_sc = num_shards * capacity
    dst = jnp.where(dest < flat_sc, row * flat_sc + dest, n * flat_sc)
    send_keys = (
        jnp.full((n * flat_sc,), SENTINEL, jnp.int32)
        .at[dst.reshape(-1)]
        .set(unique_keys.reshape(-1), mode="drop")
        .reshape(n, num_shards, capacity)
    )
    overflow = jnp.sum(valid & ~in_cap, axis=1).astype(jnp.int32)
    return BucketResult(send_keys, dest.astype(jnp.int32), overflow)


def bucket_by_owner(
    unique_keys: jax.Array, num_shards: int, capacity: int, rows_per_shard: int
) -> BucketResult:
    """Bucket sorted-unique keys by destination shard into (S, C) send buffers.

    Because ``unique_keys`` is sorted and owners are contiguous ranges, keys
    are already grouped by owner; the rank within each owner group is
    ``arange - group_start``. Single-row view of
    :func:`bucket_by_owner_window`.
    """
    res = bucket_by_owner_window(
        unique_keys[None], num_shards, capacity, rows_per_shard
    )
    return BucketResult(res.send_keys[0], res.slot_of_unique[0], res.overflow[0])


def gather_rows(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """rows[idx] with out-of-range -> 0 (sentinel-safe gather)."""
    return jnp.take(rows, idx, axis=0, mode="fill", fill_value=0)


def segment_rowsum(values: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    """Sum rows of ``values`` into ``num_segments`` buckets (drop out-of-range).

    ``values``: (L, D); ``segment_ids``: (L,) with id == num_segments meaning
    "drop". Accumulates in f32 regardless of input dtype.
    """
    acc = jnp.zeros((num_segments, values.shape[-1]), jnp.float32)
    return acc.at[segment_ids].add(values.astype(jnp.float32), mode="drop")


def sorted_lookup(sorted_keys: jax.Array, queries: jax.Array) -> jax.Array:
    """Index of each query in a sorted sentinel-padded key buffer.

    Returns len(sorted_keys) (== miss) for queries not present. Used only by
    :func:`intersect_sorted` (the DBP dual-buffer sync): the window's lookups
    take each key's buffer slot from routing instead.
    """
    n = sorted_keys.shape[0]
    idx = jnp.searchsorted(sorted_keys, queries, side="left")
    idx_c = jnp.minimum(idx, n - 1)
    hit = (sorted_keys[idx_c] == queries) & (queries != SENTINEL)
    return jnp.where(hit, idx_c, n).astype(jnp.int32)


def intersect_sorted(keys_a: jax.Array, keys_b: jax.Array):
    """For each slot of ``keys_b``, the matching slot in ``keys_a`` (or len(a)).

    Both inputs sorted + sentinel padded. This is the DBP dual-buffer
    intersection: rows of the active buffer (a) that must overwrite rows of
    the prefetch buffer (b).
    """
    return sorted_lookup(keys_a, keys_b)
