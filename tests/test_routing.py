"""Routing-primitive invariants (hypothesis property tests).

These are the paper-critical invariants: dedup/bucketing must be lossless
(zero overflow at configured slack), the inverse map must reconstruct every
position, and the scrambler must be bijective + balanced under zipf skew.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.embedding.routing import (
    SENTINEL,
    bucket_by_owner,
    fixed_unique,
    intersect_sorted,
    sorted_lookup,
)
from repro.core.embedding.table import make_mega_table_spec
from repro.utils import round_up


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 200), vocab=st.integers(2, 500), seed=st.integers(0, 2**16),
       pad=st.integers(0, 20))
def test_fixed_unique_reconstructs(n, vocab, seed, pad):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, vocab, size=n).astype(np.int32)
    full = np.concatenate([keys, np.full(pad, SENTINEL, np.int32)])
    u_max = round_up(len(full), 8)
    res = fixed_unique(jnp.asarray(full), u_max)
    assert int(res.overflow) == 0
    uk = np.asarray(res.unique_keys)
    inv = np.asarray(res.inverse)
    # every real position maps back to its key
    for i, k in enumerate(keys):
        assert uk[inv[i]] == k
    # sentinel positions map out of range
    for i in range(n, n + pad):
        assert inv[i] == u_max
    # unique keys sorted, actually unique
    reals = uk[uk != SENTINEL]
    assert np.all(np.diff(reals) > 0)
    assert int(res.n_unique) == len(np.unique(keys))


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 128), shards=st.sampled_from([1, 2, 4, 8]),
       seed=st.integers(0, 2**16))
def test_bucket_by_owner_lossless(n, shards, seed):
    rng = np.random.default_rng(seed)
    rows_per_shard = 64
    vocab = shards * rows_per_shard
    keys = np.unique(rng.integers(0, vocab, size=n)).astype(np.int32)
    u_max = round_up(max(len(keys), 8), 8)
    uk = np.full(u_max, SENTINEL, np.int32)
    uk[: len(keys)] = np.sort(keys)
    cap = round_up(u_max, 8)  # generous capacity -> no overflow
    res = bucket_by_owner(jnp.asarray(uk), shards, cap, rows_per_shard)
    assert int(res.overflow) == 0
    send = np.asarray(res.send_keys)
    # every key appears exactly once in its owner's bucket
    for k in keys:
        owner = k // rows_per_shard
        assert k in send[owner], (k, owner)
    assert (send != SENTINEL).sum() == len(keys)
    # slot_of_unique round-trips
    slots = np.asarray(res.slot_of_unique)
    flat = send.reshape(-1)
    for i in range(len(keys)):
        assert flat[slots[i]] == uk[i]


@settings(max_examples=30, deadline=None)
@given(na=st.integers(0, 60), nb=st.integers(0, 60), seed=st.integers(0, 2**16))
def test_intersect_sorted(na, nb, seed):
    rng = np.random.default_rng(seed)
    a = np.unique(rng.integers(0, 100, size=na)).astype(np.int32) if na else \
        np.array([], np.int32)
    b = np.unique(rng.integers(0, 100, size=nb)).astype(np.int32) if nb else \
        np.array([], np.int32)
    ka = np.full(64, SENTINEL, np.int32); ka[: len(a)] = a
    kb = np.full(64, SENTINEL, np.int32); kb[: len(b)] = b
    idx = np.asarray(intersect_sorted(jnp.asarray(ka), jnp.asarray(kb)))
    for j in range(64):
        if kb[j] != SENTINEL and kb[j] in a:
            assert ka[idx[j]] == kb[j]
        else:
            assert idx[j] == 64


@settings(max_examples=20, deadline=None)
@given(vocab=st.integers(10, 100000), shards=st.sampled_from([1, 4, 16, 256]))
def test_scrambler_bijective(vocab, shards):
    spec = make_mega_table_spec(None, vocab_size=vocab, dim=8, num_shards=shards)
    n = min(vocab, 4096)
    keys = jnp.arange(n, dtype=jnp.int32)
    mixed = np.asarray(spec.scramble(keys))
    assert len(np.unique(mixed)) == n  # injective on the sample
    assert mixed.min() >= 0 and mixed.max() < spec.padded_rows


def test_scrambler_balances_zipf_unique_traffic():
    """What routing actually transmits is the DEDUPED key set per batch
    (engine dedups before the key All2All); the scrambler must balance the
    unique-key ownership across shards. (Raw multiset hotness of a single
    key is irreducible by any bijection — dedup is what absorbs it, which
    is exactly the paper's retrieval-stage design.)"""
    spec = make_mega_table_spec(None, vocab_size=100000, dim=8, num_shards=16)
    from repro.data.synthetic import _zipf
    rng = np.random.default_rng(0)
    raw = np.unique(_zipf(rng, 100000, 20000, a=1.3))  # batch-level dedup
    mixed = np.asarray(spec.scramble(jnp.asarray(raw.astype(np.int32))))
    owners = mixed // spec.rows_per_shard
    counts = np.bincount(owners, minlength=16)
    # without scrambling, zipf uniques are dense near 0 -> shard 0 hot:
    raw_counts = np.bincount(
        np.minimum(raw // spec.rows_per_shard, 15).astype(int), minlength=16)
    assert counts.max() / counts.mean() < 1.3, counts
    assert raw_counts.max() / raw_counts.mean() > 3.0  # skew existed


def test_merge_sorted_unique():
    """The buffer-key union of several key sets is ``fixed_unique`` over
    them flattened; its inverse is each position's buffer slot."""
    a = jnp.asarray(np.array([[3, 7, SENTINEL], [1, 3, 9]], np.int32))
    res = fixed_unique(a.reshape(-1), 8)
    out = np.asarray(res.unique_keys)
    reals = out[out != SENTINEL]
    np.testing.assert_array_equal(reals, [1, 3, 7, 9])
    np.testing.assert_array_equal(np.asarray(res.inverse), [1, 2, 8, 0, 1, 3])


def test_sorted_lookup_miss_and_hit():
    keys = jnp.asarray(np.array([2, 5, 9, SENTINEL], np.int32))
    q = jnp.asarray(np.array([5, 3, 9, SENTINEL], np.int32))
    idx = np.asarray(sorted_lookup(keys, q))
    np.testing.assert_array_equal(idx, [1, 4, 2, 4])
