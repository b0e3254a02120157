"""Fused window routing == per-micro-batch reference (ISSUE 2 tentpole).

The window route must produce, for every micro-batch independently, exactly
what routing each micro-batch alone produces — sentinel padding, capacity
overflow and all — while containing no Python loop over micro-batches
(asserted structurally: the jaxpr's sort count does not scale with N).

The per-row reference here is an INDEPENDENT numpy reimplementation of the
dedup/bucketing semantics, not a second call into the jax code under test.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import NestPipeConfig
from repro.core.embedding.engine import EmbeddingEngine
from repro.core.embedding.routing import (
    SENTINEL,
    bucket_by_owner_window,
    fixed_unique,
    fixed_unique_window,
    sorted_lookup,
)
from repro.core.embedding.table import make_mega_table_spec
from repro.utils import round_up


# ---------------------------------------------------------------------------
# independent numpy references (single-row semantics)
# ---------------------------------------------------------------------------


def np_fixed_unique(keys: np.ndarray, u_max: int):
    valid_keys = keys[keys != SENTINEL]
    uniq = np.unique(valid_keys)
    kept = uniq[:u_max]
    unique_keys = np.full(u_max, SENTINEL, np.int64)
    unique_keys[: len(kept)] = kept
    slot = {int(k): i for i, k in enumerate(kept)}
    inverse = np.array(
        [slot.get(int(k), u_max) if k != SENTINEL else u_max for k in keys],
        np.int64,
    )
    overflow = max(len(uniq) - u_max, 0)
    return unique_keys, inverse, len(uniq), overflow


def np_bucket_by_owner(unique_keys: np.ndarray, num_shards: int, capacity: int,
                       rows_per_shard: int):
    u_max = len(unique_keys)
    send = np.full((num_shards, capacity), SENTINEL, np.int64)
    slots = np.full(u_max, num_shards * capacity, np.int64)
    counts = np.zeros(num_shards, np.int64)
    overflow = 0
    for i, k in enumerate(unique_keys):  # rows arrive sorted; sentinels last
        if k == SENTINEL:
            continue
        owner = min(int(k) // rows_per_shard, num_shards - 1)
        p = counts[owner]
        counts[owner] += 1
        if p < capacity:
            send[owner, p] = k
            slots[i] = owner * capacity + p
        else:
            overflow += 1
    return send, slots, overflow


# ---------------------------------------------------------------------------
# primitive-level equivalence (property tests)
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(n=st.sampled_from([1, 2, 4]), l=st.integers(1, 80),
       vocab=st.integers(2, 300), u_max_pad=st.integers(0, 24),
       seed=st.integers(0, 2**16))
def test_fixed_unique_window_matches_per_row_reference(n, l, vocab, u_max_pad,
                                                       seed):
    """Random multisets incl. sentinel padding AND capacity overflow."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, vocab, size=(n, l)).astype(np.int32)
    # sprinkle sentinel padding at random positions
    keys[rng.random((n, l)) < 0.2] = SENTINEL
    # small u_max so overflow actually happens in some draws
    u_max = max(4, min(l, 8) + u_max_pad)
    got = fixed_unique_window(jnp.asarray(keys), u_max)
    for i in range(n):
        uk, inv, n_uniq, ovf = np_fixed_unique(keys[i], u_max)
        np.testing.assert_array_equal(np.asarray(got.unique_keys[i]), uk)
        np.testing.assert_array_equal(np.asarray(got.inverse[i]), inv)
        assert int(got.n_unique[i]) == n_uniq
        assert int(got.overflow[i]) == ovf


@settings(max_examples=25, deadline=None)
@given(n=st.sampled_from([1, 2, 4]), nk=st.integers(0, 60),
       shards=st.sampled_from([1, 2, 4, 8]), cap=st.integers(1, 24),
       seed=st.integers(0, 2**16))
def test_bucket_by_owner_window_matches_per_row_reference(n, nk, shards, cap,
                                                          seed):
    rng = np.random.default_rng(seed)
    rows_per_shard = 32
    vocab = shards * rows_per_shard
    u_max = round_up(max(nk, 8), 8)
    rows = np.full((n, u_max), SENTINEL, np.int32)
    for i in range(n):
        uniq = np.unique(rng.integers(0, vocab, size=nk).astype(np.int32)) \
            if nk else np.array([], np.int32)
        rows[i, : len(uniq)] = uniq  # sorted unique, sentinel padded
    got = bucket_by_owner_window(jnp.asarray(rows), shards, cap, rows_per_shard)
    for i in range(n):
        send, slots, ovf = np_bucket_by_owner(rows[i], shards, cap,
                                              rows_per_shard)
        np.testing.assert_array_equal(np.asarray(got.send_keys[i]), send)
        np.testing.assert_array_equal(np.asarray(got.slot_of_unique[i]), slots)
        assert int(got.overflow[i]) == ovf


# ---------------------------------------------------------------------------
# engine-level: route_window == per-micro-batch route, N in {1, 2, 4}
# ---------------------------------------------------------------------------


def make_engine(unique_capacity_factor=2.0, bucket_slack=4.0):
    spec = make_mega_table_spec(None, vocab_size=512, dim=8, num_shards=1)
    cfg = NestPipeConfig(unique_capacity_factor=unique_capacity_factor,
                         bucket_slack=bucket_slack)
    return spec, EmbeddingEngine(spec, None, ("model",), P(None, None), cfg,
                                 compute_dtype=jnp.float32)


@pytest.mark.parametrize("n_micro", [1, 2, 4])
@pytest.mark.parametrize("factor", [2.0, 0.25])  # 0.25 forces overflow
def test_route_window_equals_per_micro_batch_reference(n_micro, factor):
    spec, eng = make_engine(unique_capacity_factor=factor)
    rng = np.random.default_rng(n_micro)
    keys = np.asarray(
        spec.scramble(jnp.asarray(
            rng.integers(0, 512, size=(n_micro, 8, 4)).astype(np.int32)))
    )
    window = eng.route_window(jnp.asarray(keys), n_micro)
    dims = eng.dims(keys.shape[1:], n_micro)
    recv_sets = []
    for i in range(n_micro):
        ref_plan = eng._route_one(jnp.asarray(keys[i]).reshape(-1), dims)
        got_plan = jax.tree.map(lambda x: x[i], window.plans)
        # every leaf but buffer_slot, which indexes the window's union
        # (test_buffer_slot_equals_search_of_buffer_keys checks it)
        for field in ref_plan._fields:
            if field != "buffer_slot":
                np.testing.assert_array_equal(
                    np.asarray(getattr(got_plan, field)),
                    np.asarray(getattr(ref_plan, field)))
        recv_sets.append(np.asarray(ref_plan.recv_keys).reshape(-1))
    if factor == 0.25:
        assert int(eng.overflow_metric(window)) > 0  # overflow path exercised
    # buffer keys are the sorted union of all received key sets
    want_union = np.asarray(fixed_unique(
        jnp.asarray(np.concatenate(recv_sets)), dims.buffer_cap).unique_keys)
    np.testing.assert_array_equal(np.asarray(window.buffer_keys), want_union)


@pytest.mark.parametrize("case", [
    "no_overflow", "unique_overflow", "bucket_overflow", "sentinel_heavy",
    "psum_union",
])
def test_buffer_slot_equals_search_of_buffer_keys(case):
    """The slot routing hands the window is, at every received position,
    exactly what a binary search of the buffer keys finds (K for a sentinel
    or a key that overflowed its capacity)."""
    if case == "psum_union":
        # the union over replicated axes needs a mesh: 8 virtual devices,
        # in a process of its own (tests/scenarios/buffer_slot_multidev.py)
        from test_multidevice import run_scenario

        assert "BUFFER SLOT OK" in run_scenario("buffer_slot_multidev.py")
        return
    factor, slack = {"unique_overflow": (0.25, 4.0),
                     "bucket_overflow": (2.0, 0.25)}.get(case, (2.0, 4.0))
    spec, eng = make_engine(unique_capacity_factor=factor, bucket_slack=slack)
    n_micro = 4
    rng = np.random.default_rng(11)
    keys = np.array(spec.scramble(jnp.asarray(
        rng.integers(0, 512, size=(n_micro, 8, 4)).astype(np.int32))))
    if case == "sentinel_heavy":
        keys[rng.random(keys.shape) < 0.8] = SENTINEL
    window = eng.route_window(jnp.asarray(keys), n_micro)
    overflowed = case in ("unique_overflow", "bucket_overflow")
    assert (int(eng.overflow_metric(window)) > 0) == overflowed
    recv = window.plans.recv_keys
    want = sorted_lookup(window.buffer_keys, recv.reshape(-1))
    np.testing.assert_array_equal(
        np.asarray(window.plans.buffer_slot),
        np.asarray(want).reshape(recv.shape))


def test_route_window_sort_count_does_not_scale_with_n():
    """Structural no-Python-loop assertion: the number of sort ops in the
    lowered route is constant in N (one window-wide key sort + one union
    sort), so routing work per micro-batch amortizes exactly as the paper's
    lookahead argument requires."""
    def count_sorts(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                total += 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):  # closed sub-jaxprs (scan/cond/...)
                    total += count_sorts(v.jaxpr)
        return total

    counts = {}
    for n in (1, 2, 4):
        spec, eng = make_engine()
        dims = eng.dims((8, 4), n)
        jaxpr = jax.make_jaxpr(
            lambda k: eng._route_window_local(k, dims)
        )(jnp.zeros((n, 8, 4), jnp.int32))
        counts[n] = count_sorts(jaxpr.jaxpr)
    assert counts[1] == counts[2] == counts[4], counts
    assert counts[4] <= 3, counts  # window key sort + union sort (+ nothing per-mb)


def test_serial_lookup_reuses_fused_route():
    """lookup_from_master (serial / serving) routes through the same fused
    window path (N=1 view) and still serves exact embeddings."""
    spec, eng = make_engine()
    rng = np.random.default_rng(7)
    raw = rng.integers(0, 512, size=(8, 4)).astype(np.int32)
    keys = spec.scramble(jnp.asarray(raw))
    from repro.core.embedding import init_table_state

    table = init_table_state(jax.random.PRNGKey(0), spec, None, ("model",))
    emb, plan = eng.lookup_from_master(table, keys)
    np.testing.assert_array_equal(
        np.asarray(emb),
        np.asarray(table.rows)[np.asarray(keys).reshape(-1)].reshape(8, 4, -1),
    )
    # the plan is exactly the N=1 fused route
    dims = eng.dims(keys.shape, 1)
    ref = eng._route_one(jnp.asarray(keys).reshape(-1), dims)
    for got_leaf, ref_leaf in zip(plan, ref):
        np.testing.assert_array_equal(np.asarray(got_leaf), np.asarray(ref_leaf))


def test_window_lookup_and_update_search_nothing():
    """Structural: the window's buffer lookups, gradient packets and buffer
    update hold no search loop (a ``jnp.searchsorted`` lowers to a scan):
    every buffer row they touch comes from the plan's ``buffer_slot``."""
    def count_loops(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name in ("scan", "while")
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):  # closed sub-jaxprs (pjit/shard_map)
                    total += count_loops(getattr(v.jaxpr, "jaxpr", v.jaxpr))
        return total

    spec, eng = make_engine()
    from repro.core.embedding import init_table_state

    n_micro, mb_shape = 4, (8, 4)
    keys = spec.scramble(jnp.arange(n_micro * 32, dtype=jnp.int32)
                         .reshape((n_micro,) + mb_shape))
    window = eng.route_window(keys, n_micro)
    table = init_table_state(jax.random.PRNGKey(0), spec, None, ("model",))
    buf = eng.retrieve(table, window)

    def window_sparse(buf, plans):
        packets = []
        for i in range(n_micro):
            plan = jax.tree.map(lambda x: x[i], plans)
            emb = eng.lookup_from_buffer(buf, plan, mb_shape, n_micro)
            packets.append(eng.grads_to_owner(plan, emb, mb_shape, n_micro))
        pkts = jax.tree.map(lambda *xs: jnp.stack(xs), *packets)
        return eng.apply_window_to_buffer(buf, pkts)

    jaxpr = jax.make_jaxpr(window_sparse)(buf, window.plans)
    assert count_loops(jaxpr.jaxpr) == 0
