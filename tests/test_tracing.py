"""The program's own record of a training run: the DBP driver's profiler
spans, the named scopes that split the FWP window program, and the
window's unique-key counter.

Spans are read back from a trace recorded here, on the CPU backend; the
scopes from the ``op_name`` metadata of the compiled window program; the
counter against a numpy count of the batches' unique keys.
"""
import glob
import os
import re
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import Session
from repro.api.streams import resolve_stream
from repro.core.dbp.pipeline import PipelineStats
from repro.data.pipeline import make_cluster_transform

DBP_SPANS = ("dbp.input_wait", "dbp.h2d", "dbp.plan", "dbp.retrieve",
             "dbp.window", "dbp.sync", "dbp.commit", "dbp.drain")
SCOPES = ("fwp_attention", "fwp_sparse", "fwp_optimizer")
STEPS = 4


def tiny_session(arch="hstu-industrial", **kw):
    return Session.from_arch(arch, mode="nestpipe", reduced=True,
                             global_batch=8, seq_len=32, n_micro=2, **kw)


def window_args(sess):
    """The window program's arguments for the stream's first batch, as the
    driver builds them (stages 1-4a, no pipelining)."""
    wl = sess.workload
    host = next(iter(resolve_stream(wl, sess.data_seed)))
    b = make_cluster_transform(wl.n_micro, "keycentric")(host)
    batch = {k: jnp.asarray(b[k]) for k in wl.batch_shapes}
    carry = sess.fns.init_carry(sess.state.table, batch["keys"])
    return sess.state, carry.buffer, carry.plan, batch


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A tiny ``Session.train`` inside the benchmark's window span, under
    the profiler; returns the host events of the trace."""
    sess = tiny_session(metrics_every=2)
    sess.train(1)  # compile outside the trace
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        sess.train(STEPS)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("dbp.", "bench.")):
                        events.append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       dict(ev.stats)))
    return events


def test_every_dbp_span_inside_the_window(traced_run):
    (win,) = [e for e in traced_run if e[0] == "bench.window"]
    spans = [e for e in traced_run if e[0].startswith("dbp.")]
    assert set(DBP_SPANS) <= {e[0] for e in spans}, Counter(
        e[0] for e in spans)
    assert all(win[1] <= s <= e <= win[2] for _, s, e, _ in spans)


def test_one_window_span_per_step_with_its_step_number(traced_run):
    steps = [st.get("step_num") for name, _, _, st in traced_run
             if name == "dbp.window"]
    assert sorted(steps) == list(range(STEPS))
    # stages that run once a step: the queue read and the commit
    n = Counter(e[0] for e in traced_run)
    assert n["dbp.commit"] == STEPS and n["dbp.input_wait"] >= STEPS


@pytest.mark.parametrize("arch", ["hstu-industrial", "fuxi-kuairand"])
def test_window_program_carries_the_scopes(arch):
    sess = tiny_session(arch)
    text = jax.jit(sess.fns.window_step).lower(
        *window_args(sess)).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(scope in p.split("/") for p in paths), scope
    # backward and recompute ops keep the attention scope in their path
    assert any("transpose" in p and "fwp_attention" in p.split("/")
               for p in paths)


def test_buffer_keys_valid_counts_the_windows_unique_keys():
    sess = tiny_session()
    args = window_args(sess)
    _, aux, _ = jax.jit(sess.fns.window_step)(*args)
    keys = np.asarray(args[3]["keys"])
    assert int(aux["buffer_keys_valid"]) == len(np.unique(keys))
    assert int(aux["buffer_keys_valid"]) < args[1].keys.shape[0]


def test_driver_sums_the_counter_and_records_the_capacity():
    sess = tiny_session()
    k = window_args(sess)[1].keys.shape[0]
    rep = sess.train(3)
    want = 0
    for _, host in zip(range(3), resolve_stream(sess.workload,
                                                sess.data_seed)):
        want += len(np.unique(host["keys"]))
    assert rep.stats.buffer_rows == k
    assert rep.stats.buffer_keys_valid == want


def test_summary_drops_fields_nothing_reads():
    s = PipelineStats(step_times=[1.0, 2.0], losses=[0.5, 0.4]).summary()
    assert "p50_step_s" not in s and "p99_step_s" not in s
    assert not hasattr(PipelineStats(), "h2d_times")
    rep = tiny_session().train(2)
    assert "qps" not in rep.summary
    assert rep.summary["steps"] == 2
