"""The trace reduction and the trace-reading metrics on a small trace
recorded here, on the CPU backend."""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import pytest

from bench import spec
from bench import trace as tr


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two programs named like the program's window and retrieval, run in
    turn inside the benchmark's window span, with host sleeps between."""
    import time

    def window_step(x):
        return jnp.tanh(x @ x) @ x

    def retrieve(x):
        return jnp.sort(x, axis=0)

    w, r = jax.jit(window_step), jax.jit(retrieve)
    x = jnp.ones((384, 384), jnp.float32)
    w(x).block_until_ready()
    r(x).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            w(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host.pause"):
                time.sleep(0.02)
            r(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.load(tr.find_xplane(str(d)))


def test_programs_found_by_name(recorded):
    dev = recorded.devices[0]
    mods = {o.module for o in recorded.ops[dev]}
    assert any("window_step" in m for m in mods), mods
    assert any("retrieve" in m for m in mods), mods
    for name in ("window_step", "retrieve"):
        assert tr.op_seconds(recorded, dev, lambda o: name in o.module) > 0


def test_busy_within_window_and_idle_share_a_share(recorded):
    assert 0 < tr.busy_s(recorded) <= recorded.window_s
    for d in recorded.devices:
        assert 0.0 <= tr.idle_share(recorded, d) <= 1.0
        # three 20 ms host pauses with nothing on the device
        assert tr.idle_share(recorded, d) * recorded.window_s >= 0.05


def test_idle_gaps_named_by_host_and_longest_first(recorded):
    gaps = tr.idle_gaps(recorded, recorded.devices[0], n=3)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[0][1] >= 0.015
    assert all(name == "host.pause" for name, _ in gaps)


def test_top_ops_sum_to_at_most_busy(recorded):
    ops = tr.top_ops(recorded, n=1000)
    assert ops and sum(s for _, s in ops) >= tr.busy_s(recorded) * 0.999


def _window(trace, steps=3, **kw):
    cell = types.SimpleNamespace(chips=1)
    return types.SimpleNamespace(trace=trace, steps=steps, cell=cell,
                                 samples=steps, **kw)


def test_trace_readers(recorded):
    win = spec.metric_reader("fwp.window_ms")(_window(recorded))
    sparse = spec.metric_reader("engine.sparse_ms")(_window(recorded))
    idle = spec.metric_reader("device.idle_pct")(_window(recorded))
    assert win > 0 and sparse > 0
    assert (win + sparse) * 3 * 1e-3 <= recorded.window_s
    assert 0 < idle < 100
    # no all-to-all on one device: nothing to read, so nothing is reported
    assert spec.metric_reader("engine.a2a_exposed_ms")(
        _window(recorded)) is None


def test_mfu_reader_against_hand_count(recorded):
    w = _window(recorded, flops_per_sample=1e12,
                peaks={"bf16_flops": 2e12})
    got = spec.metric_reader("mfu_pct")(w)
    assert got == pytest.approx(100 * 3e12 / (recorded.window_s * 2e12))
    assert spec.metric_reader("mfu_pct")(
        _window(None, flops_per_sample=1.0, peaks=None)) is None


@pytest.mark.parametrize("a,b,inter", [
    ([(0, 10), (20, 30)], [(5, 25)], [(5, 10), (20, 25)]),
    ([(0, 10)], [(10, 20)], []),
    ([(0, 100)], [(10, 20), (30, 40)], [(10, 20), (30, 40)]),
])
def test_interval_arithmetic(a, b, inter):
    assert tr.intersect(a, b) == inter
    assert tr.union(a + b) == tr.union(tr.union(a) + tr.union(b))
    assert tr.length(tr.union(a + b)) == (tr.length(a) + tr.length(b)
                                          - tr.length(inter))


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


GATHER_HLO = ('%embedding_gather.3 = f32[1024,512]{1,0} custom-call('
              's32[8,1,128]{2,1,0} %p0, f32[4096,512]{1,0} %p1, '
              'f32[8,512]{1,0} %p2), custom_call_target="tpu_custom_call"')


@pytest.fixture
def device_planes():
    """A trace laid out as on a chip: a host plane with the window span and
    a device plane whose ops carry no program stat and sit inside the runs
    of the ``XLA Modules`` line."""
    host = _plane("/host:CPU", python=[
        _ev(tr.WINDOW, 1_000, 10_000), _ev("driver.wait", 6_000, 2_500)])
    dev = _plane(
        "/device:TPU:0",
        XLA_Modules=[_ev("jit_window_step(7)", 2_000, 3_000),
                     _ev("jit_retrieve(9)", 8_500, 2_000)],
        XLA_Ops=[_ev("fusion.1", 2_000, 1_000),
                 _ev("embedding_gather.3", 3_000, 2_000,
                     long_name=GATHER_HLO),
                 _ev("sort.2", 8_500, 2_000),
                 _ev("fusion.9", 100, 200)])  # before the window
    return [host, dev]


def test_device_plane_ops_take_their_program(device_planes):
    t = tr.from_planes(device_planes)
    assert t.devices == ["/device:TPU:0"] and t.window == (1_000, 11_000)
    ops = t.ops["/device:TPU:0"]
    assert [(o.name, o.module) for o in ops] == [
        ("fusion.1", "jit_window_step"),
        ("embedding_gather.3", "jit_window_step"), ("sort.2", "jit_retrieve")]
    assert tr.busy_s(t) == pytest.approx(5_000e-9)
    assert tr.idle_gaps(t, "/device:TPU:0", n=1) == [
        ("driver.wait", pytest.approx(3_500e-9))]
    w = _window(t, steps=1, peaks={"hbm_bytes_per_s": 1e12,
                                   "bf16_flops": 1e15})
    assert spec.metric_reader("fwp.window_ms")(w) == pytest.approx(3_000e-6)
    assert spec.metric_reader("engine.sparse_ms")(w) == pytest.approx(
        2_000e-6)
    # 1024 rows of 512 f32 read and written, 1024 indices, in 2 us
    least = (1024 * 512 * 8 + 4 * 1024) / 1e12
    assert spec.metric_reader("gather_rows_roofline")(w) == pytest.approx(
        100 * least / 2_000e-9)
    # no segment-sum call in the window: nothing to read
    assert spec.metric_reader("segment_rowsum_roofline")(w) is None


def test_kernel_without_shapes_is_an_error(device_planes):
    ops = device_planes[1].lines[1].events
    ops[1] = _ev("embedding_gather.3", 3_000, 2_000)
    w = _window(tr.from_planes(device_planes), steps=1,
                peaks={"hbm_bytes_per_s": 1e12, "bf16_flops": 1e15})
    with pytest.raises(ValueError, match="embedding_gather"):
        spec.metric_reader("gather_rows_roofline")(w)


def test_kernel_named_by_its_hlo_text(device_planes):
    """On a TPU an operation's name is its HLO text, with no stat that
    holds it; the reader takes the kernel's name and shapes from there."""
    ops = device_planes[1].lines[1].events
    ops[1] = _ev(GATHER_HLO, 3_000, 2_000)
    w = _window(tr.from_planes(device_planes), steps=1,
                peaks={"hbm_bytes_per_s": 1e12, "bf16_flops": 1e15})
    least = (1024 * 512 * 8 + 4 * 1024) / 1e12
    assert spec.metric_reader("gather_rows_roofline")(w) == pytest.approx(
        100 * least / 2_000e-9)
