"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Set-up builds the cell's ``Session`` through the
normal path with weights and master table made from the seed, runs the
three steps that the comparison reads through the same ``train`` call the
window uses, and one more warm call. The window is one measured ``train``
call of as many steps as fill ``--seconds`` at the warm step time, ended in
``block_until_ready``; a compilation inside it fails the run. With
``--trace 1`` the window runs under the profiler and the cell's per-layer
metrics are read from the trace; otherwise its end-to-end metrics are
reported. After the window the program's state is freed and the float32
reference (``bench/reference.py``) decides ``correct``.

The last line on standard output is the result as one JSON object; the
numbers compared, each with its limit, are the last lines on standard
error. Without a TPU, with fewer chips than the cell asks for, or on a
``device_kind`` missing from ``bench/peaks.json``, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

if __name__ == "__main__":
    # Run as a script, the interpreter puts bench/ first on the path, where
    # trace.py would shadow the standard library's module of that name.
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path = [p for p in sys.path if os.path.abspath(p or ".") != _here]
    sys.path.insert(0, os.path.dirname(_here))

from bench import check, program, reference, spec  # noqa: E402
from bench import trace as tr  # noqa: E402

GIB = float(1 << 30)
WINDOW_MARGIN = 1.15  # steps for 15% more than --seconds at the warm rate


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs traced, compiled or loaded from the persistent
    cache, process-wide (JAX's monitoring events)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, *_a, **_kw):
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program, the
    small ones too, so that a run after the first compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def find_devices(cell: spec.Cell, require_chip: bool):
    """The cell's devices and its peak-table entry; refuses a CPU, too few
    chips and a ``device_kind`` the table lacks."""
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"platform is {devs[0].platform!r}, not 'tpu'; the "
                         "benchmark never falls back to another device")
        if len(devs) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, found "
                         f"{len(devs)}")
        try:
            peaks = spec.peaks(devs[0].device_kind)
        except spec.SpecError as e:
            raise NoChip(str(e)) from None
    else:
        peaks = None
    return devs, peaks


@dataclass
class Window:
    """What the metric readers see of one measured (or traced) window."""

    cell: spec.Cell
    steps: int
    samples: int
    wall_s: float
    stats: Any  # the program's PipelineStats of the window's train call
    store_timers: Optional[Dict[str, float]]
    trace: Optional[tr.Trace]
    flops_per_sample: float
    peaks: Optional[Dict[str, float]]


def end_to_end(w: Window, setup_s: float, peak_bytes: int
               ) -> Dict[str, float]:
    return {"samples_per_s": w.samples / w.wall_s,
            "hbm_peak_gib": peak_bytes / GIB,
            "setup_s": setup_s}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, t0: float = _T0,
             trace_dir: Optional[str] = None,
             reader: Callable[[str], Callable] = spec.metric_reader,
             tamper: Optional[Callable[[Any], None]] = None,
             ) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object that ``main`` prints.
    ``trace_dir`` keeps the raw trace there; ``tamper(session)``, called on
    the fresh session, lets a test break the program underneath."""
    program.import_program()
    import jax

    devs, peaks = find_devices(cell, require_chip)
    used = devs[:cell.chips]
    if require_chip:
        log(f"compile cache {use_compile_cache()}")
    compiles = CompileCounter()
    log(f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"cell {cell.name}, seed {seed}")

    sess = program.build_session(cell, seed)
    if tamper is not None:
        tamper(sess)
    log(f"session built at {time.perf_counter() - t0!r} s")
    init = program.Initial(sess, cell, seed)
    sess.state = init.state()
    jax.block_until_ready(sess.state)
    log(f"weights and table made at {time.perf_counter() - t0!r} s")
    with program.bench_stream(sess, cell, seed):
        prog = program.check_steps(sess, cell, init)
        del init
        gc.collect()
        t_w = time.perf_counter()
        sess.train(2)
        jax.block_until_ready(sess.state)
        step_s = (time.perf_counter() - t_w) / 2
        n = max(2, math.ceil(WINDOW_MARGIN * seconds / step_s))
        log(f"check steps {prog['wall_s']!r} s; warm step {step_s!r} s; "
            f"window of {n} steps")

        tdir = None
        if traced:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        before = compiles.snapshot()
        setup_s = time.perf_counter() - t0
        ws = time.perf_counter()
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            rep = sess.train(n)
            jax.block_until_ready(rep.state)
        wall = time.perf_counter() - ws
        after = compiles.snapshot()
        if traced:
            jax.profiler.stop_trace()
    peak = peak_bytes(used)
    in_window = {k.rsplit("/", 1)[-1]: after[k] - before[k] for k in after}
    log(f"window {wall!r} s, {n} steps; compiles in window {in_window}")
    stats = rep.stats
    failed = sum(1 for x in stats.losses if not math.isfinite(x))
    if in_window["backend_compile_duration"] or in_window["cache_hits"]:
        raise RuntimeError(f"programs compiled inside the window: {in_window}")

    trace = None
    if traced:
        trace = tr.load(tr.find_xplane(tdir))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    w = Window(cell=cell, steps=n, samples=n * cell.global_batch, wall_s=wall,
               stats=stats, store_timers=program.store_timers(stats),
               trace=trace, flops_per_sample=spec.flops_per_sample(cell.config),
               peaks=peaks)
    if traced:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(w, setup_s, peak)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace is not None:
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = trace.window_s
        worst = max(trace.devices, key=lambda d: tr.idle_share(trace, d))
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(trace)],
                     "idle_gaps": [list(x) for x in
                                   tr.idle_gaps(trace, worst)]}

    # the program's state goes before the reference takes the chip
    del sess, rep, stats, w
    gc.collect()
    r0 = time.perf_counter()
    ref = reference.train(seed, cell.config, cell.traffic, cell.chips,
                          steps=cell.cell["ref_steps"],
                          block=cell.cell["ref_block"])
    log(f"reference {time.perf_counter() - r0!r} s")
    log(f"losses program {prog['losses']!r} reference {ref['losses']!r}")
    values = check.numbers(prog, ref)
    ok, checks = check.verdict(values, cell.cell.get("limits", {}),
                               cell.cell.get("not_compared", ()))
    result = {"correct": ok, "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                        for k, c in checks.items()}
    for line in check.lines(checks):
        log(line)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (NoChip, spec.SpecError, program.ProgramMissing) as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
