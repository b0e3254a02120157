"""Paper Table II: end-to-end step latency + DBP/FWP ablation + storage tiers.

CPU-scale real execution of the training modes on the HSTU backbone
(reduced config): TorchRec-like serial, UniEmb-like async (DBP w/o sync),
NestPipe. The production-mesh latency decomposition lives in the dry-run
roofline (EXPERIMENTS.md §Roofline); here we measure the real host+device
pipeline effects that exist on CPU: input-wait hiding and per-step wall
time, plus the step-exact loss to confirm no mode trades accuracy except
async (which is the paper's point).

Storage-tier axis (``--store``): the same NestPipe loop on the
cache-dominated ``dlrm-cached`` arch (steep zipf) through each
``EmbeddingStore`` tier. Cells are INTERLEAVED across repetitions and the
min-of-reps is recorded — on a noisy shared VM, ordering A...AB...B folds
machine drift into the A/B delta; interleaving + min is the methodology
PR 2 established for the routing cell. The cached cell also records the
hot-cache hit rate (steady = after the one-window admission warm-up).

Async-stages axis (``--async-stages``): every store cell additionally runs
with the async host-stage executor on (``table2_step_latency_store_
{store}_async``) — plan/retrieve on stage workers, the commit epilogue on
the commit thread, epoch-fenced (core/store/async_exec.py). Async cells
interleave with their sync twins inside each rep, and every cell's derived
field carries the per-step stage breakdown (plan/retrieve/commit/h2d ms)
so the overlap is visible in the trajectory file. Read the twins with the
harness in mind: overlap pays where window compute is long enough to hide
host work behind (measured 1.10-1.14x under moderate co-load; real
accelerators are the target regime), while an idle 2-core container
leaves these GIL-bound cells at parity-to-slightly-worse — losses are
identical either way, which CI asserts.

Mesh axis (``--mesh-devices N``, default ``$REPRO_BENCH_MESH_DEVICES``):
the same dlrm-cached loop run SPMD on an N-device (1, N) mesh, where
host/cached select the SHARDED per-host master tier
(``core/store/sharded.py``). Three cells per rep — the mesh device tier
and the two sharded variants — interleaved within each rep with
min-of-reps like every other store cell. The mesh cells run in a
SUBPROCESS with their own forced host-platform device count: splitting a
small CI box into N XLA devices slows every single-device cell (measured
3.2x on the nestpipe cell), so forcing it process-wide would break the
trajectory's comparability across PRs — exactly the benches-needing-a-
different-device-count rule benchmarks/run.py documents. The sharded
tiers are bit-exact with the same-mesh device run, so CI asserts cell
presence and identical losses across the three cells — NEVER a
throughput ratio (the CPU simulation round-trips shard buffers through
numpy; real accelerators are the target regime).

Sparse-comm axis (``--sparse-comm``): the dlrm-cached NestPipe loop under
each sparse-path compression mode (``core/store/comm.py``), interleaved
within each rep with min-of-reps like every other store cell
(``table2_step_latency_comm_{off,pack,int8}``). Each cell records the
modeled byte ledger (wire/h2d/d2h/idx); the ``pack`` cell additionally
records ``losses_equal_off`` (the lossless contract, compared step-exact
against the ``off`` cell's loss trajectory) and the ``int8`` cell records
``max_loss_dev`` + ``lossy=1`` (explicitly approximate, loss-parity on
the record). CI asserts the byte savings and the exactness flags — NEVER
a latency ratio (same rule as the mesh cells: CPU-modeled traffic, real
accelerators are the target regime).

Cache-policy axis (``--cache-policy``): the NestPipe loop on the DRIFTING
stream (``dlrm-drift``: the zipf hot head marches through the vocab) under
each chunk-granular eviction policy (``core/store/policy.py``), plus the
row-granular seed baseline (``cache_{rowgran}``: chunk_rows=1, the
pre-chunking movement pattern move for move) and a host-tier ground-truth
run. Cells interleave within reps, min-of-reps. Every cell records the
hit rate (total + steady), the staged-burst ledger (h2d_bursts =
DRAM->HBM staging descriptors, d2h_bursts = whole-chunk eviction
writebacks) and ``losses_equal_host`` — the value-transparency contract:
policies decide WHERE rows live, never what they are, so every policy
replays the host tier bit for bit. CI asserts the exactness flags and
that the chunked cells stage FEWER bursts than the row-granular baseline
— NEVER a latency ratio (CPU-modeled traffic; real accelerators are the
target regime).

Dense-comm cells (with ``--mesh-devices N``): the same loop on an (N, 1)
DATA-major mesh — all devices on the reduction axis — with the dense-grad
quantized ring off vs on (``table2_step_latency_dense_comm_{off,int8}``,
``train.step._build_dense_reducer``). The int8 cell records
``max_loss_dev`` against its lossless twin (explicitly approximate:
residual dropped; PR 7 discipline — deviation on the record, never
asserted to be zero).

Fault-recovery cell (``table2_step_latency_faults``): the dlrm-cached
NestPipe loop twice — fault-free, then with a deterministic fault injected
at EVERY store stage hook point (plan/retrieve/commit/h2d; dist/inject.py)
— recording ``losses_equal_faultfree`` plus the recovery counters
(faults_injected / stage_retries / commit_rollbacks). The cell's value and
derived fields are counts/equality ONLY — NEVER a latency ratio: recovery
cost under injected chaos is not a performance number.

``REPRO_BENCH_STEPS`` / ``REPRO_BENCH_BATCH`` / ``REPRO_BENCH_REPS``
shrink the run for CI's perf-smoke job (trajectory-only, no thresholds).
"""
from __future__ import annotations

import argparse
import os
from typing import Dict, List, Optional

from repro.core.store import (CACHE_POLICIES, SPARSE_COMMS, STAGE_TIMER_KEYS,
                              STORES)

from .common import emit, make_bench_mesh, run_driver

MODES = [("torchrec_serial", "serial"), ("uniemb_async", "async"),
         ("nestpipe", "nestpipe")]

ARCH = "hstu-industrial"
# Routing-dominated cell: trivial dense net, wide multi-hot bags, sizable
# table — isolates the sparse hot paths (routing, buffers, writeback).
ROUTING_ARCH = "dlrm-routing"
# Cache-dominated cell: steep-zipf keys so the CachedStore hot set is real.
CACHED_ARCH = "dlrm-cached"
# Drifting-stream cell: the rank->key mapping rotates every step, the
# stressor the cache-policy axis exists for (stale-but-frequent residents).
DRIFT_ARCH = "dlrm-drift"
# The drift cells pin the cache size so the policy axis is apples-to-apples
# (generous enough that the chunked grain competes on movement, not on
# capacity fragmentation).
DRIFT_CACHE_ROWS = 4096


def _stage_breakdown(s: dict) -> str:
    """Per-step stage wall-time breakdown for a cell's derived field."""
    steps = max(int(s.get("steps", 1)), 1)
    parts = []
    for k in STAGE_TIMER_KEYS:
        if k in s:
            parts.append(f"{k}={s[k] / steps:.2f}")
    return ";".join(parts)


def _store_cells(steps: int, global_batch: int, reps: int,
                 stores: List[str], async_axis: List[bool]) -> Dict[str, dict]:
    """Interleaved pre/post-style A/B over the (store, async) axes,
    min-of-reps per cell."""
    best: Dict[str, dict] = {}
    for _rep in range(reps):
        for store in stores:  # interleave: one cell per variant per rep
            for async_on in async_axis:
                _, stats, _ = run_driver(
                    CACHED_ARCH, mode="nestpipe", steps=steps, n_micro=4,
                    global_batch=global_batch, store=store,
                    async_stages="on" if async_on else "off")
                s = stats.summary()
                cell = store + ("_async" if async_on else "")
                if cell not in best or s["mean_step_s"] < best[cell]["mean_step_s"]:
                    best[cell] = s
    return best


def _comm_cells(steps: int, global_batch: int, reps: int,
                modes: List[str]):
    """Interleaved sparse-comm A/B on the cached tier, min-of-reps per
    cell. Also returns each mode's step-exact loss trajectory (runs are
    same-seed deterministic, so the trajectory is rep-invariant) for the
    pack/int8 exactness records."""
    best: Dict[str, dict] = {}
    losses: Dict[str, List[float]] = {}
    for _rep in range(reps):
        for mode in modes:  # interleave: one cell per mode per rep
            _, stats, _ = run_driver(
                CACHED_ARCH, mode="nestpipe", steps=steps, n_micro=4,
                global_batch=global_batch, store="cached", sparse_comm=mode)
            s = stats.summary()
            losses[mode] = [float(x) for x in stats.losses]
            if mode not in best or s["mean_step_s"] < best[mode]["mean_step_s"]:
                best[mode] = s
    return best, losses


def _cache_policy_cells(steps: int, global_batch: int, reps: int,
                        policies: List[str]):
    """Cache-policy axis on the drifting stream: each policy at the
    chunked grain, the row-granular seed baseline (``rowgran``:
    chunk_rows=1 under the seed's freq scheme), and one host-tier
    ground-truth run for the exactness records. Interleaved within reps,
    min-of-reps; losses are same-seed deterministic so the trajectories
    are rep-invariant."""
    _, stats, _ = run_driver(DRIFT_ARCH, mode="nestpipe", steps=steps,
                             n_micro=4, global_batch=global_batch,
                             store="host")
    host_losses = [float(x) for x in stats.losses]
    variants = [("rowgran", {"cache_chunk_rows": 1, "cache_policy": "freq"})]
    variants += [(pol, {"cache_policy": pol}) for pol in policies]
    best: Dict[str, dict] = {}
    losses: Dict[str, List[float]] = {}
    for _rep in range(reps):
        for cell, kw in variants:  # interleave: one cell per variant per rep
            _, stats, _ = run_driver(
                DRIFT_ARCH, mode="nestpipe", steps=steps, n_micro=4,
                global_batch=global_batch, store="cached",
                cache_rows=DRIFT_CACHE_ROWS, **kw)
            s = stats.summary()
            losses[cell] = [float(x) for x in stats.losses]
            if cell not in best or s["mean_step_s"] < best[cell]["mean_step_s"]:
                best[cell] = s
    return best, losses, host_losses


_MESH_MARKER = "MESH_CELLS_JSON:"


def _mesh_worker(mesh_devices: int, steps: int, global_batch: int,
                 reps: int) -> None:
    """Subprocess body: device tier + the two sharded variants on an
    N-device mesh, interleaved within each rep, min-of-reps. Emits the
    cells as one marked JSON line for the parent to re-emit."""
    import json

    mesh = make_bench_mesh(mesh_devices)
    # Dense-comm pair on a DATA-major (N, 1) mesh: the quantized ring runs
    # over the data axis, so it needs all N devices there — on the (1, N)
    # store mesh the 1-device data axis would short-circuit to identity.
    mesh_d = make_bench_mesh(mesh_devices, data_major=True)
    best: Dict[str, dict] = {}
    dc_losses: Dict[str, List[float]] = {}
    for _rep in range(reps):
        for store in ("device", "host", "cached"):
            _, stats, _ = run_driver(
                CACHED_ARCH, mode="nestpipe", steps=steps, n_micro=4,
                global_batch=global_batch, store=store, mesh=mesh)
            s = stats.summary()
            cell = "mesh_device" if store == "device" else f"sharded_{store}"
            if cell not in best or s["mean_step_s"] < best[cell]["mean_step_s"]:
                best[cell] = s
        for dc in ("off", "int8"):
            _, stats, _ = run_driver(
                CACHED_ARCH, mode="nestpipe", steps=steps, n_micro=4,
                global_batch=global_batch, store="device", mesh=mesh_d,
                dense_comm=dc)
            s = stats.summary()
            dc_losses[dc] = [float(x) for x in stats.losses]
            cell = f"dense_comm_{dc}"
            if cell not in best or s["mean_step_s"] < best[cell]["mean_step_s"]:
                best[cell] = s
    # loss-parity record for the approximate cell (PR 7 discipline:
    # measured and recorded, never asserted to be zero)
    best["dense_comm_int8"]["max_loss_dev_vs_off"] = max(
        (abs(a - b) for a, b in zip(dc_losses["int8"], dc_losses["off"])),
        default=0.0)
    print(_MESH_MARKER + json.dumps(best))


def _mesh_cells(steps: int, global_batch: int, reps: int,
                mesh_devices: int) -> Dict[str, dict]:
    """Run :func:`_mesh_worker` in a subprocess whose XLA_FLAGS force the
    simulated device count (must be set before JAX initializes, and must
    NOT leak into this process's single-device cells — module doc)."""
    import json
    import subprocess
    import sys

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    # a simulated CPU mesh by design: never compete with this process for
    # an accelerator it may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={mesh_devices}").strip()
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_step_latency",
         "--mesh-worker", str(mesh_devices), str(steps), str(global_batch),
         str(reps)],
        capture_output=True, text=True, env=env, cwd=root, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"mesh-cell subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith(_MESH_MARKER)][-1]
    return json.loads(line[len(_MESH_MARKER):])


def main(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--store", action="append", choices=STORES, default=None,
                   help="storage tiers for the dlrm-cached cells "
                        "(repeatable; default: all three)")
    p.add_argument("--reps", type=int,
                   default=int(os.environ.get("REPRO_BENCH_REPS", "3")),
                   help="interleaved repetitions per store cell (min-of-reps; "
                        "3 reps keeps the min meaningful under ~2x VM drift)")
    p.add_argument("--async-stages", choices=["both", "on", "off"],
                   default="both",
                   help="async host-stage executor axis for the store cells "
                        "(both = interleaved sync + async twins)")
    p.add_argument("--sparse-comm", action="append", choices=SPARSE_COMMS,
                   default=None,
                   help="sparse-path compression modes for the cached-tier "
                        "comm cells (repeatable; default: all three)")
    p.add_argument("--cache-policy", action="append", choices=CACHE_POLICIES,
                   default=None,
                   help="chunk-granular eviction policies for the drifting-"
                        "stream cache cells (repeatable; default: all four; "
                        "the row-granular seed baseline always runs)")
    p.add_argument("--mesh-devices", type=int,
                   default=int(os.environ.get("REPRO_BENCH_MESH_DEVICES",
                                              "0")),
                   help="N>0 adds sharded-store cells on an N-device mesh "
                        "(run in a subprocess that forces the simulated "
                        "device count; this process stays single-device)")
    argv = argv if argv is not None else []
    if argv[:1] == ["--mesh-worker"]:  # subprocess entry (see _mesh_cells)
        _mesh_worker(*(int(a) for a in argv[1:5]))
        return
    args = p.parse_args(argv)
    stores = args.store or list(STORES)
    async_axis = {"both": [False, True], "on": [True],
                  "off": [False]}[args.async_stages]

    steps = int(os.environ.get("REPRO_BENCH_STEPS", "12"))
    global_batch = int(os.environ.get("REPRO_BENCH_BATCH", "32"))
    results = {}
    for name, mode in MODES:
        state, stats, wl = run_driver(ARCH, mode=mode, steps=steps,
                                      global_batch=global_batch)
        s = stats.summary()
        results[name] = s
        emit(
            f"table2_step_latency_{name}",
            s["mean_step_s"] * 1e6,
            f"input_wait_us={s['mean_input_wait_s']*1e6:.1f};"
            f"final_loss={s['final_loss']:.4f};overflow={s['overflow_max']}",
            config={"arch": ARCH, "mode": mode, "steps": steps,
                    "global_batch": global_batch, "n_micro": 4,
                    "seq_len": 32, "reduced": True},
        )
    speedup = results["torchrec_serial"]["mean_step_s"] / max(
        results["nestpipe"]["mean_step_s"], 1e-9)
    emit("table2_nestpipe_speedup_x1000", speedup * 1000,
         "serial_vs_nestpipe_wall",
         config={"arch": ARCH, "steps": steps, "global_batch": global_batch})

    # routing-dominated cell (nestpipe only: the hot-path trajectory number)
    r_batch = global_batch * 8
    state, stats, wl = run_driver(ROUTING_ARCH, mode="nestpipe", steps=steps,
                                  n_micro=8, global_batch=r_batch)
    s = stats.summary()
    emit(
        "table2_step_latency_routing_nestpipe",
        s["mean_step_s"] * 1e6,
        f"final_loss={s['final_loss']:.4f};overflow={s['overflow_max']}",
        config={"arch": ROUTING_ARCH, "mode": "nestpipe", "steps": steps,
                "global_batch": r_batch, "n_micro": 8, "reduced": True},
    )

    # storage-tier x async-stages cells: interleaved across reps,
    # min-of-reps per cell
    c_batch = global_batch * 4
    best = _store_cells(steps, c_batch, max(args.reps, 1), stores, async_axis)
    if args.mesh_devices > 0:
        best.update(_mesh_cells(steps, c_batch, max(args.reps, 1),
                                args.mesh_devices))
    for cell, s in best.items():
        derived = f"final_loss={s['final_loss']:.4f}"
        if "cache_hit_rate" in s:
            derived += (f";hit_rate={s['cache_hit_rate']:.3f}"
                        f";hit_rate_steady={s.get('cache_hit_rate_steady', 0):.3f}")
        if "h2d_bytes" in s:
            derived += f";h2d_bytes={int(s['h2d_bytes'])}"
        if "store_shards" in s:
            derived += f";shards={s['store_shards']}"
        if "store_shard_grid" in s:  # 2D sparse grid (cols x rows)
            derived += f";grid={s['store_shard_grid']}"
        if "max_loss_dev_vs_off" in s:
            derived += f";lossy=1;max_loss_dev={s['max_loss_dev_vs_off']:.6f}"
        breakdown = _stage_breakdown(s)
        if breakdown:
            derived += ";" + breakdown
        is_mesh = cell.startswith(("mesh_", "sharded_", "dense_comm_"))
        is_dc = cell.startswith("dense_comm_")
        emit(
            f"table2_step_latency_{'' if is_dc else 'store_'}{cell}",
            s["mean_step_s"] * 1e6,
            derived,
            config={"arch": CACHED_ARCH, "mode": "nestpipe", "steps": steps,
                    "global_batch": c_batch, "n_micro": 4,
                    "store": "device" if is_dc else cell.replace("_async", ""),
                    "dense_comm": cell.split("_")[-1] if is_dc else "off",
                    "async_stages": cell.endswith("_async"),
                    "mesh_devices": args.mesh_devices if is_mesh else 0,
                    "reps": args.reps, "reduced": True},
        )

    # cache-policy cells: the drifting stream under every eviction scheme,
    # with the row-granular seed baseline and host-tier exactness records
    policies = args.cache_policy or list(CACHE_POLICIES)
    p_best, p_losses, host_losses = _cache_policy_cells(
        steps, c_batch, max(args.reps, 1), policies)
    for cell, s in p_best.items():
        derived = (
            f"final_loss={s['final_loss']:.4f}"
            f";hit_rate={s.get('cache_hit_rate', 0):.3f}"
            f";hit_rate_steady={s.get('cache_hit_rate_steady', 0):.3f}"
            f";h2d_bursts={int(s.get('h2d_bursts', 0))}"
            f";d2h_bursts={int(s.get('d2h_bursts', 0))}"
            f";losses_equal_host={int(p_losses[cell] == host_losses)}")
        emit(
            f"table2_step_latency_cache_{cell}",
            s["mean_step_s"] * 1e6,
            derived,
            config={"arch": DRIFT_ARCH, "mode": "nestpipe", "steps": steps,
                    "global_batch": c_batch, "n_micro": 4, "store": "cached",
                    "cache_policy": "freq" if cell == "rowgran" else cell,
                    "cache_chunk_rows": 1 if cell == "rowgran" else 0,
                    "cache_rows": DRIFT_CACHE_ROWS,
                    "reps": args.reps, "reduced": True},
        )

    # sparse-comm cells: the cached-tier loop under each compression mode,
    # interleaved within reps; pack carries the lossless contract on the
    # record, int8 its loss-parity deviation
    comm_modes = args.sparse_comm or list(SPARSE_COMMS)
    comm_best, comm_losses = _comm_cells(steps, c_batch, max(args.reps, 1),
                                         comm_modes)
    for mode in comm_modes:
        s = comm_best[mode]
        derived = f"final_loss={s['final_loss']:.4f}"
        for k in ("wire_bytes", "h2d_bytes", "d2h_bytes", "idx_bytes"):
            if k in s:
                derived += f";{k}={int(s[k])}"
        if "cache_hit_rate" in s:
            derived += f";hit_rate={s['cache_hit_rate']:.3f}"
        if mode == "pack" and "off" in comm_losses:
            derived += (";losses_equal_off="
                        f"{int(comm_losses['pack'] == comm_losses['off'])}")
        if mode == "int8":
            derived += ";lossy=1"
            if "off" in comm_losses:
                dev = max((abs(a - b) for a, b in zip(comm_losses["int8"],
                                                      comm_losses["off"])),
                          default=0.0)
                derived += f";max_loss_dev={dev:.6f}"
            derived += (f";rows_synced={int(s.get('comm_rows_synced', 0))}"
                        f";rows_deferred={int(s.get('comm_rows_deferred', 0))}")
        emit(
            f"table2_step_latency_comm_{mode}",
            s["mean_step_s"] * 1e6,
            derived,
            config={"arch": CACHED_ARCH, "mode": "nestpipe", "steps": steps,
                    "global_batch": c_batch, "n_micro": 4, "store": "cached",
                    "sparse_comm": mode, "reps": args.reps, "reduced": True},
        )

    # fault-recovery cell: cached tier with a deterministic fault at every
    # stage hook point vs its fault-free twin. Value + derived are counts
    # and the bit-exactness flag only — never a latency ratio.
    fault_spec = "plan:step=1;retrieve:step=2;commit:step=3;h2d:step=1"
    _, stats_ff, _ = run_driver(CACHED_ARCH, mode="nestpipe", steps=steps,
                                n_micro=4, global_batch=c_batch,
                                store="cached")
    _, stats_fi, _ = run_driver(CACHED_ARCH, mode="nestpipe", steps=steps,
                                n_micro=4, global_batch=c_batch,
                                store="cached", fault_inject=fault_spec)
    s = stats_fi.summary()
    equal = [float(x) for x in stats_fi.losses] == \
        [float(x) for x in stats_ff.losses]
    emit(
        "table2_step_latency_faults",
        s.get("faults_injected", 0.0),
        f"losses_equal_faultfree={int(equal)}"
        f";faults_injected={int(s.get('faults_injected', 0))}"
        f";stage_retries={int(s.get('stage_retries', 0))}"
        f";commit_rollbacks={int(s.get('commit_rollbacks', 0))}"
        f";final_loss={s['final_loss']:.4f}",
        config={"arch": CACHED_ARCH, "mode": "nestpipe", "steps": steps,
                "global_batch": c_batch, "n_micro": 4, "store": "cached",
                "fault_inject": fault_spec, "reduced": True},
    )


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
