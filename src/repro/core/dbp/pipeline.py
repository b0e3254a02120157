"""DBP six-stage host driver (paper §IV + the async host-stage executor).

Orchestrates the inter-batch pipeline over a batch stream. Six stages, and
— with ``async_stages`` on — four kinds of thread run them:

    stage 1  data prefetch   — PrefetchQueue thread (data/pipeline)
    stage 2  data H2D        — async device_put (driver thread dispatch)
    stage 3  key routing     — store.plan: fused key All2All + host key copy
    stage 4  retrieval+sync  — store.retrieve: master rows -> dual buffer
                               (4a), + intersection sync against in-flight
                               commits (4b, driver-dispatched jit)
    stage 5  fwd/bwd (FWP)   — frozen-window micro-batch execution (device)
    stage 6  commit epilogue — store.commit: D2H pull + master scatter

Storage is a seam, not a branch: the driver talks to ONE
:class:`~repro.core.store.EmbeddingStore` — ``plan`` / ``retrieve`` /
``commit`` — and the device-HBM, host-DRAM, HBM-hot-cache and mesh-sharded
tiers all ride the same loop (core/store). On a mesh the sharded tier's
``commit`` applies every shard's scatter for the window atomically under
the executor's master lock — the epoch fence keeps counting whole-window
commits, and the store's per-shard ledger (``commits_applied``) records
the per-host applications the single epoch stands in for. A :class:`~repro.core.store.Prefetcher` keeps
``lookahead`` batches routed+retrieved ahead of the window compute, the
intra-driver analogue of DBP's retrieval overlap; every in-flight buffer is
re-synced at every commit so lookahead never trades exactness (Prop. 1
generalized — see core/store/prefetch.py).

**Async host stages** (``async_stages=True``, the BagPipe/Hotline-style
disaggregation — core/store/async_exec.py): stages 3-4a run on a
:class:`~repro.core.store.StageExecutor` stage-worker pool and stage 6 on
its dedicated commit thread, so the driver thread only dispatches jits and
pops completed futures — the host-side numpy gather/scatter and the
blocking D2H never sit on the critical path between two window dispatches.
Exactness holds through the executor's **commit epoch fence**: the master
carries a monotone commit epoch; a retrieve waits until the epoch covers
every commit submitted before it (reproducing the synchronous
interleaving deterministically) and records the epoch it read; any buffer
whose read epoch trails a completed commit is repaired through the same
``sync_buffers`` intersection path (eagerly at the commit when its future
has resolved, else queued and applied at ``pop``). Sync repairs copy
post-update rows verbatim, so over-repair is idempotent and the async
schedule replays the synchronous loop bit-for-bit (tests/test_async_exec).
Mid-run exports (checkpoints) drain the commit queue first and read the
master under the executor's lock.

It also runs the baselines: ``serial`` (no pipelining, device tier only),
``async`` (prefetch without dual-buffer sync — the staleness baseline;
orthogonal to ``async_stages``, which never trades exactness).

Hot-loop discipline (this is the part the paper's overlap depends on):

- **Donated buffers.** The window jit donates the ``TrainState`` and the
  ``PipelineCarry`` (dual buffers, adagrad state, optimizer moments); the
  master table lives in the store for the duration of the run (the state
  carries a zero-row placeholder) and the store's commit applies the
  writeback with the master donated and singly-consumed, so the scatter is
  truly in place (see train/step.py). The state/carry passed to ``run``
  are CONSUMED — callers must not touch them afterwards (pass
  ``donate=False`` to keep them alive, e.g. for A/B comparisons).
  ``buf_updated`` is deliberately NEVER donated anywhere: it is read by
  the sync jits, the deferred epoch repairs AND the commit job, possibly
  concurrently from two threads.
- **Non-blocking metric drain.** The loop never calls ``float(aux[...])``
  per step — that would insert a host sync serializing stages 1-2 against
  stage 5. Instead per-step aux pytrees stay on device in a pending list
  and are drained (one ``jax.block_until_ready`` + host conversion) every
  ``metrics_every`` steps, at checkpoints, and at the end of the run. The
  store's transfer/cache counters and per-stage wall-time counters
  (``plan_ms``/``retrieve_ms``/``commit_ms``/``h2d_ms``) are snapshotted
  into the stats at the same drain points — they are plain host counters,
  so surfacing them never blocks the device. Step wall times and the
  straggler EMA are computed from drained timestamps: every step in a
  drained span is attributed the span's mean wall time (minus host
  input-wait), so straggler detection operates at drain granularity.

Profiler spans (``jax.profiler``; each an inactive ``TraceMe`` when no
trace runs): ``dbp.input_wait`` around the queue read, ``dbp.h2d`` around
the batch's device_put, ``dbp.window`` (a step annotation carrying the
step number) around each window dispatch, ``dbp.sync`` around stage 4b,
``dbp.drain`` around the metric drain's block and host conversion, and
the store's ``dbp.plan`` / ``dbp.retrieve`` / ``dbp.commit`` / ``dbp.h2d``
from its stage timers. Per-step host timestamps thus come from the trace,
not from the drain's span means.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import jax
import numpy as np

from ...data.pipeline import PrefetchQueue, make_cluster_transform, stage_to_device
from ...train.state import PipelineCarry, TrainState
from ...train.step import (
    COMMIT_DONATE_ARGNUMS,
    SERIAL_DONATE_ARGNUMS,
    STEADY_DONATE_ARGNUMS,
)
from ..store import (
    STAGE_TIMER_KEYS,
    AsyncPrefetcher,
    DeviceStore,
    EmbeddingStore,
    Prefetcher,
    StageExecutor,
    resolve_async_stages,
)


@dataclass
class PipelineStats:
    step_times: List[float] = field(default_factory=list)
    losses: List[float] = field(default_factory=list)
    # sum over the drained steps of the window's unique keys (the
    # non-sentinel rows of the dual buffer), and the buffer's static row
    # capacity K: the rows retrieve gathers and sync_buffers copies each
    # step (0 in serial mode, which has no buffer)
    buffer_keys_valid: int = 0
    buffer_rows: int = 0
    input_wait_times: List[float] = field(default_factory=list)
    input_wait_total: float = 0.0  # running sum (the drain reads it per
    # span; recomputing sum(input_wait_times) there was O(steps^2))
    straggler_steps: List[int] = field(default_factory=list)
    overflow_max: int = 0
    store_tier: str = "device"
    sparse_comm: str = "off"
    async_stages: bool = False
    # step boundary (1-based, relative to this run) where a preemption
    # notice stopped the loop early; None for a run that went the distance
    preempted_at: Optional[int] = None
    # cumulative store counters at the last drain / after the warm-up drain
    store_metrics: Dict[str, float] = field(default_factory=dict)
    store_metrics_warm: Dict[str, float] = field(default_factory=dict)

    def add_input_wait(self, dt: float) -> None:
        self.input_wait_times.append(dt)
        self.input_wait_total += dt

    def _cache_rates(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        m = self.store_metrics
        if "cache_hits" in m:
            total = m["cache_hits"] + m["cache_misses"]
            if total:
                out["cache_hit_rate"] = m["cache_hits"] / total
            w = self.store_metrics_warm
            if w:
                dh = m["cache_hits"] - w.get("cache_hits", 0.0)
                dm = m["cache_misses"] - w.get("cache_misses", 0.0)
                if dh + dm > 0:
                    out["cache_hit_rate_steady"] = dh / (dh + dm)
        return out

    def summary(self) -> Dict[str, float]:
        st = np.asarray(self.step_times[1:] or self.step_times)
        out = {
            "steps": len(self.step_times),
            "mean_step_s": float(st.mean()) if len(st) else 0.0,
            "mean_input_wait_s": float(np.mean(self.input_wait_times or [0.0])),
            "stragglers": len(self.straggler_steps),
            "final_loss": self.losses[-1] if self.losses else float("nan"),
            "overflow_max": self.overflow_max,
            "store": self.store_tier,
            "sparse_comm": self.sparse_comm,
            "async_stages": self.async_stages,
        }
        for k in ("h2d_bytes", "d2h_bytes", "h2d_bursts", "d2h_bursts",
                  "wire_bytes", "idx_bytes",
                  "wire_bytes_ax0", "wire_bytes_ax1",
                  "comm_rows_synced", "comm_rows_deferred",
                  "stage_retries", "commit_rollbacks",
                  "faults_injected") + STAGE_TIMER_KEYS:
            if k in self.store_metrics:
                out[k] = self.store_metrics[k]
        if "shards" in self.store_metrics:  # sharded tier: per-host masters
            out["store_shards"] = int(self.store_metrics["shards"])
        if "shard_cols" in self.store_metrics:  # 2D sparse grid shape
            out["store_shard_grid"] = "%dx%d" % (
                int(self.store_metrics["shard_cols"]),
                int(self.store_metrics["shard_rows"]))
        if self.preempted_at is not None:
            out["preempted_at"] = self.preempted_at
        out.update(self._cache_rates())
        return out


class _MetricsDrain:
    """Deferred device->host metric conversion (see module docstring).

    ``push`` keeps a step's aux pytree on device; ``drain`` blocks once on
    the newest aux (everything older is already done by program order),
    converts the whole pending span, spreads the span's wall time — minus
    the host-side input wait accrued inside it — evenly over its steps for
    the stats and the straggler EMA, and snapshots the store's host-side
    transfer/cache counters.
    """

    def __init__(self, stats: PipelineStats, straggler_factor: float,
                 store: Optional[EmbeddingStore] = None, watchdog=None):
        self.stats = stats
        self.straggler_factor = straggler_factor
        self.store = store
        # dist.fault.StepWatchdog — when supplied it OWNS straggler
        # detection (its own EMA + threshold) and the internal EMA check
        # below is bypassed, so its event log and stats.straggler_steps
        # agree by construction.
        self.watchdog = watchdog
        self.pending: List[tuple] = []
        self.ema: Optional[float] = None
        self._t_mark = time.perf_counter()
        self._wait_mark = 0.0  # stats.input_wait_total at the mark

    def _snapshot_store(self) -> None:
        if self.store is not None:
            self.stats.store_metrics = dict(self.store.metrics())
            if not self.stats.store_metrics_warm and self.stats.step_times:
                # first post-step drain = end of warm-up (compile + cold cache)
                self.stats.store_metrics_warm = dict(self.stats.store_metrics)

    def drain(self) -> None:
        if not self.pending:
            self._t_mark = time.perf_counter()
            self._wait_mark = self.stats.input_wait_total
            self._snapshot_store()
            return
        with jax.profiler.TraceAnnotation("dbp.drain"):
            jax.block_until_ready(self.pending[-1][1])
            now = time.perf_counter()
            waited = self.stats.input_wait_total - self._wait_mark
            dt = max(now - self._t_mark - waited, 0.0) / len(self.pending)
            for t, aux in self.pending:
                self._record(t, aux, dt)
        self.pending.clear()
        self._t_mark = now
        self._wait_mark = self.stats.input_wait_total
        self._snapshot_store()

    def _record(self, t: int, aux, dt: float) -> None:
        self.stats.step_times.append(dt)
        self.stats.losses.append(float(aux["loss"]))
        self.stats.overflow_max = max(
            self.stats.overflow_max, int(aux.get("routing_overflow", 0)))
        self.stats.buffer_keys_valid += int(aux.get("buffer_keys_valid", 0))
        if self.watchdog is not None:
            if self.watchdog.observe(t, dt):
                self.stats.straggler_steps.append(t)
        else:
            if self.ema is not None and dt > self.straggler_factor * self.ema:
                self.stats.straggler_steps.append(t)
            self.ema = dt if self.ema is None else 0.9 * self.ema + 0.1 * dt

    def push(self, t: int, aux) -> None:
        self.pending.append((t, aux))


class DBPDriver:
    """Runs NestPipe training (or a baseline mode) over a host batch stream."""

    def __init__(
        self,
        step_fns,  # train.step.StepFns
        source: Iterator,  # yields dict batches with a "keys" field (numpy)
        n_micro: int,
        *,
        mode: str = "nestpipe",  # "nestpipe" | "async" | "serial"
        clustering: str = "keycentric",
        batch_shardings=None,  # pytree/dict of NamedSharding for staged batches
        prefetch_depth: int = 2,
        device_fields: Optional[List[str]] = None,  # batch fields shipped to device
        straggler_factor: float = 3.0,
        on_checkpoint: Optional[Callable[[TrainState, int], None]] = None,
        ckpt_every: int = 0,
        metrics_every: int = 8,  # steps between deferred metric drains
        donate: bool = True,  # donate state+carry to the steady-state jits
        store: Optional[EmbeddingStore] = None,  # None -> DeviceStore
        lookahead: int = 1,  # DBP retrieval lookahead depth k (Prefetcher)
        async_stages="auto",  # host stages on worker threads ("auto" ->
        # $REPRO_ASYNC_STAGES -> off); ignored by serial mode
        stage_workers: int = 1,  # plan/retrieve worker threads (>1 keeps
        # values exact but cache placement/counters nondeterministic)
        fence_slack: Optional[int] = None,  # commits a retrieve may trail
        # (None -> lookahead+1 on host tiers in nestpipe mode, else 0; see
        # core/store/async_exec.py — 0 replays the sync critical path)
        stage_hooks=None,  # StageExecutor test seam (schedule injection)
        guard=None,  # dist.fault.PreemptionGuard — polled at step
        # boundaries; a latched notice checkpoints (via on_checkpoint) and
        # exits the loop cleanly so a resumed run continues the exact
        # trajectory (see run())
        watchdog=None,  # dist.fault.StepWatchdog — owns straggler
        # detection when supplied (its events mirror stats.straggler_steps)
    ):
        self.fns = step_fns
        self.n_micro = n_micro
        self.mode = mode
        self.batch_shardings = batch_shardings
        self.device_fields = device_fields
        self.straggler_factor = straggler_factor
        self.on_checkpoint = on_checkpoint
        self.ckpt_every = ckpt_every
        self.metrics_every = max(int(metrics_every), 1)
        self.donate = donate
        self.store = store if store is not None \
            else DeviceStore(step_fns, donate=donate)
        self.lookahead = max(int(lookahead), 1)
        self.async_stages = resolve_async_stages(async_stages) \
            and mode != "serial"
        self.stage_workers = max(int(stage_workers), 1)
        if fence_slack is None:
            # overlap needs a relaxed fence; the device tier and the
            # staleness baseline must keep the synchronous interleaving
            # (async_exec module doc)
            fence_slack = self.lookahead + 1 \
                if (mode == "nestpipe" and self.store.tier != "device") else 0
        self.fence_slack = max(int(fence_slack), 0)
        self.stage_hooks = stage_hooks
        self.guard = guard
        self.watchdog = watchdog
        self._exec: Optional[StageExecutor] = None  # live only inside run()
        if mode == "serial" and self.store.tier != "device":
            raise ValueError(
                "serial mode is the TorchRec-like device-resident baseline; "
                f"store={self.store.tier!r} requires a pipelined mode "
                "(nestpipe | async)")
        # Key-centric clustering only shapes FWP micro-batch locality; the
        # serial baseline has no window to cluster for, so it skips the
        # host-side permutation entirely.
        self.clustering = clustering if mode != "serial" else "none"
        transform = make_cluster_transform(n_micro, self.clustering)
        self.queue = PrefetchQueue(source, depth=prefetch_depth, transform=transform)
        # Split-phase steps (train/step.py): the window jit leaves the master
        # untouched (the store owns it) and the store's commit applies the
        # update with the master donated and singly-consumed, so the scatter
        # is truly in place.
        # window jit donates (state, buffer); the plan's int32 routing leaves
        # are read-only and stay undonated (they have no aliasable output).
        steady_donate = STEADY_DONATE_ARGNUMS if donate else ()
        self._jit_window = jax.jit(step_fns.window_step,
                                   donate_argnums=steady_donate)
        # sync consumes the prefetch buffer (arg 1); the active buffer is
        # read again by commit, so it is never donated here.
        self._jit_sync = jax.jit(step_fns.sync_buffers,
                                 donate_argnums=(1,) if donate else ())
        self._jit_serial = jax.jit(step_fns.serial_step_noupd,
                                   donate_argnums=SERIAL_DONATE_ARGNUMS if donate else ())
        self._jit_commit_pkts = jax.jit(step_fns.commit_packets,
                                        donate_argnums=COMMIT_DONATE_ARGNUMS if donate else ())

    # -- stages 1-2 -----------------------------------------------------

    def _next_device_batch(self, stats: PipelineStats):
        with jax.profiler.TraceAnnotation("dbp.input_wait"):
            t0 = time.perf_counter()
            host_batch = self.queue.get()
            stats.add_input_wait(time.perf_counter() - t0)
        if self.device_fields is not None:
            host_batch = {k: host_batch[k] for k in self.device_fields}
        with jax.profiler.TraceAnnotation("dbp.h2d"):
            return stage_to_device(host_batch, self.batch_shardings or {})

    # -- main loop --------------------------------------------------------

    def run(self, state: TrainState, num_steps: int) -> (TrainState, PipelineStats):
        stats = PipelineStats()
        stats.store_tier = self.store.tier
        stats.sparse_comm = getattr(self.store, "sparse_comm", "off")
        drain = _MetricsDrain(stats, self.straggler_factor, store=self.store,
                              watchdog=self.watchdog)
        try:
            if self.mode == "serial":
                for t in range(num_steps):
                    batch = self._next_device_batch(stats)
                    with jax.profiler.StepTraceAnnotation("dbp.window",
                                                          step_num=t):
                        state, aux, pkts = self._jit_serial(state, batch)
                    state = state._replace(
                        table=self._jit_commit_pkts(state.table, pkts))
                    drain.push(t, aux)
                    self._maybe_drain(drain, t, num_steps)
                    self._maybe_ckpt(state, t, drain)
                    if self._preempt(t, num_steps):
                        stats.preempted_at = t + 1
                        break
                drain.drain()
                if stats.preempted_at is not None \
                        and self.on_checkpoint is not None:
                    self.on_checkpoint(self._ckpt_state(state),
                                       stats.preempted_at)
                return state, stats

            if num_steps <= 0:
                return state, stats

            # ---- pipelined modes: one loop, any storage tier ------------
            state = state._replace(table=self.store.ingest(state.table))
            sync_on = self.mode == "nestpipe"
            next_batch = lambda: self._next_device_batch(stats)  # noqa: E731
            if self.async_stages:
                stats.async_stages = True
                self._exec = StageExecutor(self.store,
                                           workers=self.stage_workers,
                                           fence_slack=self.fence_slack,
                                           hooks=self.stage_hooks)
                if hasattr(self.store, "use_stage_pool"):
                    self.store.use_stage_pool()
                pf = AsyncPrefetcher(next_batch, self.store, self._exec,
                                     depth=self.lookahead, strict=sync_on)
                commit = self._exec.submit_commit
            else:
                pf = Prefetcher(next_batch, self.store, depth=self.lookahead)
                commit = self.store.commit
            pf.fill(limit=num_steps)  # windows 0..min(k,N)-1
            first = pf.pop()  # warm-up: route + retrieve batch 0
            carry = PipelineCarry(first.buffer, first.plan.window)
            stats.buffer_rows = int(first.buffer.keys.shape[0])
            cur_plan, batch = first.plan, first.batch
            for t in range(num_steps):
                # stages 3+4 for t+1..t+k overlap this window; capped so a
                # finite run never retrieves windows no step consumes
                pf.fill(limit=num_steps - 1 - t)
                with jax.profiler.StepTraceAnnotation("dbp.window",
                                                      step_num=t):
                    state, aux, buf_updated = self._jit_window(
                        state, carry.buffer, carry.plan, batch)
                if t + 1 < num_steps:
                    nxt = pf.pop()
                    if sync_on:
                        # stage 4b: repair the t+1 buffer (and every deeper
                        # in-flight buffer) against this window's updates.
                        with jax.profiler.TraceAnnotation("dbp.sync"):
                            nxt_buf = self._jit_sync(buf_updated, nxt.buffer)
                            pf.resync(buf_updated, self._jit_sync)
                    else:
                        nxt_buf = nxt.buffer  # staleness baseline: no sync
                commit(buf_updated, cur_plan)  # stage 6 (inline or queued)
                if t + 1 < num_steps:
                    carry = PipelineCarry(nxt_buf, nxt.plan.window)
                    cur_plan, batch = nxt.plan, nxt.batch
                drain.push(t, aux)
                self._maybe_drain(drain, t, num_steps)
                self._maybe_ckpt(state, t, drain)
                if self._preempt(t, num_steps):
                    # Break AFTER this window's commit was submitted: the
                    # master holds exactly t+1 whole-window commits once the
                    # executor drains, and the discarded lookahead buffers
                    # were never committed — a resumed run's fresh
                    # retrieves against this master equal the
                    # epoch-repaired buffers the uninterrupted run carried
                    # (Prop. 1), so the trajectory continues bit-for-bit.
                    stats.preempted_at = t + 1
                    break
            if self._exec is not None:
                self._exec.drain()  # all commits applied: master is final
                if stats.preempted_at is not None:
                    # quiesce in-flight lookahead retrieves before release:
                    # they hold the master lock mid-gather, and the cached
                    # tier's release flushes hot rows into the master.
                    # Safe from hangs: fences only reference commits
                    # already submitted, and drain() just applied them all.
                    self._exec.shutdown(wait=True)
            drain.drain()
            state = state._replace(table=self.store.release())
            if stats.preempted_at is not None \
                    and self.on_checkpoint is not None:
                # state carries the real master post-release — save it so a
                # resumed run restores the exact table + step.
                self.on_checkpoint(state, stats.preempted_at)
            return state, stats
        finally:
            if self._exec is not None:
                self._exec.shutdown()
                self._exec = None
                if hasattr(self.store, "clear_stage_pool"):
                    # a later sync-mode run on this store must not inherit
                    # the pooled (blocking) staging path
                    self.store.clear_stage_pool()
            self.queue.close()

    def _preempt(self, t: int, num_steps: int) -> bool:
        # Poll at the step boundary only — never mid-step — so every exit
        # is at a consistent (whole-window-committed) state. The last step
        # exits anyway; don't mislabel it a preemption.
        return (self.guard is not None and self.guard.should_checkpoint
                and t + 1 < num_steps)

    def _maybe_drain(self, drain: _MetricsDrain, t: int, num_steps: int):
        # Step 0 carries compile time — drain it alone so the smear stays out
        # of the steady-state timings (summary() already drops step 0).
        if t == 0 or (t + 1) % self.metrics_every == 0 or t == num_steps - 1:
            drain.drain()

    def _ckpt_state(self, state: TrainState) -> TrainState:
        if self.store.owns_master:
            if self._exec is not None:
                # all queued commits must reach the master before export;
                # the lock fences out in-flight retrieves while the cached
                # tier's export flushes hot rows into the DRAM master
                self._exec.drain()
                with self._exec.lock:
                    return state._replace(table=self.store.export_table())
            return state._replace(table=self.store.export_table())
        return state

    def _maybe_ckpt(self, state, t, drain: _MetricsDrain):
        if self.on_checkpoint is not None and self.ckpt_every and (t + 1) % self.ckpt_every == 0:
            drain.drain()  # flush the device queue + stats before saving
            self.on_checkpoint(self._ckpt_state(state), t + 1)
            drain.drain()  # re-mark: keep save time out of the next span's steps
