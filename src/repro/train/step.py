"""Step builders: the fused NestPipe steady-state step, the serial
(TorchRec-like) baseline step, and the async (UniEmb-like) staleness step.

The fused NestPipe step contains the device-side work of ALL five DBP
stages for one steady-state iteration (paper Fig. 3):

    stage 5  FWP window over batch t   (emb A2A / dense fwd-bwd / grad A2A xN)
    stage 5' frozen-window updates     (dense AdamW + buffer rowwise-adagrad)
    stage 5'' master writeback of t
    stage 3  key routing for t+1       (fused key All2All)
    stage 4a retrieval for t+1         (from the PRE-writeback master — the
                                        overlap the paper exploits)
    stage 4b dual-buffer sync          (intersection copy: Prop. 1 exactness)

Retrieval deliberately reads the stale master: keys in K(t) ∩ K(t+1) are
repaired by the sync, keys outside K(t) were never touched — so the step is
*exactly* synchronous while retrieval needs no dependency on the writeback,
which is what lets XLA overlap it with the window compute.

Donation contract: every step family returns state (and carry) pytrees that
are leaf-for-leaf shape/dtype-identical to its inputs, so callers jit them
with ``donate_argnums=STEADY_DONATE_ARGNUMS`` (steady-state: state + carry)
or ``SERIAL_DONATE_ARGNUMS`` (serial: state) and XLA updates the master
table, dual buffers and optimizer moments in place — no per-step copy of
the largest arrays in the system. Donated inputs are consumed; the DBP
driver (core/dbp/pipeline.py) owns that lifecycle.

Split-phase variants: inside ONE XLA program the master table has TWO
consumers — the stage-4a retrieval (stale read, by design) and the
stage-5'' writeback scatter — which forces buffer assignment to copy the
whole table before scattering even when it is donated (the dominant
per-step cost for big tables). The ``*_nowb`` / ``*_noupd`` step fns
therefore return the table UNTOUCHED (trivially aliasable passthrough) plus
the update payload, and ``commit_writeback`` / ``commit_packets`` apply it
in a second jit where the donated table has a single consumer, so the
scatter really is in place. The fused fns remain the composition of the two
phases (identical math, one dispatch) for the dry-run and for TPU runs that
want XLA to overlap the writeback with stage 3/4 of the next batch.

Async-executor ordering note (core/store/async_exec.py): when the driver
runs host stages on background threads, ``buf_updated`` outlives the step
that produced it — it is read by the driver's sync jits (stage 4b and the
deferred epoch repairs) AND by the commit job on the commit thread,
potentially concurrently. That is safe precisely because no step fn and no
driver jit ever takes ``buf_updated`` donated (``sync_buffers`` donates
only the PREFETCH buffer; ``commit_writeback`` donates only the table);
keep it that way when adding step variants. Likewise the window jit must
never donate the ``plan`` leaves — the store's commit job may still read
``plan.host_keys``-adjacent state when the window for step t+1 dispatches.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.embedding.engine import EmbeddingEngine, GradPacket
from ..core.fwp.executor import build_fwp_window
from ..dist.compressed import ring_allreduce_quant_tree
from ..utils import tree_scale
from .optim import OptimizerPair
from .state import PipelineCarry, TrainState


class StepFns(NamedTuple):
    init_carry: Callable  # (table, keys0) -> PipelineCarry
    nestpipe_step: Callable  # (state, carry, batch, keys_next) -> (state, carry, aux)
    async_step: Callable  # same, but no dual-buffer sync (staleness baseline)
    serial_step: Callable  # (state, batch) -> (state, aux)
    # split-phase variants (see module doc: in-place master updates) --------
    nestpipe_step_nowb: Callable  # -> (state[old table], carry, aux, buf_updated)
    async_step_nowb: Callable  # same, staleness baseline
    serial_step_noupd: Callable  # (state, batch) -> (state[old table], aux, pkts)
    commit_writeback: Callable  # (table, buf_updated) -> table  [donate table]
    commit_packets: Callable  # (table, pkts) -> table  [donate table]
    # store-seam pieces (see core/store): the pipelined driver composes
    # these around an EmbeddingStore instead of hard-wiring the device
    # master into one fused step -------------------------------------------
    window_step: Callable  # (state, buffer, plan, batch) -> (state, aux, buf_updated)
    route_window: Callable  # (keys (N, *mb)) -> WindowPlan   [DBP stage 3]
    retrieve: Callable  # (table, window) -> DualBuffer       [stage 4a, device tier]
    sync_buffers: Callable  # (active, prefetch) -> DualBuffer [stage 4b]


# Canonical donate_argnums for jitting the step families (see module doc).
STEADY_DONATE_ARGNUMS = (0, 1)  # steady-state fns: state + carry
SERIAL_DONATE_ARGNUMS = (0,)  # serial fns: state
COMMIT_DONATE_ARGNUMS = (0,)  # commit fns: master table (in-place scatter)

# Dense-path gradient reduction schemes (NestPipeConfig.dense_comm).
DENSE_COMMS = ("off", "int8")


def _build_dense_reducer(engine: EmbeddingEngine, dense_comm: str) -> Callable:
    """Dense-grad re-reduction seam behind ``NestPipeConfig.dense_comm``.

    ``"off"`` is the identity. ``"int8"`` pushes the already-mean-reduced
    dense grads through the quantized ring AllReduce (dist.compressed):
    every replica holds the same mean grad g after the window's implicit
    cross-data-axis reduction, so each contributes g/n and the ring's sum
    reconstructs g up to int8 quantization error. The per-leaf residual is
    DROPPED on purpose — feeding it back would add leaves to the TrainState
    pytree and break the donation contract in the module doc. On a
    1-replica axis the ring short-circuits to an exact identity, so
    single-device runs stay bit-exact while multi-replica runs are
    explicitly approximate (reported next to the lossless baseline in
    bench_step_latency's dense-comm cells — loss deviation is measured,
    never asserted, PR 7 discipline).
    """
    if dense_comm not in DENSE_COMMS:
        raise ValueError(f"dense_comm={dense_comm!r} not in {DENSE_COMMS}")
    axes = engine.psum_axes
    if dense_comm == "off" or engine.mesh is None or not axes:
        return lambda g: g
    n = 1
    for a in axes:
        n *= engine.mesh.shape[a]

    def body(g):
        part = tree_scale(g, 1.0 / n)
        for a in axes:
            part, _residual = ring_allreduce_quant_tree(part, a)
        return part

    # Replicated in/out: the grads enter and leave as full per-replica
    # copies; only the ring's wire traffic is quantized.
    return engine._smap(body, P(), P())


def build_step_fns(
    engine: EmbeddingEngine,
    loss_fn: Callable,  # (dense_params, emb, mb_batch) -> (loss, metrics)
    optimizer: OptimizerPair,
    lr_sched: Callable,
    n_micro: int,
    mb_keys_shape: Tuple[int, ...],
    *,
    unroll: bool = True,
    dense_comm: str = "off",
) -> StepFns:
    window_fn = build_fwp_window(
        engine, loss_fn, n_micro, mb_keys_shape, unroll=unroll
    )
    reduce_dense = _build_dense_reducer(engine, dense_comm)

    def init_carry(table, keys0) -> PipelineCarry:
        """Pipeline warm-up: route + retrieve batch 0 (no sync partner yet)."""
        plan = engine.route_window(keys0, n_micro)
        buf = engine.retrieve(table, plan)
        return PipelineCarry(buf, plan)

    def _step_nowb(state: TrainState, carry: PipelineCarry, batch, keys_next,
                   *, sync: bool):
        # ---- stage 5: frozen window over batch t --------------------------
        out = window_fn(state.dense, carry.buffer, carry.plan, batch)
        lr = lr_sched(state.step)
        new_dense, new_opt, gnorm = optimizer.update(
            state.dense, state.opt, reduce_dense(out.dense_grads), lr
        )
        buf_updated = engine.apply_window_to_buffer(carry.buffer, out.packets)

        # ---- stages 3+4: routing, retrieval and sync for t+1 --------------
        plan_next = engine.route_window(keys_next, n_micro)
        pre_buf = engine.retrieve(state.table, plan_next)  # stale master: OK
        if sync:
            pre_buf = engine.sync_buffers(buf_updated, pre_buf)

        aux = {
            "loss": out.loss,
            "grad_norm": gnorm,
            "lr": lr,
            "routing_overflow": engine.overflow_metric(carry.plan),
            **out.metrics,
        }
        # The table is returned UNTOUCHED: stage 5'' (writeback of t) runs in
        # commit_writeback so the donated table has one consumer there.
        new_state = TrainState(new_dense, new_opt, state.table, state.step + 1)
        return new_state, PipelineCarry(pre_buf, plan_next), aux, buf_updated

    def commit_writeback(table, buf_updated):
        """Stage 5'': in-place master writeback (jit with the table donated)."""
        return engine.writeback(table, buf_updated)

    # ---------------- store-seam pieces (core/store) ------------------------
    # The tiered-store driver runs stages 5+5' here and delegates stages
    # 3 (route_window), 4a (store.retrieve) and 5'' (store.commit) to the
    # EmbeddingStore, so host/cached master tiers slot in without touching
    # the window math. The table leaf of ``state`` is a pass-through (the
    # store owns the master while a run is in flight).

    def window_step(state: TrainState, buffer, plan, batch):
        """Stages 5+5' only: FWP window over batch t + frozen-window updates
        (dense AdamW, buffer rowwise-adagrad). No routing / retrieval /
        writeback — those are the store's half of the step. ``plan`` is
        passed as its own (non-donated) argument: its int32 routing leaves
        are not returned, so donating them would only raise unusable-buffer
        warnings."""
        out = window_fn(state.dense, buffer, plan, batch)
        lr = lr_sched(state.step)
        with jax.named_scope("fwp_optimizer"):
            new_dense, new_opt, gnorm = optimizer.update(
                state.dense, state.opt, reduce_dense(out.dense_grads), lr
            )
        with jax.named_scope("fwp_sparse"):
            buf_updated = engine.apply_window_to_buffer(buffer, out.packets)
        aux = {
            "loss": out.loss,
            "grad_norm": gnorm,
            "lr": lr,
            "routing_overflow": engine.overflow_metric(plan),
            "buffer_keys_valid": engine.buffer_keys_valid(buffer),
            **out.metrics,
        }
        new_state = TrainState(new_dense, new_opt, state.table, state.step + 1)
        return new_state, aux, buf_updated

    def route_window(keys):
        """DBP stage 3 for one lookahead batch (store.plan's device half)."""
        return engine.route_window(keys, n_micro)

    def nestpipe_step_nowb(state, carry, batch, keys_next):
        return _step_nowb(state, carry, batch, keys_next, sync=True)

    def async_step_nowb(state, carry, batch, keys_next):
        """UniEmb-like pipeline WITHOUT dual-buffer sync: embeddings read by
        batch t+1 miss batch t's updates for intersecting keys (one-step
        staleness) — reproduces the paper's consistency comparison."""
        return _step_nowb(state, carry, batch, keys_next, sync=False)

    def _fused(step_nowb):
        def step(state, carry, batch, keys_next):
            new_state, new_carry, aux, buf_updated = step_nowb(
                state, carry, batch, keys_next)
            table = commit_writeback(new_state.table, buf_updated)
            return new_state._replace(table=table), new_carry, aux

        return step

    nestpipe_step = _fused(nestpipe_step_nowb)
    async_step = _fused(async_step_nowb)

    # ---------------- serial (TorchRec-like) baseline ----------------------
    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)

    def serial_step_noupd(state: TrainState, batch):
        """Fully synchronous flat step: batch-level lookup from master,
        single fwd/bwd over the whole batch. The same math as NestPipe
        (test-asserted), none of the pipelining. Returns the packets; the
        master update runs in commit_packets (in-place, table donated)."""
        # batch keys arrive stacked (N, ...) for uniformity; flatten window.
        packets = []
        losses = []
        gsum = None
        for i in range(n_micro):
            mb = jax.tree.map(lambda x: x[i], batch)
            emb, plan = engine.lookup_from_master(state.table, mb["keys"])
            (loss, metrics), (dg, demb) = grad_fn(state.dense, emb, mb)
            packets.append(
                engine.grads_to_owner(
                    plan, demb * (1.0 / n_micro), mb_keys_shape, n_micro
                )
            )
            losses.append(loss)
            gsum = dg if gsum is None else jax.tree.map(jnp.add, gsum, dg)
        pkts = jax.tree.map(lambda *xs: jnp.stack(xs), *packets)
        gmean = tree_scale(gsum, 1.0 / n_micro)
        lr = lr_sched(state.step)
        new_dense, new_opt, gnorm = optimizer.update(
            state.dense, state.opt, reduce_dense(gmean), lr)
        aux = {"loss": jnp.mean(jnp.stack(losses)), "grad_norm": gnorm, "lr": lr}
        return TrainState(new_dense, new_opt, state.table, state.step + 1), aux, pkts

    def commit_packets(table, pkts):
        """Serial-mode master update (jit with the table donated)."""
        return engine.apply_packets_to_master(table, pkts)

    def serial_step(state, batch):
        new_state, aux, pkts = serial_step_noupd(state, batch)
        table = commit_packets(new_state.table, pkts)
        return new_state._replace(table=table), aux

    return StepFns(init_carry, nestpipe_step, async_step, serial_step,
                   nestpipe_step_nowb, async_step_nowb, serial_step_noupd,
                   commit_writeback, commit_packets,
                   window_step, route_window, engine.retrieve,
                   engine.sync_buffers)
