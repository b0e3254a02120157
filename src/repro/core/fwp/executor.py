"""Frozen-Window Pipelining executor (paper §V).

Builds the jittable window function that runs N micro-batches through
(embedding All2All -> dense fwd/bwd -> gradient All2All) with NO parameter
update until the window closes — the parameter-freezing phenomenon that
makes the overlap semantically free (Prop. 2).

Named scopes split the window in the HLO's ``op_name`` metadata:
``fwp_sparse`` on the embedding lookup, the gradient packets and (in
``train/step.py``) the buffer update; ``fwp_optimizer`` on the dense
update; the backbones put ``fwp_attention`` on their attention block. A
scope changes metadata only: no jit is split and nothing is renamed.

Overlap realization on TPU (DESIGN.md §2): with ``unroll=True`` the window
is straight-line HLO, so the embedding All2All of micro-batch i+1 has no
data dependency on the dense compute of micro-batch i and XLA's
latency-hiding scheduler may interleave them (dual "streams"). With
``unroll=False`` a ``lax.scan`` keeps the HLO compact (one body) at the cost
of a control-flow barrier per micro-batch — the scan-vs-unroll trade-off is
a §Perf hillclimb axis.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ...utils import tree_add, tree_scale, tree_zeros_like
from ..embedding.engine import (
    DualBuffer,
    EmbeddingEngine,
    GradPacket,
    LookupPlan,
    WindowPlan,
)


class FwpStepOutputs(NamedTuple):
    loss: jax.Array  # () mean loss over the window
    dense_grads: jax.Array  # pytree: mean dense grads (frozen-window sum / N)
    packets: GradPacket  # stacked (N, ...) gradient packets for the sparse side
    metrics: dict  # auxiliary metrics (mean over micro-batches)


def build_fwp_window(
    engine: EmbeddingEngine,
    loss_fn: Callable,  # loss_fn(dense_params, emb, mb_batch) -> (loss, metrics)
    n_micro: int,
    mb_keys_shape: Tuple[int, ...],  # global per-micro-batch keys shape
    *,
    unroll: bool = True,
):
    """Returns ``window(dense_params, buffer, window_plan, mb_batches)``.

    ``mb_batches``: pytree stacked (N, ...) with a ``keys`` leaf of shape
    (N, *mb_keys_shape) (already scrambled); ``window_plan`` from
    ``engine.route_window``. The returned dense grads are averaged over the
    window (equivalently over the full batch) and the gradient packets carry
    loss-sum-scaled sparse grads, so downstream updates reproduce Eq. (1).
    """
    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)

    def one_micro(dense_params, buffer: DualBuffer, plan: LookupPlan, mb):
        with jax.named_scope("fwp_sparse"):
            emb = engine.lookup_from_buffer(buffer, plan, mb_keys_shape,
                                            n_micro)
        (loss, metrics), (dgrads, demb) = grad_fn(dense_params, emb, mb)
        # 1/N so the window total is the batch-mean gradient.
        demb = demb * (1.0 / n_micro)
        with jax.named_scope("fwp_sparse"):
            packet = engine.grads_to_owner(plan, demb, mb_keys_shape, n_micro)
        return loss, metrics, tree_scale(dgrads, 1.0 / n_micro), packet

    if unroll:

        def window(dense_params, buffer, window_plan: WindowPlan, mb_batches):
            losses, all_metrics, packets = [], [], []
            gsum = None
            gate = None  # compute-stream serializer (see below)
            for i in range(n_micro):
                plan_i = jax.tree.map(lambda x: x[i], window_plan.plans)
                mb_i = jax.tree.map(lambda x: x[i], mb_batches)
                with jax.named_scope("fwp_sparse"):
                    emb = engine.lookup_from_buffer(buffer, plan_i,
                                                    mb_keys_shape, n_micro)
                if gate is not None:
                    # Two-stream schedule (paper Fig. 5): the embedding All2All
                    # of micro-batch i (communication stream) has no dependency
                    # on prior compute and may overlap it; the DENSE fwd/bwd
                    # (computation stream) is serialized behind micro-batch
                    # i-1's backward via an optimization barrier, so only one
                    # micro-batch's activations are ever live — without this,
                    # XLA may run all N forwards first and hold N x activations.
                    emb, _ = jax.lax.optimization_barrier((emb, gate))
                (loss, metrics), (dg, demb) = grad_fn(dense_params, emb, mb_i)
                # Gate on demb: it requires the FULL backward pass, so the
                # barrier orders bwd(i) before fwd(i+1), not just fwd(i).
                gate = demb.ravel()[0] * 0.0 + loss
                demb = demb * (1.0 / n_micro)
                with jax.named_scope("fwp_sparse"):
                    pkt = engine.grads_to_owner(plan_i, demb, mb_keys_shape,
                                                n_micro)
                dg = tree_scale(dg, 1.0 / n_micro)
                losses.append(loss)
                all_metrics.append(metrics)
                packets.append(pkt)
                gsum = dg if gsum is None else tree_add(gsum, dg)
            pkts = jax.tree.map(lambda *xs: jnp.stack(xs), *packets)
            metrics = jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs)), *all_metrics)
            return FwpStepOutputs(
                jnp.mean(jnp.stack(losses)), gsum, pkts, metrics
            )

    else:

        def window(dense_params, buffer, window_plan: WindowPlan, mb_batches):
            def body(carry, xs):
                gsum = carry
                plan_i, mb_i = xs
                loss, metrics, dg, pkt = one_micro(dense_params, buffer, plan_i, mb_i)
                return tree_add(gsum, dg), (loss, metrics, pkt)

            g0 = tree_zeros_like(dense_params)
            gsum, (losses, metrics, pkts) = jax.lax.scan(
                body, g0, (window_plan.plans, mb_batches)
            )
            metrics = jax.tree.map(jnp.mean, metrics)
            return FwpStepOutputs(jnp.mean(losses), gsum, pkts, metrics)

    return window


def close_window(
    engine: EmbeddingEngine,
    buffer: DualBuffer,
    outputs: FwpStepOutputs,
) -> DualBuffer:
    """Apply the window's accumulated sparse grads to the active buffer —
    the single per-step embedding update (frozen-window end)."""
    return engine.apply_window_to_buffer(buffer, outputs.packets)
