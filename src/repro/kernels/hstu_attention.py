"""Pallas TPU kernel: fused HSTU pointwise (silu) attention.

HSTU replaces softmax attention with ``A = silu(QK^T)/s`` (paper backbone,
Zhai et al. 2024). Without a softmax there is no running-max state: the
output is a plain sum over k blocks of ``silu(q k^T) v`` — embarrassingly
streamable, one f32 VMEM accumulator, causal-masked on the diagonal block.
This is the dense hot loop of the paper's own workload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import round_up


def _hstu_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, *, scale: float,
                 inv_s: float, block_q: int, block_k: int, causal: bool,
                 seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    a = jax.nn.silu(s) * inv_s
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    mask = k_pos < seq_k
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        mask = mask & (q_pos >= k_pos)
    a = jnp.where(mask, a, 0.0)
    acc_ref[...] += jax.lax.dot_general(
        a.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def hstu_attention(
    q: jax.Array,  # (B, T, H, dqk)
    k: jax.Array,  # (B, T, H, dqk)
    v: jax.Array,  # (B, T, H, dv)
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool,
) -> jax.Array:
    b, t, h, dqk = q.shape
    dv = v.shape[-1]
    dqk_pad = round_up(dqk, 128)
    dv_pad = round_up(dv, 128)
    bq = min(block_q, round_up(t, 8))
    bk = min(block_k, round_up(t, 8))
    t_pad = round_up(t, max(bq, bk))

    def prep(x, dp):
        x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0), (0, dp - x.shape[-1])))
        return x.transpose(0, 2, 1, 3).reshape(b * h, t_pad, dp)

    qp, kp, vp = prep(q, dqk_pad), prep(k, dqk_pad), prep(v, dv_pad)
    grid = (b * h, t_pad // bq, t_pad // bk)
    kernel = functools.partial(
        _hstu_kernel, scale=1.0 / (dqk ** 0.5), inv_s=1.0 / t, block_q=bq,
        block_k=bk, causal=causal, seq_k=t,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dqk_pad), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, dqk_pad), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, dv_pad), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv_pad), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t_pad, dv_pad), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dv_pad), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(b, h, t_pad, dv_pad)[:, :, :t, :dv].transpose(0, 2, 1, 3)
