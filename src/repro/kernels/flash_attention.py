"""Pallas TPU kernel: causal flash attention (forward).

Streaming-softmax attention with (block_q x block_k) VMEM tiles and running
(max, denom, acc) state carried across the k grid dimension — the TPU
blocking of FlashAttention with MXU-aligned tiles (multiples of 128 on the
lane dim; head_dim padded by the wrapper). Causal: k blocks strictly above
the diagonal are masked (their contribution is zero; the grid still visits
them — the classic skip optimization needs dynamic grids, which we trade
for simplicity since the dry-run roofline uses the pure-JAX chunked path).

Used for TPU execution via ``AttentionConfig.impl="pallas"``; validated in
interpret mode against ref.py on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..utils import cdiv, round_up

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, d_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  seq_k: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (block_q, hd)
    k = k_ref[0]  # (block_k, hd)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (block_q, block_k)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_k
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = mask & (q_pos >= k_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    d_ref[...] = d_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, H, hd) — kv heads pre-repeated by caller
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool,
) -> jax.Array:
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    hd_pad = round_up(hd, 128)
    bq = min(block_q, round_up(tq, 8))
    bk = min(block_k, round_up(tk, 8))
    tq_pad = round_up(tq, bq)
    tk_pad = round_up(tk, bk)

    def pad(x, t_pad):
        return jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0),
                           (0, hd_pad - hd)))

    # (B*H, T, hd) layout: grid over (bh, q blocks, k blocks)
    qp = pad(q, tq_pad).transpose(0, 2, 1, 3).reshape(b * h, tq_pad, hd_pad)
    kp = pad(k, tk_pad).transpose(0, 2, 1, 3).reshape(b * h, tk_pad, hd_pad)
    vp = pad(v, tk_pad).transpose(0, 2, 1, 3).reshape(b * h, tk_pad, hd_pad)

    grid = (b * h, tq_pad // bq, tk_pad // bk)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (hd ** 0.5), block_q=bq, block_k=bk,
        causal=causal, seq_k=tk,
    )
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd_pad), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd_pad), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, hd_pad), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd_pad), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq_pad, hd_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),  # running max
            pltpu.VMEM((bq, 1), jnp.float32),  # running denom
            pltpu.VMEM((bq, hd_pad), jnp.float32),  # accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    out = out.reshape(b, h, tq_pad, hd_pad)[:, :, :tq, :hd].transpose(0, 2, 1, 3)
    return out
