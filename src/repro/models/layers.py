"""Shared neural layers: norms, RoPE, GQA attention (naive / chunked-flash /
Pallas), MLPs (swiglu / relu² / gelu), MoE (shard_map EP and GSPMD paths).

All layers are pure functions over explicit param pytrees. Initializers
return params; ``*_pspecs`` return matching PartitionSpec pytrees for the
production mesh (TP over "model", optional FSDP over "data").
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import AttentionConfig, MoEConfig
from ..utils import cdiv, round_up

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(d: int, norm_type: str = "rmsnorm"):
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_norm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in params:  # layernorm
        mean = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, hd); positions: (..., T) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def activation_fn(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # squared ReLU (nemotron-4)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def init_mlp(rng, d: int, f: int, mlp_type: str, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / (d ** 0.5)
    s_out = 1.0 / (f ** 0.5)
    p = {
        "wi": jax.random.normal(k1, (d, f), dtype) * s_in,
        "wo": jax.random.normal(k2, (f, d), dtype) * s_out,
    }
    if mlp_type == "swiglu":
        p["wg"] = jax.random.normal(k3, (d, f), dtype) * s_in
    return p


def mlp_pspecs(mlp_type: str, fsdp: Optional[str] = None):
    p = {"wi": P(fsdp, "model"), "wo": P("model", fsdp)}
    if mlp_type == "swiglu":
        p["wg"] = P(fsdp, "model")
    return p


def apply_mlp(params, x, mlp_type: str, activation: str):
    act = activation_fn(activation)
    h = x @ params["wi"]
    if mlp_type == "swiglu":
        h = act(x @ params["wg"]) * h
    else:
        h = act(h)
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# Attention (GQA + RoPE), three implementations
# ---------------------------------------------------------------------------


def init_attention(rng, d: int, cfg: AttentionConfig, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s = 1.0 / (d ** 0.5)
    so = 1.0 / ((cfg.n_heads * cfg.head_dim) ** 0.5)
    return {
        "wq": jax.random.normal(k1, (d, cfg.n_heads * cfg.head_dim), dtype) * s,
        "wk": jax.random.normal(k2, (d, cfg.n_kv_heads * cfg.head_dim), dtype) * s,
        "wv": jax.random.normal(k3, (d, cfg.n_kv_heads * cfg.head_dim), dtype) * s,
        "wo": jax.random.normal(k4, (cfg.n_heads * cfg.head_dim, d), dtype) * so,
    }


def attention_pspecs(fsdp: Optional[str] = None):
    return {"wq": P(fsdp, "model"), "wk": P(fsdp, "model"), "wv": P(fsdp, "model"),
            "wo": P("model", fsdp)}


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, T, KV, hd) -> (B, T, KV*groups, hd) by group repetition."""
    if groups == 1:
        return k
    b, t, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, kv, groups, hd)).reshape(
        b, t, kv * groups, hd
    )


def naive_attention(q, k, v, *, causal: bool, q_offset: int = 0,
                    kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Materialized-scores reference. q: (B, Tq, H, hd), k/v: (B, Tk, H, hd)."""
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = jnp.arange(tq) + q_offset
    k_pos = jnp.arange(tk)
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if kv_len is not None:
        mask &= k_pos[None, :] < kv_len
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def chunked_attention(q, k, v, *, causal: bool, q_chunk: int, kv_chunk: int,
                      q_offset: int = 0) -> jax.Array:
    """Flash-style streaming attention in pure JAX.

    Unrolls query chunks (static count) and scans key/value chunks with a
    running (max, denom, acc) triple. For causal attention each query chunk
    only visits keys up to its own end — no wasted FLOPs in the lowered HLO
    (the dry-run roofline counts real work only).
    """
    b, tq, h, hd = q.shape
    tk = k.shape[1]
    # Cap the number of UNROLLED query chunks at 8: HLO size (and so compile
    # time) grows linearly with the unroll count while the causal-FLOP
    # savings saturate quickly (<=1/16 waste at 8 chunks).
    qc = min(max(q_chunk, cdiv(tq, 8)), tq)
    kc = min(kv_chunk, tk)
    n_q = cdiv(tq, qc)
    scale = 1.0 / (hd ** 0.5)

    outs = []
    for i in range(n_q):
        q_i = jax.lax.dynamic_slice_in_dim(q, i * qc, min(qc, tq - i * qc), axis=1)
        tq_i = q_i.shape[1]
        q_hi = i * qc + tq_i + q_offset  # causal horizon for this chunk
        tk_i = min(tk, q_hi) if causal else tk
        tk_i = max(tk_i, 1)
        n_k = cdiv(tk_i, kc)
        k_i = jax.lax.slice_in_dim(k, 0, n_k * kc if n_k * kc <= tk else tk, axis=1)
        v_i = jax.lax.slice_in_dim(v, 0, k_i.shape[1], axis=1)
        # pad kv to multiple of kc for the scan
        pad = n_k * kc - k_i.shape[1]
        if pad > 0:
            k_i = jnp.pad(k_i, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_i = jnp.pad(v_i, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_ch = k_i.reshape(b, n_k, kc, h, hd).transpose(1, 0, 2, 3, 4)
        v_ch = v_i.reshape(b, n_k, kc, h, hd).transpose(1, 0, 2, 3, 4)
        q_pos = jnp.arange(tq_i) + i * qc + q_offset

        def body(carry, xs):
            m_run, d_run, acc = carry
            k_c, v_c, j = xs
            # bf16 operands + f32 MXU accumulation: halves the wire/HBM bytes
            # of the attention fwd/bwd vs all-f32 internals (§Perf iteration).
            s = jnp.einsum("bqhd,bkhd->bhqk", q_i, k_c,
                           preferred_element_type=jnp.float32) * scale
            k_pos = j * kc + jnp.arange(kc)
            mask = k_pos[None, :] < tk_i  # drop padding
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m_run, s.max(-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            d_new = d_run * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(q_i.dtype), v_c,
                preferred_element_type=jnp.float32,
            )
            return (m_new, d_new, acc), None

        m0 = jnp.full((b, h, tq_i), -jnp.inf, jnp.float32)
        d0 = jnp.zeros((b, h, tq_i), jnp.float32)
        a0 = jnp.zeros((b, h, tq_i, hd), jnp.float32)
        (m, d, acc), _ = jax.lax.scan(
            body, (m0, d0, a0), (k_ch, v_ch, jnp.arange(n_k))
        )
        out_i = (acc / jnp.maximum(d, 1e-30)[..., None]).astype(q.dtype)
        outs.append(out_i.transpose(0, 2, 1, 3))  # (B, tq_i, H, hd)
    return jnp.concatenate(outs, axis=1)


def gqa_attention(
    params,
    x: jax.Array,  # (B, T, D)
    cfg: AttentionConfig,
    *,
    positions: Optional[jax.Array] = None,
    impl: Optional[str] = None,
    tp_ctx=None,  # (mesh, batch_axes, tensor_axes): explicit head-TP layout
) -> jax.Array:
    """Full-sequence GQA attention (training / prefill-style).

    With ``tp_ctx``, q/k/v are constrained to a head-sharded layout
    (padding the head dim to the shard count when it doesn't divide — yi's
    56 heads on 16-way TP) so the whole attention computes with local heads
    and k/v are gathered over seq exactly ONCE per layer instead of per
    kv-chunk (§Perf yi-34b iteration: kills the per-chunk gather storm).
    """
    b, t, d = x.shape
    impl = impl or cfg.impl
    q = (x @ params["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    groups = cfg.n_heads // cfg.n_kv_heads

    h_eff = cfg.n_heads
    if tp_ctx is not None:
        import math

        import jax.sharding as jsh

        mesh, batch_axes, tensor_axes = tp_ctx
        s = 1
        for a in tensor_axes:
            s *= mesh.shape[a]
        ba = batch_axes if len(batch_axes) > 1 else (
            batch_axes[0] if batch_axes else None)
        ma = tensor_axes if len(tensor_axes) > 1 else tensor_axes[0]
        # 1) replicate the small pre-repeat k/v over the model axes — ONE
        #    gather per layer; the subsequent head-dim repeat/pad then
        #    partitions by cheap local slicing instead of XLA's
        #    "involuntary full rematerialization" (seq-shard -> head-shard
        #    on a broadcast is inexpressible; measured 2x collective win).
        # Repeat kv to full heads, pad the head dim to the shard count
        # (yi: 56 -> 64; zero heads sliced off after attention), and pin the
        # head-sharded layout. [Two refuted §Perf variants, kept as notes:
        # (a) group-structured pad preserving head->kv pairing: 76.6s vs
        # 64.6s collective — slicing the padded group dim of a sharded 5D
        # tensor forces extra reshards; (b) pre-replicating k/v over the
        # model axes before the repeat: 69.8s — the extra gathers cost more
        # than the involuntary-remat copies they avoid.]
        k = _repeat_kv(k, groups)
        v = _repeat_kv(v, groups)
        h_pad = round_up(cfg.n_heads, s)
        if h_pad != cfg.n_heads:
            padw = ((0, 0), (0, 0), (0, h_pad - cfg.n_heads), (0, 0))
            q, k, v = jnp.pad(q, padw), jnp.pad(k, padw), jnp.pad(v, padw)
        hs = jsh.NamedSharding(mesh, jsh.PartitionSpec(ba, None, ma, None))
        q = jax.lax.with_sharding_constraint(q, hs)
        k = jax.lax.with_sharding_constraint(k, hs)
        v = jax.lax.with_sharding_constraint(v, hs)
        h_eff = h_pad
    else:
        k = _repeat_kv(k, groups)
        v = _repeat_kv(v, groups)

    if impl == "naive":
        o = naive_attention(q, k, v, causal=cfg.causal)
    elif impl == "pallas":
        from ..kernels import ops as kops

        o = kops.flash_attention(q, k, v, causal=cfg.causal)
    else:
        o = chunked_attention(
            q, k, v, causal=cfg.causal, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk
        )
    if h_eff != cfg.n_heads:  # drop the zero padding heads
        o = o[:, :, : cfg.n_heads]
    return o.reshape(b, t, -1) @ params["wo"]


def gqa_decode(
    params,
    x: jax.Array,  # (B, 1, D)
    cache_k: jax.Array,  # (B, S, KV, hd)
    cache_v: jax.Array,
    pos: jax.Array,  # () current position
    cfg: AttentionConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode with KV cache update. Linear in cache length."""
    b, _, d = x.shape
    q = (x @ params["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
    k = (x @ params["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ params["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
    posb = jnp.broadcast_to(pos[None], (b, 1)) if pos.ndim == 0 else pos
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, axis=1)
    groups = cfg.n_heads // cfg.n_kv_heads
    kk = _repeat_kv(cache_k, groups)
    vv = _repeat_kv(cache_v, groups)
    o = naive_attention(q, kk.astype(q.dtype), vv.astype(q.dtype), causal=False,
                        kv_len=pos + 1)
    return (o.reshape(b, 1, -1) @ params["wo"], cache_k, cache_v)


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def init_moe(rng, d: int, f: int, cfg: MoEConfig, mlp_type: str, dtype=jnp.float32):
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    e = cfg.num_experts
    s_in = 1.0 / (d ** 0.5)
    s_out = 1.0 / (f ** 0.5)
    p = {
        "router": jax.random.normal(k0, (d, e), jnp.float32) * s_in,
        "wi": jax.random.normal(k1, (e, d, f), dtype) * s_in,
        "wo": jax.random.normal(k2, (e, f, d), dtype) * s_out,
    }
    if mlp_type == "swiglu":
        p["wg"] = jax.random.normal(k3, (e, d, f), dtype) * s_in
    return p


def moe_pspecs(cfg: MoEConfig, num_expert_shards: int, mlp_type: str,
               fsdp: Optional[str] = None):
    """Experts sharded over 'model' when divisible (EP); else TP on d_ff."""
    if cfg.num_experts % max(num_expert_shards, 1) == 0 and num_expert_shards > 1:
        wi_spec, wo_spec = P("model", fsdp, None), P("model", None, fsdp)
    else:  # E < shards (grok-1): tensor-parallel experts on the ff dim
        wi_spec, wo_spec = P(None, fsdp, "model"), P(None, "model", fsdp)
    p = {"router": P(None, None), "wi": wi_spec, "wo": wo_spec}
    if mlp_type == "swiglu":
        p["wg"] = wi_spec
    return p


def _topk_routing(logits: jax.Array, top_k: int):
    """(T, E) -> (T, k) expert ids + combine weights (softmax over top-k)."""
    gates, ids = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(gates.astype(jnp.float32), axis=-1)
    return ids, weights


def moe_aux_loss(logits: jax.Array, ids: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balance loss: E * sum(frac_tokens * frac_prob)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    frac_prob = probs.mean(0)
    onehot = jax.nn.one_hot(ids[:, 0], num_experts)  # top-1 assignment share
    frac_tok = onehot.mean(0)
    return num_experts * jnp.sum(frac_prob * frac_tok)


def apply_moe_dense(params, x, cfg: MoEConfig, mlp_type: str, activation: str):
    """Masked-dense MoE: every expert computes every token; combine via
    top-k weights. FLOP-inflated by E/top_k but fully GSPMD-shardable — used
    when E is not divisible by the expert shard count (grok-1)."""
    b, t, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ params["router"]
    ids, w = _topk_routing(logits, cfg.top_k)
    act = activation_fn(activation)
    h = jnp.einsum("td,edf->etf", xt, params["wi"])
    if mlp_type == "swiglu":
        h = act(jnp.einsum("td,edf->etf", xt, params["wg"])) * h
    else:
        h = act(h)
    y = jnp.einsum("etf,efd->etd", h, params["wo"])  # (E, T, D)
    combine = jnp.zeros((xt.shape[0], cfg.num_experts), jnp.float32)
    combine = combine.at[jnp.arange(xt.shape[0])[:, None], ids].add(w)
    out = jnp.einsum("te,etd->td", combine.astype(y.dtype), y)
    aux = moe_aux_loss(logits, ids, cfg.num_experts)
    return out.reshape(b, t, d), aux


def apply_moe_slotted(params, x, cfg: MoEConfig, mlp_type: str, activation: str):
    """Capacity-slotted MoE (sort + scatter dispatch, gather combine).

    Exact-FLOP expert compute: tokens are ranked per expert and placed into
    (E, Cap) slots; overflow tokens are dropped (standard Switch semantics).
    Works at the pjit level; expert einsums shard over 'model'.
    """
    b, t, d = x.shape
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    e, k = cfg.num_experts, cfg.top_k
    cap = max(8, int(n * k / e * cfg.capacity_factor))
    cap = round_up(cap, 8)
    logits = xt.astype(jnp.float32) @ params["router"]
    ids, w = _topk_routing(logits, k)  # (n, k)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_exp = ids.reshape(-1)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_exp)
    se, st, sw = flat_exp[order], flat_tok[order], flat_w[order]
    starts = jnp.searchsorted(se, jnp.arange(e), side="left")
    rank = jnp.arange(n * k) - starts[se]
    ok = rank < cap
    slot = jnp.where(ok, se * cap + rank, e * cap)
    xe = jnp.zeros((e * cap, d), xt.dtype).at[slot].set(xt[st], mode="drop")
    xe = xe.reshape(e, cap, d)
    act = activation_fn(activation)
    h = jnp.einsum("ecd,edf->ecf", xe, params["wi"])
    if mlp_type == "swiglu":
        h = act(jnp.einsum("ecd,edf->ecf", xe, params["wg"])) * h
    else:
        h = act(h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["wo"]).reshape(e * cap, d)
    contrib = jnp.take(ye, jnp.minimum(slot, e * cap - 1), axis=0)
    contrib = jnp.where(ok[:, None], contrib, 0.0) * sw[:, None].astype(ye.dtype)
    out = jnp.zeros((n, d), ye.dtype).at[st].add(contrib)
    aux = moe_aux_loss(logits, ids, e)
    return out.reshape(b, t, d).astype(x.dtype), aux


def apply_moe_ep_shardmap(params, x, cfg: MoEConfig, mlp_type: str,
                          activation: str, mesh, batch_axes, model_axes,
                          *, slack: float = None):
    """Expert-parallel MoE via shard_map fixed-capacity All2All dispatch.

    Reuses the NestPipe routing pattern (sort -> capacity slots -> All2All)
    with experts as owners: tokens enter seq-sharded over the model axes
    (the SP layout at block boundaries), each device routes its local
    tokens' top-k picks to the shard owning the expert, local experts
    compute, results return by a second All2All. The collective payload is
    exactly tokens x top_k x D per direction — no global scatter/gather,
    no replicated (E, Cap, D) buffers (measured ~50x collective-byte
    reduction vs the GSPMD-slotted path on olmoe, EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if slack is None:
        slack = cfg.capacity_factor
    e = cfg.num_experts
    s = 1
    for a in model_axes:
        s *= mesh.shape[a]
    e_loc = e // s
    ma = model_axes if len(model_axes) > 1 else model_axes[0]
    ba = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes
                                                 else None)
    act = activation_fn(activation)
    axis = model_axes if len(model_axes) > 1 else model_axes[0]

    def _local(wr, wi, wo, wg, xl):
        b_loc, t_loc, d = xl.shape
        n = b_loc * t_loc
        xt = xl.reshape(n, d)
        sid = jnp.int32(0)
        for a in model_axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
        logits = xt.astype(jnp.float32) @ wr
        ids, w = _topk_routing(logits, cfg.top_k)  # (n, k)
        k = cfg.top_k
        flat_tok = jnp.repeat(jnp.arange(n), k)
        flat_eid = ids.reshape(-1)
        flat_w = w.reshape(-1)
        dest = flat_eid // e_loc  # owning shard
        order = jnp.argsort(dest)
        dest_s, tok_s, eid_s, w_s = dest[order], flat_tok[order], \
            flat_eid[order], flat_w[order]
        starts = jnp.searchsorted(dest_s, jnp.arange(s), side="left")
        rank = jnp.arange(n * k) - starts[dest_s]
        cap = round_up(max(int(n * k / s * slack), 8), 8)
        ok = rank < cap
        slot = jnp.where(ok, dest_s * cap + rank, s * cap)
        send_x = jnp.zeros((s * cap, d), xl.dtype).at[slot].set(
            jnp.take(xt, tok_s, 0), mode="drop")
        send_eid = jnp.full((s * cap,), -1, jnp.int32).at[slot].set(
            eid_s.astype(jnp.int32), mode="drop")
        recv_x = jax.lax.all_to_all(send_x.reshape(s, cap, d), axis, 0, 0,
                                    tiled=True) if s > 1 else \
            send_x.reshape(s, cap, d)
        recv_eid = jax.lax.all_to_all(send_eid.reshape(s, cap), axis, 0, 0,
                                      tiled=True) if s > 1 else \
            send_eid.reshape(s, cap)

        # local expert dispatch (second sort, expert-local slots)
        r_eid = recv_eid.reshape(-1)
        leid = jnp.where(r_eid >= 0, r_eid - sid * e_loc, e_loc)
        order2 = jnp.argsort(leid)
        leid_s = leid[order2]
        starts2 = jnp.searchsorted(leid_s, jnp.arange(e_loc + 1), side="left")
        rank2 = jnp.arange(s * cap) - starts2[jnp.minimum(leid_s, e_loc)]
        cap_e = round_up(max(int(s * cap / max(e_loc, 1) * slack), 8), 8)
        ok2 = (rank2 < cap_e) & (leid_s < e_loc)
        slot2 = jnp.where(ok2, leid_s * cap_e + rank2, e_loc * cap_e)
        xe = jnp.zeros((e_loc * cap_e, d), xl.dtype).at[slot2].set(
            jnp.take(recv_x.reshape(-1, d), order2, 0), mode="drop")
        xe = xe.reshape(e_loc, cap_e, d)
        h = jnp.einsum("ecd,edf->ecf", xe, wi)
        if mlp_type == "swiglu":
            h = act(jnp.einsum("ecd,edf->ecf", xe, wg)) * h
        else:
            h = act(h)
        ye = jnp.einsum("ecf,efd->ecd", h, wo).reshape(-1, d)
        # un-dispatch back to the recv layout, then All2All home
        y_recv = jnp.zeros((s * cap, d), xl.dtype).at[order2].set(
            jnp.where(ok2[:, None],
                      jnp.take(ye, jnp.minimum(slot2, e_loc * cap_e - 1), 0),
                      0.0).astype(xl.dtype))
        y_home = jax.lax.all_to_all(y_recv.reshape(s, cap, d), axis, 0, 0,
                                    tiled=True) if s > 1 else \
            y_recv.reshape(s, cap, d)
        y_flat = y_home.reshape(-1, d)
        contrib = jnp.take(y_flat, jnp.minimum(slot, s * cap - 1), 0)
        contrib = jnp.where(ok[:, None], contrib, 0.0) * w_s[:, None].astype(
            y_flat.dtype)
        out = jnp.zeros((n, d), xl.dtype).at[tok_s].add(contrib)
        aux = moe_aux_loss(logits, ids, e)
        aux = jax.lax.pmean(aux, model_axes)
        if ba is not None:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(b_loc, t_loc, d), aux[None]

    wg = params.get("wg", params["wi"])
    f = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(None, None), P(ma, None, None), P(ma, None, None),
                  P(ma, None, None), P(ba, ma, None)),
        out_specs=(P(ba, ma, None), P(None)),
        check_vma=False,
    )
    out, aux = f(params["router"], params["wi"], params["wo"], wg, x)
    return out, aux[0]


def apply_moe(params, x, cfg: MoEConfig, mlp_type: str, activation: str,
              num_expert_shards: int = 1, *, ep_ctx=None):
    """ep_ctx = (mesh, batch_axes, model_axes) enables the shard_map EP path
    when experts divide the expert shards (olmoe 64/16, jamba 16/16)."""
    if (ep_ctx is not None and num_expert_shards > 1
            and cfg.num_experts % num_expert_shards == 0):
        mesh, batch_axes, model_axes = ep_ctx
        return apply_moe_ep_shardmap(params, x, cfg, mlp_type, activation,
                                     mesh, batch_axes, model_axes)
    if cfg.num_experts % max(num_expert_shards, 1) == 0 or num_expert_shards <= 1:
        return apply_moe_slotted(params, x, cfg, mlp_type, activation)
    return apply_moe_dense(params, x, cfg, mlp_type, activation)
