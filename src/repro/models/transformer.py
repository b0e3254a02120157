"""TransformerLM: the generic decoder backbone for the assigned LM archs.

Supports: GQA attention (+RoPE), swiglu/relu²/gelu MLPs, MoE FFNs, Mamba2
mixers, arbitrary per-layer (mixer, ffn) patterns (Jamba's 1:7 hybrid),
scan-over-layers with optional remat (compile-hygiene for 96-layer archs),
vocab-parallel chunked cross-entropy (shard_map), and KV-cache serving
(prefill + decode, with heads- or seq-sharded caches).

The token embedding is NOT part of this module: lookups go through the
NestPipe embedding engine (the paper's subject); the backbone consumes
ready embeddings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ModelConfig, ParallelConfig
from ..utils import cdiv
from . import layers as L
from . import mamba as M

# ---------------------------------------------------------------------------
# Parameter init / pspecs
# ---------------------------------------------------------------------------


def _pattern_groups(cfg: ModelConfig):
    """(period, n_rep): layers are stacked as n_rep repeats of the period."""
    plan = cfg.layer_plan
    period = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    n_rep = cfg.n_layers // period
    return plan[:period], n_rep


def _init_block(rng, cfg: ModelConfig, mixer: str, ffn: str, dtype):
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg.d_model, cfg.norm_type)}
    if mixer == "attn":
        p["attn"] = L.init_attention(ks[0], cfg.d_model, cfg.attention, dtype)
    else:
        p["mamba"] = M.init_mamba(ks[0], cfg.d_model, cfg.mamba, dtype)
    if ffn != "none":
        p["norm2"] = L.init_norm(cfg.d_model, cfg.norm_type)
        if ffn == "moe":
            p["moe"] = L.init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.moe,
                                  cfg.mlp_type, dtype)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def _block_pspecs(cfg: ModelConfig, mixer: str, ffn: str, n_expert_shards: int,
                  fsdp: Optional[str]):
    p: Dict[str, Any] = {"norm1": {"scale": P(None)}}
    if cfg.norm_type == "layernorm":
        p["norm1"]["bias"] = P(None)
    if mixer == "attn":
        p["attn"] = L.attention_pspecs(fsdp)
    else:
        p["mamba"] = M.mamba_pspecs(fsdp)
    if ffn != "none":
        p["norm2"] = dict(p["norm1"])
        if ffn == "moe":
            p["moe"] = L.moe_pspecs(cfg.moe, n_expert_shards, cfg.mlp_type, fsdp)
        else:
            p["mlp"] = L.mlp_pspecs(cfg.mlp_type, fsdp)
    return p


def init_lm_params(rng, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    pattern, n_rep = _pattern_groups(cfg)
    keys = jax.random.split(rng, n_rep * len(pattern) + 2)
    blocks = []
    ki = 0
    for pos, (mixer, ffn) in enumerate(pattern):
        reps = []
        for r in range(n_rep):
            reps.append(_init_block(keys[ki], cfg, mixer, ffn, dtype))
            ki += 1
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *reps))
    params = {
        "blocks": blocks,
        "final_norm": L.init_norm(cfg.d_model, cfg.norm_type),
        "head_w": jax.random.normal(keys[-1], (cfg.d_model, cfg.vocab_size), dtype)
        * (1.0 / cfg.d_model ** 0.5),
    }
    return params


def lm_pspecs(cfg: ModelConfig, parallel: ParallelConfig, mesh: Optional[Mesh] = None,
              *, for_optimizer: bool = False):
    fsdp = None
    if parallel.fsdp_axes and (for_optimizer or not parallel.zero1):
        # ZeRO-1: only optimizer state carries the fsdp axis
        fsdp = parallel.fsdp_axes if len(parallel.fsdp_axes) > 1 else parallel.fsdp_axes[0]
    n_es = 1
    if mesh is not None:
        n_es = 1
        for a in parallel.expert_axes:
            n_es *= mesh.shape[a]
    pattern, _ = _pattern_groups(cfg)
    blocks = []
    for mixer, ffn in pattern:
        bp = _block_pspecs(cfg, mixer, ffn, n_es, fsdp)
        blocks.append(jax.tree.map(
            lambda s: P(*(None,) + tuple(s)), bp, is_leaf=lambda x: isinstance(x, P)
        ))
    fn = {"scale": P(None)}
    if cfg.norm_type == "layernorm":
        fn["bias"] = P(None)
    return {"blocks": blocks, "final_norm": fn, "head_w": P(None, "model")}


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _cast_tree(params, dtype):
    return jax.tree.map(
        lambda p: p.astype(dtype) if (p.dtype == jnp.float32 and p.ndim > 1) else p,
        params,
    )


def _apply_block(p, cfg: ModelConfig, mixer: str, ffn: str, x, positions,
                 n_expert_shards: int, attn_impl: Optional[str] = None,
                 ep_ctx=None, tp_ctx=None):
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(p["norm1"], x, cfg.norm_eps)
    if mixer == "attn":
        x = x + L.gqa_attention(p["attn"], h, cfg.attention, positions=positions,
                                impl=attn_impl, tp_ctx=tp_ctx)
    else:
        x = x + M.mamba_mixer(p["mamba"], h, cfg.mamba)
    if ffn != "none":
        h = L.apply_norm(p["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux = L.apply_moe(p["moe"], h, cfg.moe, cfg.mlp_type, cfg.activation,
                                 n_expert_shards, ep_ctx=ep_ctx)
            x = x + y
        else:
            x = x + L.apply_mlp(p["mlp"], h, cfg.mlp_type, cfg.activation)
    return x, aux


def lm_backbone(
    params,
    cfg: ModelConfig,
    emb: jax.Array,  # (B, T, D) token embeddings from the engine
    *,
    parallel: ParallelConfig = ParallelConfig(),
    positions: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    attn_impl: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (hidden (B,T,D), moe_aux_loss)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = emb.astype(cdt)
    b, t, d = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    pattern, n_rep = _pattern_groups(cfg)
    n_es = 1
    if mesh is not None:
        for a in parallel.expert_axes:
            n_es *= mesh.shape[a]
    params = _cast_tree(params, cdt)

    # Sequence parallelism: keep the residual stream (the scan carry — the
    # tensor that survives every layer and dominates activation memory)
    # sharded over the tensor axes on the seq dim. GSPMD inserts the
    # all-gather before attention/matmuls and the reduce-scatter after.
    seq_constrain = lambda v: v
    if parallel.sequence_parallel and mesh is not None:
        s_model = 1
        for a in parallel.tensor_axes:
            s_model *= mesh.shape[a]
        if t % s_model == 0 and t > 1:
            ba = parallel.batch_axes if len(parallel.batch_axes) > 1 else (
                parallel.batch_axes[0] if parallel.batch_axes else None)
            ma = parallel.tensor_axes if len(parallel.tensor_axes) > 1 else \
                parallel.tensor_axes[0]
            sp_sharding = jax.sharding.NamedSharding(
                mesh, P(ba if b > 1 else None, ma, None))
            seq_constrain = lambda v: jax.lax.with_sharding_constraint(
                v, sp_sharding)
    x = seq_constrain(x)

    # Expert-parallel MoE context: shard_map All2All dispatch when tokens are
    # seq-shardable over the tensor axes and experts divide the shards.
    ep_ctx = None
    if mesh is not None and cfg.moe is not None and n_es > 1:
        s_model = 1
        for a in parallel.tensor_axes:
            s_model *= mesh.shape[a]
        if t % s_model == 0 and cfg.moe.num_experts % s_model == 0:
            ep_ctx = (mesh, parallel.batch_axes if b > 1 else (),
                      parallel.tensor_axes)
    tp_ctx = None
    if mesh is not None and cfg.attention is not None:
        tp_ctx = (mesh, parallel.batch_axes if b > 1 else (),
                  parallel.tensor_axes)

    def superblock(x, rep_params):
        aux = jnp.zeros((), jnp.float32)
        for pos, (mixer, ffn) in enumerate(pattern):
            x, a = _apply_block(rep_params[pos], cfg, mixer, ffn, x, positions,
                                n_es, attn_impl, ep_ctx, tp_ctx)
            aux = aux + a
        return seq_constrain(x), aux

    if parallel.scan_layers and n_rep > 1:
        body = superblock
        if parallel.remat == "full":
            body = jax.checkpoint(body)

        def scan_body(carry, rep_params):
            x, aux = carry
            x, a = body(x, rep_params)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
    else:
        aux = jnp.zeros((), jnp.float32)
        for r in range(n_rep):
            rep_params = jax.tree.map(lambda p: p[r], params["blocks"])
            body = superblock
            if parallel.remat == "full":
                body = jax.checkpoint(body)
            x, a = body(x, rep_params)
            aux = aux + a
    x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


# ---------------------------------------------------------------------------
# Vocab-parallel cross-entropy (chunked, shard_map)
# ---------------------------------------------------------------------------


def vocab_parallel_xent(
    hidden: jax.Array,  # (B, T, D)
    head_w: jax.Array,  # (D, V) sharded P(None, "model")
    labels: jax.Array,  # (B, T) int32 global token ids
    mesh: Optional[Mesh],
    *,
    batch_axes: Tuple[str, ...] = ("data",),
    model_axes: Tuple[str, ...] = ("model",),
    t_chunk: int = 512,
    pad_id: int = -1,
) -> jax.Array:
    """Megatron-style sharded softmax xent, chunked over T to bound the
    logits working set to (B_loc, t_chunk, V/S). Mean over non-pad tokens."""

    def _local(hid, w, lab):
        if mesh is None:
            shard_lo = 0
        else:
            sid = jnp.int32(0)
            for a in model_axes:
                sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
            shard_lo = sid * w.shape[1]
        bl, tl, dd = hid.shape
        vs = w.shape[1]
        tc = min(t_chunk, tl)
        nch = cdiv(tl, tc)
        pad = nch * tc - tl
        hid_p = jnp.pad(hid, ((0, 0), (0, pad), (0, 0))) if pad else hid
        lab_p = jnp.pad(lab, ((0, 0), (0, pad)), constant_values=pad_id) if pad else lab
        hid_c = hid_p.reshape(bl, nch, tc, dd).swapaxes(0, 1)
        lab_c = lab_p.reshape(bl, nch, tc).swapaxes(0, 1)

        def chunk_loss(carry, xs):
            h_c, l_c = xs
            logits = (h_c @ w).astype(jnp.float32)  # (B, tc, V/S)
            # stability shift: stop_gradient BEFORE pmax so autodiff sees a
            # zero tangent and never needs a pmax differentiation rule
            mx = jax.lax.stop_gradient(logits.max(-1))
            if mesh is not None:
                mx = jax.lax.pmax(mx, model_axes)
            lse = jnp.sum(jnp.exp(logits - mx[..., None]), -1)
            if mesh is not None:
                lse = jax.lax.psum(lse, model_axes)
            lse = jnp.log(lse) + mx
            li = l_c - shard_lo
            ok = (li >= 0) & (li < vs)
            li_c = jnp.clip(li, 0, vs - 1)
            picked = jnp.take_along_axis(logits, li_c[..., None], axis=-1)[..., 0]
            picked = jnp.where(ok, picked, 0.0)
            if mesh is not None:
                picked = jax.lax.psum(picked, model_axes)
            valid = (l_c != pad_id).astype(jnp.float32)
            nll = (lse - picked) * valid
            s, n = carry
            return (s + nll.sum(), n + valid.sum()), None

        # (1,)-shaped carries: scalar scan carries become scalar shard_map
        # residuals under grad, which the experimental shard_map's out-spec
        # rank check rejects (same reason engine overflow metrics are (1,)).
        (s, n), _ = jax.lax.scan(
            chunk_loss, (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.float32)),
            (hid_c, lab_c),
        )
        if mesh is not None and batch_axes:
            s = jax.lax.psum(s, batch_axes)
            n = jax.lax.psum(n, batch_axes)
        return s / jnp.maximum(n, 1.0)

    if mesh is None:
        return _local(hidden, head_w, labels)[0]
    ba = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    ma = model_axes if len(model_axes) > 1 else model_axes[0]
    f = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(ba, None, None), P(None, ma), P(ba, None)),
        out_specs=P(None),
        check_vma=False,
    )
    return f(hidden, head_w, labels)[0]


# ---------------------------------------------------------------------------
# Loss builder (plugs into the FWP executor)
# ---------------------------------------------------------------------------


def make_lm_loss_fn(cfg: ModelConfig, parallel: ParallelConfig,
                    mesh: Optional[Mesh] = None, *, attn_impl: Optional[str] = None,
                    t_chunk: int = 512):
    """loss_fn(dense_params, emb, mb) with mb = {"labels": (B,T)} — the
    signature the FWP executor expects."""
    batch_axes = parallel.batch_axes
    model_axes = parallel.tensor_axes

    def loss_fn(dense_params, emb, mb):
        if mesh is not None:
            emb = jax.lax.with_sharding_constraint(
                emb,
                jax.sharding.NamedSharding(
                    mesh,
                    P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None),
                ),
            )
        hidden, moe_aux = lm_backbone(
            dense_params, cfg, emb, parallel=parallel, mesh=mesh, attn_impl=attn_impl
        )
        head_w = dense_params["head_w"].astype(jnp.dtype(cfg.compute_dtype))
        loss = vocab_parallel_xent(
            hidden, head_w, mb["labels"], mesh,
            batch_axes=batch_axes, model_axes=model_axes, t_chunk=t_chunk,
        )
        aux_coef = cfg.moe.aux_loss_coef if cfg.moe is not None else 0.0
        total = loss + aux_coef * moe_aux
        return total, {"xent": loss, "moe_aux": moe_aux}

    return loss_fn


# ---------------------------------------------------------------------------
# Serving: KV caches, prefill, decode
# ---------------------------------------------------------------------------


class LMCache(NamedTuple):
    """Per-pattern-position cache stacked over repeats (mirrors params)."""

    caches: Tuple[Any, ...]  # per pattern position: dict of arrays
    length: jax.Array  # () int32 tokens already in cache


def init_lm_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> LMCache:
    pattern, n_rep = _pattern_groups(cfg)
    caches = []
    for mixer, _ in pattern:
        if mixer == "attn":
            a = cfg.attention
            kv = jnp.zeros((n_rep, batch, max_len, a.n_kv_heads, a.head_dim), dtype)
            caches.append({"k": kv, "v": kv})
        else:
            conv, ssm = M.init_mamba_cache(batch, cfg.d_model, cfg.mamba)
            caches.append({
                "conv": jnp.broadcast_to(conv, (n_rep,) + conv.shape),
                "ssm": jnp.broadcast_to(ssm, (n_rep,) + ssm.shape),
            })
    return LMCache(tuple(caches), jnp.zeros((), jnp.int32))


def lm_cache_pspecs(cfg: ModelConfig, parallel: ParallelConfig) -> LMCache:
    """KV cache sharding: batch over batch_axes; kv-heads over tensor axes
    when divisible, else seq-sharded (kv_shard="seq", flash-decoding)."""
    ba = parallel.batch_axes if len(parallel.batch_axes) > 1 else parallel.batch_axes[0]
    ma = parallel.tensor_axes if len(parallel.tensor_axes) > 1 else parallel.tensor_axes[0]
    pattern, _ = _pattern_groups(cfg)
    caches = []
    for mixer, _ in pattern:
        if mixer == "attn":
            if parallel.kv_shard == "seq":
                spec = P(None, ba, ma, None, None)
            else:
                spec = P(None, ba, None, ma, None)
            caches.append({"k": spec, "v": spec})
        else:
            caches.append({
                "conv": P(None, ba, None, ma),
                "ssm": P(None, ba, ma, None, None),
            })
    return LMCache(tuple(caches), P())


def _decode_attn_seqsharded(p, h, cache_k, cache_v, pos, acfg, mesh, model_axes):
    """Flash-decoding: cache length sharded over model axes; each shard
    computes a partial softmax over its slice, combined with a psum-logsumexp
    merge. Enables 500k-token caches (jamba long_500k)."""
    ma = model_axes if len(model_axes) > 1 else model_axes[0]

    def _local(h_l, ck, cv, pos_v):
        b = h_l.shape[0]
        S = 1
        sid = jnp.int32(0)
        for a in model_axes:
            sid = sid * mesh.shape[a] + jax.lax.axis_index(a)
            S *= mesh.shape[a]
        slice_len = ck.shape[1]
        q = (h_l @ p["attn"]["wq"]).reshape(b, 1, acfg.n_heads, acfg.head_dim)
        k = (h_l @ p["attn"]["wk"]).reshape(b, 1, acfg.n_kv_heads, acfg.head_dim)
        v = (h_l @ p["attn"]["wv"]).reshape(b, 1, acfg.n_kv_heads, acfg.head_dim)
        posb = jnp.broadcast_to(pos_v[None], (b, 1))
        q = L.apply_rope(q, posb, acfg.rope_theta)
        k = L.apply_rope(k, posb, acfg.rope_theta)
        # write the new token into the owning shard's slice
        local_pos = pos_v - sid * slice_len
        write_pos = jnp.clip(local_pos, 0, slice_len - 1)
        own = (local_pos >= 0) & (local_pos < slice_len)
        k_upd = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), write_pos, 1)
        v_upd = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), write_pos, 1)
        ck = jnp.where(own, k_upd, ck)
        cv = jnp.where(own, v_upd, cv)
        groups = acfg.n_heads // acfg.n_kv_heads
        kk = L._repeat_kv(ck.astype(q.dtype), groups)
        vv = L._repeat_kv(cv.astype(q.dtype), groups)
        scale = 1.0 / (acfg.head_dim ** 0.5)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk).astype(jnp.float32) * scale
        k_pos = sid * slice_len + jnp.arange(slice_len)
        s = jnp.where(k_pos[None, None, None, :] <= pos_v, s, -1e30)
        m_loc = s.max(-1)
        m = jax.lax.pmax(m_loc, ma)
        pexp = jnp.exp(s - m[..., None])
        denom = jax.lax.psum(pexp.sum(-1), ma)
        num = jnp.einsum("bhqk,bkhd->bhqd", pexp, vv.astype(jnp.float32))
        num = jax.lax.psum(num, ma)
        o = (num / jnp.maximum(denom, 1e-30)[..., None]).astype(h_l.dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        return o @ p["attn"]["wo"], ck, cv

    f = shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(None, None, None), P(None, ma, None, None),
                  P(None, ma, None, None), P()),
        out_specs=(P(None, None, None), P(None, ma, None, None),
                   P(None, ma, None, None)),
        check_vma=False,
    )
    return f(h, cache_k, cache_v, pos)


def lm_decode_step(
    params,
    cfg: ModelConfig,
    emb: jax.Array,  # (B, 1, D) embedding of the new token
    cache: LMCache,
    *,
    parallel: ParallelConfig = ParallelConfig(),
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, LMCache]:
    """One decode step. Returns (logits (B, V), updated cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = emb.astype(cdt)
    pos = cache.length
    pattern, n_rep = _pattern_groups(cfg)
    params = _cast_tree(params, cdt)
    new_caches = []

    def rep_step(x, rep_params, rep_cache):
        upd = {}
        for ppos, (mixer, ffn) in enumerate(pattern):
            p = rep_params[ppos]
            c = rep_cache[ppos]
            h = L.apply_norm(p["norm1"], x, cfg.norm_eps)
            if mixer == "attn":
                if parallel.kv_shard == "seq" and mesh is not None:
                    o, ck, cv = _decode_attn_seqsharded(
                        p, h, c["k"], c["v"], pos, cfg.attention, mesh,
                        parallel.tensor_axes,
                    )
                else:
                    o, ck, cv = L.gqa_decode(p["attn"], h, c["k"], c["v"], pos,
                                             cfg.attention)
                x = x + o
                upd[ppos] = {"k": ck, "v": cv}
            else:
                o, conv, ssm = M.mamba_decode_step(p["mamba"], h, cfg.mamba,
                                                   c["conv"], c["ssm"])
                x = x + o
                upd[ppos] = {"conv": conv, "ssm": ssm}
            if ffn != "none":
                h = L.apply_norm(p["norm2"], x, cfg.norm_eps)
                if ffn == "moe":
                    y, _ = L.apply_moe(p["moe"], h, cfg.moe, cfg.mlp_type,
                                       cfg.activation, 1)
                    x = x + y
                else:
                    x = x + L.apply_mlp(p["mlp"], h, cfg.mlp_type, cfg.activation)
        return x, upd

    # scan over repeats, carrying x; caches are scanned in/out
    def scan_body(x, xs):
        rep_params, rep_cache = xs
        x, upd = rep_step(x, rep_params, [rep_cache[i] for i in range(len(pattern))])
        return x, tuple(upd[i] for i in range(len(pattern)))

    rep_caches = tuple({k: v for k, v in c.items()} for c in cache.caches)
    if n_rep > 1:
        x, new_rep_caches = jax.lax.scan(
            scan_body, x, (params["blocks"], rep_caches)
        )
    else:
        sq = jax.tree.map(lambda v: v[0], rep_caches)
        x, upd = rep_step(x, [jax.tree.map(lambda p: p[0], bp) for bp in params["blocks"]],
                          [sq[i] for i in range(len(pattern))])
        new_rep_caches = jax.tree.map(lambda v: v[None], tuple(upd[i] for i in range(len(pattern))))
    x = L.apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = (x[:, 0] @ params["head_w"].astype(cdt)).astype(jnp.float32)
    return logits, LMCache(tuple(new_rep_caches), cache.length + 1)


def lm_prefill(
    params,
    cfg: ModelConfig,
    emb: jax.Array,  # (B, T, D)
    *,
    parallel: ParallelConfig = ParallelConfig(),
    mesh: Optional[Mesh] = None,
    cache_len: Optional[int] = None,
) -> Tuple[jax.Array, LMCache]:
    """Prefill forward: run the backbone over the prompt and build the KV
    cache. Returns (last-token logits (B, V), cache)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t, d = emb.shape
    max_len = cache_len or t
    x = emb.astype(cdt)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    pattern, n_rep = _pattern_groups(cfg)
    params_c = _cast_tree(params, cdt)

    # same EP-MoE / head-TP contexts as the training backbone (without them,
    # prefill MoE falls back to GSPMD-slotted dispatch with expert-weight
    # gathers — measured 2x collective regression on grok/jamba prefill)
    ep_ctx = None
    tp_ctx = None
    if mesh is not None:
        s_model = 1
        for a in parallel.tensor_axes:
            s_model *= mesh.shape[a]
        ba_ctx = parallel.batch_axes if b > 1 else ()
        if (cfg.moe is not None and t % s_model == 0
                and cfg.moe.num_experts % s_model == 0):
            ep_ctx = (mesh, ba_ctx, parallel.tensor_axes)
        if cfg.attention is not None:
            tp_ctx = (mesh, ba_ctx, parallel.tensor_axes)
    n_es = 1
    if mesh is not None:
        for a in parallel.expert_axes:
            n_es *= mesh.shape[a]

    def rep_fill(x, rep_params):
        caches = {}
        for ppos, (mixer, ffn) in enumerate(pattern):
            p = rep_params[ppos]
            h = L.apply_norm(p["norm1"], x, cfg.norm_eps)
            if mixer == "attn":
                a = cfg.attention
                k = (h @ p["attn"]["wk"]).reshape(b, t, a.n_kv_heads, a.head_dim)
                v = (h @ p["attn"]["wv"]).reshape(b, t, a.n_kv_heads, a.head_dim)
                k = L.apply_rope(k, positions, a.rope_theta)
                pad = max_len - t
                ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cdt)
                cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(cdt)
                x = x + L.gqa_attention(p["attn"], h, a, positions=positions,
                                        tp_ctx=tp_ctx)
                caches[ppos] = {"k": ck, "v": cv}
            else:
                o, (conv, ssm) = M.mamba_mixer(p["mamba"], h, cfg.mamba,
                                               return_state=True)
                x = x + o
                caches[ppos] = {"conv": conv, "ssm": ssm}
            if ffn != "none":
                h = L.apply_norm(p["norm2"], x, cfg.norm_eps)
                if ffn == "moe":
                    y, _ = L.apply_moe(p["moe"], h, cfg.moe, cfg.mlp_type,
                                       cfg.activation, n_es, ep_ctx=ep_ctx)
                    x = x + y
                else:
                    x = x + L.apply_mlp(p["mlp"], h, cfg.mlp_type, cfg.activation)
        return x, tuple(caches[i] for i in range(len(pattern)))

    if n_rep > 1:
        x, rep_caches = jax.lax.scan(
            lambda xx, rp: rep_fill(xx, rp), x, params_c["blocks"]
        )
    else:
        x, caches = rep_fill(x, [jax.tree.map(lambda p: p[0], bp)
                                 for bp in params_c["blocks"]])
        rep_caches = jax.tree.map(lambda v: v[None], caches)
    x = L.apply_norm(params_c["final_norm"], x, cfg.norm_eps)
    logits = (x[:, -1] @ params_c["head_w"].astype(cdt)).astype(jnp.float32)
    return logits, LMCache(tuple(rep_caches), jnp.full((), t, jnp.int32))
