"""HSTU backbone (Zhai et al., ICML 2024 — "Actions Speak Louder than
Words"), the paper's primary generative-recommendation model.

HSTU layer (pointwise aggregated attention):
    [U, V, Q, K] = split(silu(X W_uvqk))
    A = silu(Q K^T / sqrt(d)) * causal_mask / seq_norm   (NO softmax)
    Y = A V
    out = (rmsnorm(Y) ⊙ U) W_o + X

Training objective: autoregressive next-item prediction with in-batch
dot-product logits against the *same* lookup's embeddings (sampled-softmax
style) — so ALL gradients flow through the sparse embedding path, matching
the trillion-parameter sparse-dominated regime the paper targets.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ParallelConfig, RecsysModelConfig
from . import layers as L


def init_hstu_params(rng, cfg: RecsysModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    h = cfg.n_heads
    dqk = d // h
    dv = d // h
    keys = jax.random.split(rng, cfg.n_layers + 2)

    def layer(k):
        k1, k2 = jax.random.split(k)
        s = 1.0 / (d ** 0.5)
        return {
            "norm": L.init_norm(d, "layernorm"),
            "w_uvqk": jax.random.normal(k1, (d, h * (2 * dqk + 2 * dv))) * s,
            "w_o": jax.random.normal(k2, (h * dv, d)) * (1.0 / (h * dv) ** 0.5),
            "out_norm": L.init_norm(h * dv, "layernorm"),
        }

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[layer(k) for k in keys[: cfg.n_layers]])
    return {
        "layers": stacked,
        "in_proj": jax.random.normal(keys[-2], (cfg.max_table_dim, d)) * 0.02,
        "final_norm": L.init_norm(d, "layernorm"),
    }


def hstu_pspecs(cfg: RecsysModelConfig):
    """Paper §II-A: recsys dense layers are small and REPLICATED (pure data
    parallelism; grads AllReduce) — batch shards over every worker, so any
    TP sharding here would fight the batch axes and force giant activation
    gathers (measured 16 GiB/step AGs before this fix, §Perf hstu iter 2)."""
    norm = {"scale": P(None, None), "bias": P(None, None)}
    return {
        "layers": {
            "norm": norm,
            "w_uvqk": P(None, None, None),
            "w_o": P(None, None, None),
            "out_norm": {"scale": P(None, None), "bias": P(None, None)},
        },
        "in_proj": P(None, None),
        "final_norm": {"scale": P(None), "bias": P(None)},
    }


def _hstu_layer(p, x, h: int, dqk: int, dv: int, eps: float, q_chunk: int = 256):
    b, s, d = x.shape
    n = L.apply_norm(p["norm"], x, eps)
    mixed = jax.nn.silu(n @ p["w_uvqk"])
    with jax.named_scope("fwp_attention"):
        u, v, q, k = jnp.split(
            mixed.reshape(b, s, h, 2 * dqk + 2 * dv),
            [dv, 2 * dv, 2 * dv + dqk],
            axis=-1,
        )
        # Pointwise (no-softmax) aggregation streams trivially: process
        # query chunks so the (b,h,qc,s) score block bounds memory,
        # causal-sliced keys.
        qc = max(q_chunk, -(-s // 8))  # <=8 unrolled chunks (compile hygiene)
        outs = []
        for i in range(0, s, qc):
            qi = q[:, i : i + qc]
            kv_len = min(s, i + qi.shape[1])
            ki = k[:, :kv_len]
            vi = v[:, :kv_len]
            scores = jnp.einsum("bqhd,bkhd->bhqk", qi, ki) / (dqk ** 0.5)
            a = jax.nn.silu(scores)
            q_pos = jnp.arange(qi.shape[1]) + i
            k_pos = jnp.arange(kv_len)
            a = jnp.where(q_pos[:, None] >= k_pos[None, :], a, 0.0) / s
            outs.append(jnp.einsum("bhqk,bkhd->bqhd", a, vi))
        y = jnp.concatenate(outs, axis=1).reshape(b, s, h * dv)
    y = L.apply_norm(p["out_norm"], y, eps) * u.reshape(b, s, h * dv)
    return x + y @ p["w_o"]


def hstu_forward(params, cfg: RecsysModelConfig, emb: jax.Array) -> jax.Array:
    """emb: (B, S, D_emb) item-embedding sequence -> hidden (B, S, d_model)."""
    d = cfg.d_model
    h = cfg.n_heads
    dqk = dv = d // h
    x = emb @ params["in_proj"]

    @jax.checkpoint  # remat: only layer-boundary residuals survive to bwd
    def body_fn(x, lp):
        return _hstu_layer(lp, x, h, dqk, dv, cfg.norm_eps)

    def body(x, lp):
        return body_fn(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.apply_norm(params["final_norm"], x, cfg.norm_eps)


def sequence_infonce(preds: jax.Array, targets: jax.Array,
                     temperature: float = 0.05):
    """Per-sequence sampled-softmax: position t's prediction scored against
    all target items of the SAME sequence (positives on the diagonal).

    O(B·S²·d) — independent of global batch, so it scales to industrial
    batch sizes where cross-batch in-batch negatives (O((BS)²)) cannot.
    """
    pf = preds / (jnp.linalg.norm(preds, axis=-1, keepdims=True) + 1e-6)
    tf = targets / (jnp.linalg.norm(targets, axis=-1, keepdims=True) + 1e-6)
    logits = jnp.einsum("bqd,bkd->bqk", pf, tf) / temperature  # (B, S-1, S-1)
    s = logits.shape[1]
    diag = jnp.arange(s)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.mean(logp[:, diag, diag])
    acc = jnp.mean(jnp.argmax(logits, -1) == diag[None])
    return loss, acc


def make_hstu_loss_fn(cfg: RecsysModelConfig, parallel: ParallelConfig,
                      mesh: Optional[Mesh] = None, *, temperature: float = 0.05):
    """Next-item InfoNCE over each sequence's own item embeddings.

    loss_fn(dense_params, emb, mb): emb (B, S, D) — position t's hidden
    predicts the embedding of item t+1 against in-sequence negatives.
    All gradients flow through the sparse embedding path (twice: input and
    target sides), matching the sparse-dominated regime the paper targets.
    """

    def loss_fn(dense_params, emb, mb):
        if mesh is not None:
            ba = parallel.batch_axes if len(parallel.batch_axes) > 1 else parallel.batch_axes[0]
            emb = jax.lax.with_sharding_constraint(
                emb, jax.sharding.NamedSharding(mesh, P(ba, None, None)))
        hidden = hstu_forward(dense_params, cfg, emb)  # (B, S, d)
        preds = hidden[:, :-1]  # predict items 1..S-1
        targets = emb[:, 1:] @ dense_params["in_proj"]  # (B, S-1, d)
        loss, acc = sequence_infonce(preds, targets, temperature)
        return loss, {"hitrate_inseq": acc}

    return loss_fn
