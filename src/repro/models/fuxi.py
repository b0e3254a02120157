"""FUXI-α backbone (Ye et al., WWW 2025): feature-interaction-enhanced
transformer for sequential recommendation.

Reproduction scope: the Adaptive Multi-channel Self-attention (softmax
attention over the behaviour sequence) plus the Multi-stage Feedforward
(MFFN) realized as multi-order feature interactions
``v_{k+1} = v_k ⊙ σ(W_k x) + v_k`` (xDeepFM-style Hadamard orders) — the
architectural signature that distinguishes FUXI from HSTU in the paper's
experiments. Same in-batch next-item objective as HSTU so both backbones
exercise the identical sparse path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import AttentionConfig, ParallelConfig, RecsysModelConfig
from . import layers as L

_FI_ORDERS = 3  # interaction orders in the MFFN block


def _attn_cfg(cfg: RecsysModelConfig) -> AttentionConfig:
    return AttentionConfig(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
        head_dim=cfg.d_model // cfg.n_heads, impl="chunked",
        q_chunk=256, kv_chunk=256,
    )


def init_fuxi_params(rng, cfg: RecsysModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    keys = jax.random.split(rng, cfg.n_layers + 2)
    acfg = _attn_cfg(cfg)

    def layer(k):
        ks = jax.random.split(k, 2 + _FI_ORDERS)
        p = {
            "norm1": L.init_norm(d, "rmsnorm"),
            "attn": L.init_attention(ks[0], d, acfg),
            "norm2": L.init_norm(d, "rmsnorm"),
            "w_up": jax.random.normal(ks[1], (d, cfg.d_ff)) * (1.0 / d ** 0.5),
        }
        for o in range(_FI_ORDERS):
            p[f"w_fi{o}"] = jax.random.normal(ks[2 + o], (cfg.d_ff, cfg.d_ff)) * (
                1.0 / cfg.d_ff ** 0.5
            )
        p["w_down"] = jax.random.normal(ks[-1], (cfg.d_ff, d)) * (1.0 / cfg.d_ff ** 0.5)
        return p

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[layer(k) for k in keys[: cfg.n_layers]])
    return {
        "layers": stacked,
        "in_proj": jax.random.normal(keys[-2], (cfg.max_table_dim, d)) * 0.02,
        "final_norm": L.init_norm(d, "rmsnorm"),
    }


def fuxi_pspecs(cfg: RecsysModelConfig):
    """Dense layers replicated (paper hybrid architecture) — see hstu.py."""
    rep = jax.tree.map(lambda s: P(*(None,) * (len(tuple(s)) + 1)),
                       L.attention_pspecs(None),
                       is_leaf=lambda x: isinstance(x, P))
    layer = {
        "norm1": {"scale": P(None, None)},
        "attn": rep,
        "norm2": {"scale": P(None, None)},
        "w_up": P(None, None, None),
        "w_down": P(None, None, None),
    }
    for o in range(_FI_ORDERS):
        layer[f"w_fi{o}"] = P(None, None, None)
    return {"layers": layer, "in_proj": P(None, None),
            "final_norm": {"scale": P(None)}}


def fuxi_forward(params, cfg: RecsysModelConfig, emb: jax.Array) -> jax.Array:
    x = emb @ params["in_proj"]
    b, s, d = x.shape
    acfg = _attn_cfg(cfg)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    @jax.checkpoint  # remat: only layer-boundary residuals survive to bwd
    def body_fn(x, lp):
        h = L.apply_norm(lp["norm1"], x, cfg.norm_eps)
        with jax.named_scope("fwp_attention"):
            a = L.gqa_attention(lp["attn"], h, acfg, positions=positions)
        x = x + a
        h = L.apply_norm(lp["norm2"], x, cfg.norm_eps)
        v = h @ lp["w_up"]
        base = v
        for o in range(_FI_ORDERS):  # multi-order Hadamard interactions
            v = v * jax.nn.sigmoid(base @ lp[f"w_fi{o}"]) + v
        x = x + v @ lp["w_down"]
        return x

    x, _ = jax.lax.scan(lambda c, lp: (body_fn(c, lp), None), x, params["layers"])
    return L.apply_norm(params["final_norm"], x, cfg.norm_eps)


def make_fuxi_loss_fn(cfg: RecsysModelConfig, parallel: ParallelConfig,
                      mesh: Optional[Mesh] = None, *, temperature: float = 0.05):
    from .hstu import sequence_infonce

    def loss_fn(dense_params, emb, mb):
        if mesh is not None:
            ba = parallel.batch_axes if len(parallel.batch_axes) > 1 else parallel.batch_axes[0]
            emb = jax.lax.with_sharding_constraint(
                emb, jax.sharding.NamedSharding(mesh, P(ba, None, None)))
        hidden = fuxi_forward(dense_params, cfg, emb)
        preds = hidden[:, :-1]
        targets = emb[:, 1:] @ dense_params["in_proj"]
        loss, acc = sequence_infonce(preds, targets, temperature)
        return loss, {"hitrate_inseq": acc}

    return loss_fn
