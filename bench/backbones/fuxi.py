"""FuXi-alpha (Ye et al. 2025) in the plain float32 reference, as far as
the program reproduces it.

Causal softmax self-attention with rotary positions, then the multi-stage
feed-forward ``v <- v * sigmoid(v0 W_k) + v`` over ``fi_orders`` orders,
each half behind an RMS norm. Batches, program configuration, stacking and
the InfoNCE loss: :mod:`_seqrec`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.backbones import _seqrec
from bench.reference import rmsnorm, rope

batch = _seqrec.batch
program_arch = _seqrec.program_arch
stacked = _seqrec.stacked


def param_shapes(cfg, chips):
    """The dense weights' shapes, by name (stacked over layers)."""
    d, nl, f = cfg["d_model"], cfg["n_layers"], cfg["d_ff"]
    demb = max(t["dim"] for t in cfg["tables"])
    layer = {
        "norm1": {"scale": (nl, d)},
        "attn": {"wq": (nl, d, d), "wk": (nl, d, d), "wv": (nl, d, d),
                 "wo": (nl, d, d)},
        "norm2": {"scale": (nl, d)},
        "w_up": (nl, d, f), "w_down": (nl, f, d),
    }
    for o in range(cfg["fi_orders"]):
        layer[f"w_fi{o}"] = (nl, f, f)
    return {"layers": layer, "in_proj": (demb, d),
            "final_norm": {"scale": (d,)}}


def _layer(p, x, cfg, ein):
    b, s, d = x.shape
    h = cfg["n_heads"]
    hd = d // h
    eps = cfg["norm_eps"]
    a = p["attn"]
    n = rmsnorm(p["norm1"], x, eps)
    q, k, v = (ein("bsd,de->bse", n, a[w]).reshape(b, s, h, hd)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    scores = ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = ein("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    x = x + ein("bsd,de->bse", o, a["wo"])
    n = rmsnorm(p["norm2"], x, eps)
    v0 = ein("bsd,df->bsf", n, p["w_up"])
    v = v0
    for o in range(cfg["fi_orders"]):
        v = v * jax.nn.sigmoid(ein("bsf,fg->bsg", v0, p[f"w_fi{o}"])) + v
    return x + ein("bsf,fd->bsd", v, p["w_down"])


def losses(params, emb, fields, cfg, ein):
    return _seqrec.infonce_losses(params, emb, cfg, ein, _layer, rmsnorm)
