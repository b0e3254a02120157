"""HSTU (Zhai et al. 2024) in the plain float32 reference.

``U, V, Q, K = split(silu(LN(x) W_uvqk))``,
``A = silu(Q K^T / sqrt(d_qk)) * causal / n``, ``x + (LN(A V) * U) W_o``.
No relative attention bias (not in the program either). Batches, program
configuration, stacking and the InfoNCE loss: :mod:`_seqrec`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.backbones import _seqrec
from bench.reference import layernorm

batch = _seqrec.batch
program_arch = _seqrec.program_arch
stacked = _seqrec.stacked


def param_shapes(cfg, chips):
    """The dense weights' shapes, by name (stacked over layers)."""
    d, h, nl = cfg["d_model"], cfg["n_heads"], cfg["n_layers"]
    demb = max(t["dim"] for t in cfg["tables"])
    dh = d // h
    ln = lambda n: {"scale": (nl, n), "bias": (nl, n)}  # noqa: E731
    return {
        "layers": {"norm": ln(d), "w_uvqk": (nl, d, 4 * h * dh),
                   "w_o": (nl, h * dh, d), "out_norm": ln(h * dh)},
        "in_proj": (demb, d),
        "final_norm": {"scale": (d,), "bias": (d,)},
    }


def _layer(p, x, cfg, ein):
    b, s, d = x.shape
    h = cfg["n_heads"]
    dh = d // h
    eps = cfg["norm_eps"]
    mixed = jax.nn.silu(ein("bsd,de->bse", layernorm(p["norm"], x, eps),
                            p["w_uvqk"])).reshape(b, s, h, 4 * dh)
    u, v, q, k = (mixed[..., i * dh:(i + 1) * dh] for i in range(4))
    scores = ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    a = jnp.where(causal, jax.nn.silu(scores), 0.0) / s
    y = ein("bhqk,bkhd->bqhd", a, v).reshape(b, s, d)
    y = layernorm(p["out_norm"], y, eps) * u.reshape(b, s, d)
    return x + ein("bsd,de->bse", y, p["w_o"])


def losses(params, emb, fields, cfg, ein):
    return _seqrec.infonce_losses(params, emb, cfg, ein, _layer, layernorm)
