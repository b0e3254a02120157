"""A tiny dense decoder in the plain float32 reference: test data for the
harness (``bench/test_backbones.py``), not a backbone of the benchmark.

It writes out the program's ``lm`` kind (``models/transformer.py``,
``models/layers.py``) for a stack of ``(attn, mlp)`` blocks: each block is
``x + Attn(RMS(x))`` then ``x + MLP(RMS(x))``, with grouped-query
attention under rotary positions (causal softmax) and a SwiGLU MLP
``(silu(x W_g) * x W_i) W_o``; a final RMS norm, ``head_w`` over the
table's rows, and the mean cross-entropy of each sequence's ``labels``
(the next tokens). The blocks are stacked under ``blocks`` (one pattern
position) as the program stacks them.

Where it departs from the program, each at float32 rounding: the rotary
angles are computed in float64 on the host (the program: float32 on the
device); the softmax takes each query's whole row of scores (the program:
key chunks under a running maximum); the cross-entropy is a
``log_softmax`` (the program: a log-sum-exp over chunks of positions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, reference
from bench.reference import rmsnorm, rope


def _rows(cfg, chips):
    return reference.table_rows(cfg, chips)[0]


def param_shapes(cfg, chips):
    d, f, n = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    block = {
        "norm1": {"scale": (n, d)},
        "attn": {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
                 "wo": (n, q, d)},
        "norm2": {"scale": (n, d)},
        "mlp": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)},
    }
    return {"blocks": [block], "final_norm": {"scale": (d,)},
            "head_w": (d, _rows(cfg, chips))}


def batch(seed, step, cfg, traffic, chips):
    """``seq_len + 1`` zipf tokens per sequence: the first ``seq_len`` are
    the ids, the last ``seq_len`` the labels."""
    rng = np.random.default_rng([int(seed), int(step), 0x70C])
    toks = generator.zipf(
        rng, _rows(cfg, chips),
        (traffic["batch_per_chip"] * chips, cfg["seq_len"] + 1),
        traffic["zipf_a"])
    return {"ids": toks[:, :-1], "labels": toks[:, 1:].astype(np.int32)}


def program_arch(cfg, chips, traffic):
    from repro.configs.base import AttentionConfig, ModelConfig
    from repro.configs.registry import ArchSpec

    model = ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], d_ff=cfg["d_ff"],
        vocab_size=_rows(cfg, chips),
        attention=AttentionConfig(cfg["n_heads"], cfg["n_kv_heads"],
                                  cfg["head_dim"],
                                  rope_theta=cfg["rope_theta"]),
        mlp_type="swiglu", activation="silu", norm_type="rmsnorm",
        norm_eps=cfg["norm_eps"], param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"])
    return ArchSpec(cfg["name"], "lm", model, model)


def stacked(cfg):
    return (("blocks", cfg["n_layers"]),)


def _block(p, x, cfg, ein):
    b, s, _ = x.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps = cfg["norm_eps"]
    a = p["attn"]
    n = rmsnorm(p["norm1"], x, eps)
    q = ein("bsd,de->bse", n, a["wq"]).reshape(b, s, h, hd)
    k = ein("bsd,de->bse", n, a["wk"]).reshape(b, s, kv, hd)
    v = ein("bsd,de->bse", n, a["wv"]).reshape(b, s, kv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    # query head i reads key and value head i // (h // kv)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    scores = ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = ein("bhqk,bkhd->bqhd", w, v).reshape(b, s, h * hd)
    x = x + ein("bse,ed->bsd", o, a["wo"])
    n = rmsnorm(p["norm2"], x, eps)
    m = p["mlp"]
    g = jax.nn.silu(ein("bsd,df->bsf", n, m["wg"])) \
        * ein("bsd,df->bsf", n, m["wi"])
    return x + ein("bsf,fd->bsd", g, m["wo"])


def losses(params, emb, fields, cfg, ein):
    x = emb
    step = jax.checkpoint(lambda p, x: _block(p, x, cfg, ein))
    for i in range(cfg["n_layers"]):
        x = step(jax.tree.map(lambda w: w[i], params["blocks"][0]), x)
    hidden = rmsnorm(params["final_norm"], x, cfg["norm_eps"])
    logp = jax.nn.log_softmax(
        ein("bsd,dv->bsv", hidden, params["head_w"]), axis=-1)
    nll = -jnp.take_along_axis(logp, fields["labels"][..., None], axis=-1)
    return jnp.mean(nll[..., 0], axis=-1)
