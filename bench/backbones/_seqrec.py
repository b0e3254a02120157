"""What the item-sequence backbones (``hstu``, ``fuxi``) share.

Not a backbone of its own: each of them takes from here its batches (zipf
item sequences of :func:`bench.generator.item_batch`, ``ids`` alone), its
program configuration (a ``RecsysModelConfig``), its stacking (every layer
under ``layers``) and its loss: next-item InfoNCE over each sequence's own
items (temperature from the configuration), with the item embeddings as the
targets' input as well.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import generator, reference


def batch(seed: int, step: int, cfg: Dict[str, Any],
          traffic: Dict[str, Any], chips: int) -> Dict[str, Any]:
    return {"ids": generator.item_batch(
        seed, step, batch=traffic["batch_per_chip"] * chips,
        seq_len=cfg["seq_len"], n_items=reference.table_rows(cfg, chips)[0],
        zipf_a=traffic["zipf_a"])}


def program_arch(cfg: Dict[str, Any], chips: int, traffic: Dict[str, Any]):
    """The configuration as the program's ``ArchSpec``, with each table
    holding ``chips`` chips' share of its rows."""
    from repro.configs.base import RecsysModelConfig, SparseTableConfig
    from repro.configs.registry import ArchSpec

    rows = reference.table_rows(cfg, chips)
    tables = tuple(SparseTableConfig(t["name"], vocab_size=r, dim=t["dim"])
                   for t, r in zip(cfg["tables"], rows))
    model = RecsysModelConfig(
        name=cfg["name"], backbone=cfg["backbone"], tables=tables,
        d_model=cfg["d_model"], n_layers=cfg["n_layers"],
        n_heads=cfg["n_heads"], d_ff=cfg["d_ff"], seq_len=cfg["seq_len"],
        norm_eps=cfg["norm_eps"], param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"], zipf_a=traffic["zipf_a"])
    return ArchSpec(cfg["name"], "recsys", model, model)


def stacked(cfg: Dict[str, Any]):
    return (("layers", cfg["n_layers"]),)


def infonce_losses(params, emb, cfg, ein, layer, final_norm):
    """Per-sequence next-item InfoNCE of item embeddings ``emb`` (b, s, D)
    through ``layer(p, x, cfg, ein)`` per layer and ``final_norm``."""
    x = ein("bse,ed->bsd", emb, params["in_proj"])
    step = jax.checkpoint(lambda p, x: layer(p, x, cfg, ein))
    for i in range(cfg["n_layers"]):
        x = step(jax.tree.map(lambda w: w[i], params["layers"]), x)
    hidden = final_norm(params["final_norm"], x, cfg["norm_eps"])
    preds = hidden[:, :-1]
    targets = ein("bse,ed->bsd", emb[:, 1:], params["in_proj"])
    pf = preds / (jnp.linalg.norm(preds, axis=-1, keepdims=True) + 1e-6)
    tf = targets / (jnp.linalg.norm(targets, axis=-1, keepdims=True) + 1e-6)
    logits = ein("bqd,bkd->bqk", pf, tf) / cfg["temperature"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.diagonal(logp, axis1=1, axis2=2), axis=-1)
