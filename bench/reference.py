"""Plain float32 reference of the benchmark's training cells.

Independent of the program: it imports nothing from ``repro``. From the
configuration file and the seed it makes the dense weights and the item
table, draws the batches with :mod:`bench.generator`, and trains serially:
the whole batch's mean loss, its gradients computed in blocks of sequences,
AdamW with global-norm clipping on the dense weights and rowwise Adagrad on
the item rows that the batch touched. Matrix products run at
``Precision.HIGHEST`` (``precision="highest"``).

The backbones follow the published layer equations as the configuration
file's ``backbone`` names them:

- ``hstu`` (Zhai et al. 2024): ``U, V, Q, K = split(silu(LN(x) W_uvqk))``,
  ``A = silu(Q K^T / sqrt(d_qk)) * causal / n``,
  ``x + (LN(A V) * U) W_o``. No relative attention bias (not in the
  program either).
- ``fuxi`` (Ye et al. 2025), as far as the program reproduces it: causal
  softmax self-attention with rotary positions, then the multi-stage
  feed-forward ``v <- v * sigmoid(v0 W_k) + v`` over three orders.

Both train on next-item InfoNCE over each sequence's own items
(temperature from the configuration), with the item embeddings as the
targets' input as well.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, one scale per tensor) first. ``keep`` leaves the
batch's tail out and takes the mean over the rest, and ``alter`` serves
the first eighth of the batch the wrong rows, each id's next row, as a
gather with a wrong index would (planted faults).

The same weights are what the benchmark hands the program
(:func:`init_params`, :func:`init_tables`), so both start from one state.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import generator

HIGHEST = jax.lax.Precision.HIGHEST
PyTree = Any


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def seed_keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    """(dense key, table key) of a seed of any size."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.PRNGKey(int(a)), jax.random.PRNGKey(int(b))


def param_shapes(cfg: Dict[str, Any]) -> PyTree:
    """The dense weights' shapes, by name (stacked over layers)."""
    d, h, nl = cfg["d_model"], cfg["n_heads"], cfg["n_layers"]
    demb = max(t["dim"] for t in cfg["tables"])
    if cfg["backbone"] == "hstu":
        dh = d // h
        ln = lambda n: {"scale": (nl, n), "bias": (nl, n)}  # noqa: E731
        return {
            "layers": {"norm": ln(d), "w_uvqk": (nl, d, 4 * h * dh),
                       "w_o": (nl, h * dh, d), "out_norm": ln(h * dh)},
            "in_proj": (demb, d),
            "final_norm": {"scale": (d,), "bias": (d,)},
        }
    if cfg["backbone"] == "fuxi":
        f = cfg["d_ff"]
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
    raise ValueError(f"no reference for backbone {cfg['backbone']!r}")


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(key: jax.Array, cfg: Dict[str, Any]) -> PyTree:
    """Norm scales 1, biases 0, ``in_proj`` N(0, in_proj_scale^2), other
    matrices N(0, 1/fan_in). Jit it: one call makes every leaf on device."""
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = jax.tree_util.keystr(path)
        if "'scale'" in name:
            out.append(jnp.ones(shape, jnp.float32))
        elif "'bias'" in name:
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            std = cfg["init_scale"]["in_proj"] if "in_proj" in name \
                else shape[-2] ** -0.5
            out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree_util.tree_unflatten(tree, out)


def table_rows(cfg: Dict[str, Any], chips: int) -> List[int]:
    """Rows of each table on ``chips`` chips (the file gives one chip's)."""
    return [t["vocab_size"] * chips for t in cfg["tables"]]


def init_table(key: jax.Array, cfg: Dict[str, Any], chips: int,
               index: int) -> jax.Array:
    """Table ``index``: N(0, table_scale^2) rows."""
    rows = table_rows(cfg, chips)[index]
    dim = cfg["tables"][index]["dim"]
    return jax.random.normal(jax.random.fold_in(key, index), (rows, dim),
                             jnp.float32) * cfg["init_scale"]["table"]


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(precision: str):
    def ein(spec, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _hstu_layer(p, x, cfg, ein):
    b, s, d = x.shape
    h = cfg["n_heads"]
    dh = d // h
    eps = cfg["norm_eps"]
    mixed = jax.nn.silu(ein("bsd,de->bse", _layernorm(p["norm"], x, eps),
                            p["w_uvqk"])).reshape(b, s, h, 4 * dh)
    u, v, q, k = (mixed[..., i * dh:(i + 1) * dh] for i in range(4))
    scores = ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    a = jnp.where(causal, jax.nn.silu(scores), 0.0) / s
    y = ein("bhqk,bkhd->bqhd", a, v).reshape(b, s, d)
    y = _layernorm(p["out_norm"], y, eps) * u.reshape(b, s, d)
    return x + ein("bsd,de->bse", y, p["w_o"])


def _rope(x, theta):
    """Rotary positions on (b, s, h, hd), halves rotated as pairs."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(s)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fuxi_layer(p, x, cfg, ein):
    b, s, d = x.shape
    h = cfg["n_heads"]
    hd = d // h
    eps = cfg["norm_eps"]
    a = p["attn"]
    n = _rmsnorm(p["norm1"], x, eps)
    q, k, v = (ein("bsd,de->bse", n, a[w]).reshape(b, s, h, hd)
               for w in ("wq", "wk", "wv"))
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    scores = ein("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = ein("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    x = x + ein("bsd,de->bse", o, a["wo"])
    n = _rmsnorm(p["norm2"], x, eps)
    v0 = ein("bsd,df->bsf", n, p["w_up"])
    v = v0
    for o in range(cfg["fi_orders"]):
        v = v * jax.nn.sigmoid(ein("bsf,fg->bsg", v0, p[f"w_fi{o}"])) + v
    return x + ein("bsf,fd->bsd", v, p["w_down"])


_LAYERS = {"hstu": (_hstu_layer, _layernorm), "fuxi": (_fuxi_layer, _rmsnorm)}


def sequence_losses(params, emb, cfg, precision: str = "highest"):
    """Per-sequence next-item InfoNCE of item embeddings ``emb`` (b, s, D)."""
    ein = _einsum(precision)
    layer, final_norm = _LAYERS[cfg["backbone"]]
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


# ---------------------------------------------------------------------------
# one training step
# ---------------------------------------------------------------------------


class Frozen:
    """A configuration dict usable as a jit static argument."""

    def __init__(self, cfg: Dict[str, Any]):
        self.cfg = cfg
        self._key = json.dumps(cfg, sort_keys=True)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Frozen) and self._key == other._key

    def __getitem__(self, k):
        return self.cfg[k]


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree_util.tree_leaves(tree)))


@functools.partial(jax.jit, static_argnames=("cfg", "block", "precision",
                                             "mesh", "alter"),
                   donate_argnums=(0, 1, 2, 3, 4))
def _step(params, mu, nu, items, accum, ids, count, *, cfg, block,
          precision, mesh, alter=False):
    opt, sopt = cfg["optimizer"], cfg["sparse_optimizer"]
    batch, seq = ids.shape
    emb = items[ids]  # (B, S, D)
    if alter:  # an eighth of the batch served the next row of each id
        k = max(1, batch // 8)
        emb = emb.at[:k].set(items[(ids[:k] + 1) % items.shape[0]])

    def block_loss(p, e):
        return jnp.sum(sequence_losses(p, e, cfg, precision))

    grad = jax.value_and_grad(block_loss, argnums=(0, 1))

    def body(acc, e):
        loss, (gp, ge) = grad(params, e)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], gp)), ge

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    blocks = emb.reshape(batch // block, block, seq, -1)
    if mesh is not None:  # each block's sequences split over the chips
        blocks = jax.lax.with_sharding_constraint(
            blocks, NamedSharding(mesh, P(None, "b")))
    (loss_sum, gsum), demb = jax.lax.scan(body, zero, blocks)
    loss = loss_sum / batch
    grads = jax.tree.map(lambda g: g / batch, gsum)

    # rowwise Adagrad on the rows the batch touched
    total = jnp.zeros_like(items).at[ids.reshape(-1)].add(
        demb.reshape(batch * seq, -1) / batch)
    touched = jnp.any(total != 0.0, axis=-1)
    accum = accum + jnp.where(touched, jnp.mean(total * total, -1), 0.0)
    scale = sopt["lr"] / (jnp.sqrt(accum) + sopt["eps"])
    items = items - jnp.where(touched, scale, 0.0)[:, None] * total

    # AdamW with global-norm clipping
    gnorm = _global_norm(grads)
    if opt["grad_clip"] > 0:
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-12)),
            grads)
    b1, b2 = opt["beta1"], opt["beta2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                      + opt["eps"])
                                         + opt["weight_decay"] * p),
        params, mu, nu)
    return params, mu, nu, items, accum, loss, grads, jnp.sqrt(
        jnp.sum(total * total))


def leaf_norms(tree: PyTree, n_layers: int) -> Dict[str, jax.Array]:
    """Norm of each leaf, the stacked layer leaves split per layer."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.startswith("layers/"):
            for i in range(n_layers):
                out[f"{name}[{i}]"] = jnp.sqrt(jnp.sum(jnp.square(
                    x[i].astype(jnp.float32))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return out


def train(seed: int, cfg: Dict[str, Any], traffic_cfg: Dict[str, Any],
          chips: int, *, steps: int, block: int, precision: str = "highest",
          keep: int = 0, alter: bool = False) -> Dict[str, Any]:
    """Train ``steps`` steps from the seed; returns the numbers the
    benchmark compares: each step's loss, the first step's gradient norms
    as the optimizers get them (``"table"`` is the item rows' gradient) and
    the norms of each leaf's change over the ``steps`` steps."""
    dense_key, table_key = seed_keys(seed)
    batch = traffic_cfg["batch_per_chip"] * chips
    keep = keep or batch
    seq = cfg["seq_len"]
    n_items = table_rows(cfg, chips)[0]
    fcfg = Frozen(cfg)
    # on several chips every chip holds the whole state and computes its
    # share of each block of sequences
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("b",)) \
        if chips > 1 else None
    rep = NamedSharding(mesh, P()) if mesh is not None else None
    make_params = jax.jit(init_params, static_argnums=1, out_shardings=rep)
    make_items = jax.jit(init_table, static_argnums=(1, 2, 3),
                         out_shardings=rep)
    params0 = make_params(dense_key, fcfg)
    params = jax.tree.map(jnp.copy, params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    items = make_items(table_key, fcfg, chips, 0)
    accum = jax.device_put(jnp.zeros((n_items,), jnp.float32), rep)
    losses, grads0, table_g0 = [], None, None
    for t in range(steps):
        ids = generator.item_batch(
            seed, t, batch=batch, seq_len=seq, n_items=n_items,
            zipf_a=traffic_cfg["zipf_a"])[:keep]
        params, mu, nu, items, accum, loss, grads, tnorm = _step(
            params, mu, nu, items, accum,
            jax.device_put(jnp.asarray(ids, jnp.int32), rep),
            jnp.float32(t + 1), cfg=fcfg, block=min(block, keep),
            precision=precision, mesh=mesh, alter=alter)
        losses.append(float(loss))
        if t == 0:
            grads0 = {k: float(v) for k, v in
                      leaf_norms(grads, cfg["n_layers"]).items()}
            table_g0 = float(tnorm)
    change = {k: float(v) for k, v in leaf_norms(
        jax.tree.map(jnp.subtract, params, params0),
        cfg["n_layers"]).items()}
    items0 = make_items(table_key, fcfg, chips, 0)
    change["table"] = float(jnp.sqrt(jnp.sum(jnp.square(items - items0))))
    grads0["table"] = table_g0
    return {"losses": losses, "grad_norms": grads0, "change_norms": change}
