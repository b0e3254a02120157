"""Plain float32 reference of the benchmark's training cells.

Independent of the program: it imports nothing from ``repro``. From the
configuration file and the seed it makes the dense weights and the tables,
draws the batches, and trains serially: the whole batch's mean loss, its
gradients computed in blocks of sequences, AdamW with global-norm clipping
on the dense weights and rowwise Adagrad on the rows of the first table
that the batch touched. Matrix products run at ``Precision.HIGHEST``
(``precision="highest"``).

What belongs to one model family (its weights' shapes, its layer
equations and loss, its batches and the split of its stacked leaves) is in
the configuration's ``bench/backbones/<backbone>.py``
(:func:`bench.spec.backbone`); this module holds what every backbone
shares.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, one scale per tensor) first. ``keep`` leaves the
batch's tail out and takes the mean over the rest, and ``alter`` serves
the first eighth of the batch the wrong rows, each id's next row, as a
gather with a wrong index would (planted faults).

The same weights are what the benchmark hands the program
(:func:`init_params`, :func:`init_table`), so both start from one state.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import spec

HIGHEST = jax.lax.Precision.HIGHEST
PyTree = Any


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def seed_keys(seed: int) -> Tuple[jax.Array, jax.Array]:
    """(dense key, table key) of a seed of any size."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.PRNGKey(int(a)), jax.random.PRNGKey(int(b))


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init_params(key: jax.Array, cfg: Dict[str, Any], chips: int) -> PyTree:
    """The backbone's own ``init_params`` where it has one; else norm scales
    1, biases 0, ``in_proj`` N(0, in_proj_scale^2), other matrices
    N(0, 1/fan_in). Jit it: one call makes every leaf on device."""
    bb = spec.backbone(cfg)
    if hasattr(bb, "init_params"):
        return bb.init_params(key, cfg, chips)
    shapes = bb.param_shapes(cfg, chips)
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
# building blocks of the backbones
# ---------------------------------------------------------------------------


def _q8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(precision: str):
    def ein(eq, a, b):
        if precision == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return ein


def layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def rope(x, theta):
    """Rotary positions on (b, s, h, hd), halves rotated as pairs."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(s)[:, None] * freqs[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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
def _step(params, mu, nu, items, accum, ids, fields, count, *, cfg, block,
          precision, mesh, alter=False):
    opt, sopt = cfg["optimizer"], cfg["sparse_optimizer"]
    losses = spec.backbone(cfg).losses
    ein = _einsum(precision)
    batch, seq = ids.shape
    emb = items[ids]  # (B, S, D)
    if alter:  # an eighth of the batch served the next row of each id
        k = max(1, batch // 8)
        emb = emb.at[:k].set(items[(ids[:k] + 1) % items.shape[0]])

    def block_loss(p, e, f):
        return jnp.sum(losses(p, e, f, cfg, ein))

    grad = jax.value_and_grad(block_loss, argnums=(0, 1))

    def body(acc, xs):
        loss, (gp, ge) = grad(params, *xs)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], gp)), ge

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    # the embeddings and the batch's further fields, in blocks of sequences
    blocks = jax.tree.map(
        lambda x: x.reshape((batch // block, block) + x.shape[1:]),
        (emb, fields))
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


def leaf_norms(tree: PyTree, stacked) -> Dict[str, jax.Array]:
    """Norm of each leaf; a leaf under a ``(prefix, count)`` pair of
    ``stacked`` (the backbone's ``stacked(cfg)``) is split along its first
    axis into ``count`` layers."""
    counts = dict(stacked)
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        n = counts.get(name.split("/", 1)[0])
        if n is not None:
            for i in range(n):
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
    as the optimizers get it (``"table"`` is the first table's rows'
    gradient) and the norms of each leaf's change over the ``steps``
    steps."""
    bb = spec.backbone(cfg)
    stacked = bb.stacked(cfg)
    dense_key, table_key = seed_keys(seed)
    batch = traffic_cfg["batch_per_chip"] * chips
    keep = keep or batch
    n_items = table_rows(cfg, chips)[0]
    fcfg = Frozen(cfg)
    # on several chips every chip holds the whole state and computes its
    # share of each block of sequences
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("b",)) \
        if chips > 1 else None
    rep = NamedSharding(mesh, P()) if mesh is not None else None
    make_params = jax.jit(init_params, static_argnums=(1, 2),
                          out_shardings=rep)
    make_items = jax.jit(init_table, static_argnums=(1, 2, 3),
                         out_shardings=rep)
    params0 = make_params(dense_key, fcfg, chips)
    params = jax.tree.map(jnp.copy, params0)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    items = make_items(table_key, fcfg, chips, 0)
    accum = jax.device_put(jnp.zeros((n_items,), jnp.float32), rep)
    losses, grads0, table_g0 = [], None, None
    for t in range(steps):
        fields = dict(bb.batch(seed, t, cfg, traffic_cfg, chips))
        ids = jax.device_put(jnp.asarray(fields.pop("ids")[:keep],
                                         jnp.int32), rep)
        fields = {k: jax.device_put(jnp.asarray(v[:keep]), rep)
                  for k, v in fields.items()}
        params, mu, nu, items, accum, loss, grads, tnorm = _step(
            params, mu, nu, items, accum, ids, fields,
            jnp.float32(t + 1), cfg=fcfg, block=min(block, keep),
            precision=precision, mesh=mesh, alter=alter)
        losses.append(float(loss))
        if t == 0:
            grads0 = {k: float(v) for k, v in
                      leaf_norms(grads, stacked).items()}
            table_g0 = float(tnorm)
    change = {k: float(v) for k, v in leaf_norms(
        jax.tree.map(jnp.subtract, params, params0), stacked).items()}
    items0 = make_items(table_key, fcfg, chips, 0)
    change["table"] = float(jnp.sqrt(jnp.sum(jnp.square(items - items0))))
    grads0["table"] = table_g0
    return {"losses": losses, "grad_norms": grads0, "change_norms": change}
