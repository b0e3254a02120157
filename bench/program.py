"""The benchmark's one contact with the program under test (``src/repro``).

It builds a ``Session`` through the normal path (``Session.from_arch``,
``mode="nestpipe"``: the DBP driver with the FWP window) from the
configuration's backbone module (``program_arch``), hands it the weights
and the master table made from the seed by :mod:`bench.reference`, feeds
it the backbone's batches through :mod:`bench.generator`, and reads back
what the comparison needs: each step's loss, the first gradient as the
optimizers hold it after one step, and each leaf's change after three.
"""
from __future__ import annotations

import contextlib
import os
import sys
from typing import Any, Dict, Iterator, Optional

import numpy as np

from . import generator, reference, spec
from .spec import ROOT, Cell


class ProgramMissing(RuntimeError):
    pass


def import_program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.api  # noqa: F401
    except ImportError as e:
        raise ProgramMissing(
            f"the program (package repro under {src}) cannot be imported: {e}"
        ) from None


def make_mesh(cell: Cell):
    import jax
    from jax.sharding import Mesh

    shape = tuple(cell.traffic["mesh"])
    if int(np.prod(shape)) != cell.chips:
        raise ValueError(f"traffic mesh {shape} does not hold {cell.chips} "
                         "chips")
    if cell.chips == 1:
        return None
    devices = np.asarray(jax.devices()[:cell.chips]).reshape(shape)
    return Mesh(devices, ("data", "model"))


def build_session(cell: Cell, seed: int):
    from repro.api import Session
    from repro.configs.base import OptimizerConfig

    mesh = make_mesh(cell)
    opt = dict(cell.config["optimizer"])
    arch = spec.backbone(cell.config).program_arch(cell.config, cell.chips,
                                                   cell.traffic)
    sess = Session.from_arch(
        arch, mode="nestpipe", global_batch=cell.global_batch,
        seq_len=cell.config["seq_len"], mesh=mesh,
        sparse_axes=("data", "model") if mesh is not None else None,
        n_micro=cell.traffic["fwp_microbatches"],
        store=cell.traffic["store"], opt_cfg=OptimizerConfig(**opt),
        seed=0)
    eng = sess.workload.engine
    sopt = cell.config["sparse_optimizer"]
    if (eng.sparse_lr, eng.sparse_eps) != (sopt["lr"], sopt["eps"]):
        raise ValueError(
            f"the program's sparse optimizer (lr {eng.sparse_lr}, eps "
            f"{eng.sparse_eps}) is not the configuration's {sopt}")
    return sess


def _layout(sess) -> Dict[str, int]:
    s = sess.workload.spec
    return {"mult": s.mix_mult, "add": s.mix_add, "rows": s.padded_rows}


def _perms(sess, cell: Cell):
    """Master row of every row of every table (the program's layout)."""
    s = sess.workload.spec
    lay = _layout(sess)
    return [generator.scramble(np.arange(v, dtype=np.int64) + off, lay)
            for v, off in zip(s.table_vocabs, s.table_offsets)]


class Initial:
    """Makes the program's starting weights and master table from the seed,
    on the devices and shardings the program uses, each in one jitted call,
    and can make them again to measure how far training moved them."""

    def __init__(self, sess, cell: Cell, seed: int):
        import jax
        import jax.numpy as jnp

        self.sess, self.cell = sess, cell
        wl = sess.workload
        self.dense_key, self.table_key = reference.seed_keys(seed)
        self.fcfg = reference.Frozen(cell.config)
        want = jax.eval_shape(wl.bundle.init_params, jax.random.PRNGKey(0))
        got = spec.backbone(cell.config).param_shapes(cell.config,
                                                      cell.chips)
        want_s = jax.tree.map(lambda x: tuple(x.shape), want)
        if want_s != got:
            raise ValueError(
                f"the program's dense weights {want_s} are not the "
                f"reference's {got}")
        dense_sh = table_sh = None
        if wl.mesh is not None:
            sh = wl.state_shardings(sess.optimizer)
            dense_sh, table_sh = sh.dense, sh.table
        self._params = jax.jit(reference.init_params, static_argnums=(1, 2),
                               out_shardings=dense_sh)
        self._perms = [jnp.asarray(p) for p in _perms(sess, cell)]
        rows, dim = wl.spec.padded_rows, wl.spec.dim
        fcfg, chips = self.fcfg, cell.chips

        # the key and the layout are arguments, not constants, so that one
        # compiled program serves every seed
        def master(key, perms):
            from repro.core.embedding.table import EmbeddingTableState

            out = jnp.zeros((rows, dim), jnp.float32)
            for t, perm in enumerate(perms):
                out = out.at[perm].set(
                    reference.init_table(key, fcfg, chips, t))
            return EmbeddingTableState(out, jnp.zeros((rows,), jnp.float32))

        self._master = jax.jit(master, out_shardings=table_sh)

    def params(self):
        return self._params(self.dense_key, self.fcfg, self.cell.chips)

    def master(self):
        return self._master(self.table_key, self._perms)

    def state(self):
        import jax.numpy as jnp
        from repro.train.state import TrainState

        params = self.params()
        return TrainState(params, self.sess.optimizer.init(params),
                          self.master(), jnp.zeros((), jnp.int32))


@contextlib.contextmanager
def bench_stream(sess, cell: Cell, seed: int) -> Iterator[None]:
    """Feed the session the backbone's batches: ``Session.train`` takes its
    stream from ``repro.api.session.resolve_stream``, which this replaces
    for the duration (the program has no argument for a stream)."""
    import repro.api.session as session_mod

    bb = spec.backbone(cell.config)

    def draw(step):
        return bb.batch(seed, step, cell.config, cell.traffic, cell.chips)

    def resolve(wl, data_seed=0, *, start_step=0, **_):
        if wl is not sess.workload:
            raise RuntimeError("stream asked for another workload")
        return generator.program_stream(
            draw, start_step, layout=_layout(sess),
            item_offset=sess.workload.spec.table_offsets[0])

    saved = session_mod.resolve_stream
    session_mod.resolve_stream = resolve
    try:
        yield
    finally:
        session_mod.resolve_stream = saved


def _first_grads(mu, accum, *, b1, stacked, dim):
    """The first gradient's norms as the optimizers hold it after one step:
    Adam's first moment over ``1 - b1``, and the first table's rows' from
    the rowwise Adagrad accumulator (mean of squares per row)."""
    import jax.numpy as jnp

    g = {k: v / (1 - b1) for k, v in reference.leaf_norms(mu, stacked).items()}
    g["table"] = jnp.sqrt(dim * jnp.sum(accum))
    return g


def _changes(dense, dense0, rows, rows0, *, stacked):
    import jax
    import jax.numpy as jnp

    c = reference.leaf_norms(jax.tree.map(jnp.subtract, dense, dense0),
                             stacked)
    c["table"] = jnp.sqrt(jnp.sum(jnp.square(rows - rows0)))
    return c


def check_steps(sess, cell: Cell, init: Initial) -> Dict[str, Any]:
    """Three steps through ``Session.train`` (one call of one step, one of
    two), read as the reference's numbers are (``reference.train``).
    Returns them with each call's wall seconds."""
    import time

    import jax

    c = cell.config
    stacked = spec.backbone(c).stacked(c)
    first_grads = jax.jit(_first_grads, static_argnames=("b1", "stacked",
                                                          "dim"))
    changes = jax.jit(_changes, static_argnames=("stacked",))
    t0 = time.perf_counter()
    rep1 = sess.train(1)
    st = sess.state
    grads = {k: float(v) for k, v in first_grads(
        st.opt.mu, st.table.accum, b1=c["optimizer"]["beta1"],
        stacked=stacked, dim=sess.workload.spec.dim).items()}
    t1 = time.perf_counter()
    rep2 = sess.train(2)
    jax.block_until_ready(sess.state)
    t2 = time.perf_counter()
    st = sess.state
    m0 = init.master()
    change = {k: float(v) for k, v in changes(
        st.dense, init.params(), st.table.rows, m0.rows,
        stacked=stacked).items()}
    del m0
    return {
        "losses": list(rep1.stats.losses) + list(rep2.stats.losses),
        "grad_norms": grads, "change_norms": change,
        "overflow": max(rep1.stats.overflow_max, rep2.stats.overflow_max),
        "wall_s": [t1 - t0, t2 - t1],
    }


def store_timers(stats) -> Optional[Dict[str, float]]:
    """The store's cumulative stage timers over one ``train`` call."""
    m = stats.store_metrics
    return {k: float(m[k]) for k in ("plan_ms", "retrieve_ms", "commit_ms",
                                     "h2d_ms") if k in m} or None
