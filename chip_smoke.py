"""Chip smoke test: NestPipe training and serving at hstu-industrial widths.

    python chip_smoke.py               # one TPU chip (what a deployment chip runs)
    python chip_smoke.py --four-chips  # a 2x2 TPU host: sharded stores, 2D grid

One process, no children. Any platform other than TPU is refused: the
script never falls back to the CPU (the CPU path is the test suite, run
under ``JAX_PLATFORMS=cpu``). Compiled programs go to JAX's persistent
cache (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``).

Configuration: hstu-industrial (``configs/recsys_archs.py``) at its
published widths (emb dim 512, d_model 1024, 4 layers, 8 heads, d_ff 4096,
seq_len 1024, bf16 compute). Each table is cut to the given number of
chips' share of the 256-worker production mesh (``launch/mesh.py``): one
chip holds items 390,625, users 195,313 and context 3,907 rows. Weights are
random from seed 0; data is the repo's synthetic zipf stream.

One-chip phases, each a fresh ``Session`` from the same seed:

0. kernels: each dispatched op under ``backend="pallas"`` against
   ``"reference"`` on the same inputs at the engine's capacities;
a. nestpipe training (DBP + FWP, device store, default kernel backend),
   starting at the deployment's per-chip batch of 256 sequences and
   halving until the step fits in device memory;
b. the same steps with ``kernel_backend="reference"``: losses within
   ``LOSS_TOL`` of (a);
c. the same steps with the host store (DRAM master, H2D staging): losses
   equal to (a) bit for bit;
d. serving the table trained in (a) with ``check_exact``: ``exact=1``.

``--four-chips`` runs only nestpipe on a (1, 4) mesh (device store vs the
sharded host store) and on a (2, 2) grid with ``sparse_axes=("data",
"model")``; all three loss trajectories must be bit-equal.

The last line printed is ``{"ok": true, "device": {...}}``; a failing phase
exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PRODUCTION_WORKERS = 256  # launch/mesh.py: the (16, 16) single-pod mesh
DEPLOYMENT_BATCH = 256  # per-chip share of launch/build.RECSYS_TRAIN_SHAPE
WARMUP_STEPS = 2
TIMED_STEPS = 3
LOSS_TOL = 1e-3  # |loss(pallas) - loss(reference)| per step
SEGSUM_TOL = 1e-5  # max |pallas - reference| on N(0, 1) rows


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def chip_share_arch(chips: int):
    """hstu-industrial with every table cut to ``chips`` chips' share of
    the production mesh; widths untouched."""
    from repro.configs.recsys_archs import HSTU_INDUSTRIAL
    from repro.configs.registry import ArchSpec
    from repro.utils import cdiv

    tables = tuple(
        dataclasses.replace(
            t, vocab_size=cdiv(t.vocab_size * chips, PRODUCTION_WORKERS))
        for t in HSTU_INDUSTRIAL.tables)
    cfg = dataclasses.replace(HSTU_INDUSTRIAL, tables=tables)
    return ArchSpec(f"{HSTU_INDUSTRIAL.name}-share{chips}", "recsys", cfg, cfg)


def is_oom(e: Exception) -> bool:
    text = str(e)
    return "RESOURCE_EXHAUSTED" in text or "out of memory" in text.lower()


def session(arch, batch, *, backend="auto", store="device", mesh=None,
            sparse_axes=None, seed=0):
    from repro.api import Session
    from repro.configs.base import NestPipeConfig

    return Session.from_arch(
        arch, mode="nestpipe", global_batch=batch,
        seq_len=arch.config.seq_len, mesh=mesh, sparse_axes=sparse_axes,
        npcfg=NestPipeConfig(kernel_backend=backend), store=store, seed=seed,
        metrics_every=1)


def train(sess):
    """One run of warm-up + timed steps ending in ``block_until_ready``;
    returns (losses, per-step seconds, wall seconds of the run). Metrics
    drain every step, so each step time is host clock between the
    completions of consecutive steps; the first ``WARMUP_STEPS`` include
    compilation."""
    import jax

    t0 = time.perf_counter()
    rep = sess.train(WARMUP_STEPS + TIMED_STEPS)
    jax.block_until_ready(rep.state)
    wall = time.perf_counter() - t0
    check(rep.stats.overflow_max == 0,
          f"routing overflow {rep.stats.overflow_max}")
    losses = rep.stats.losses
    check(len(losses) == WARMUP_STEPS + TIMED_STEPS, f"losses {losses}")
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"non-finite loss in {losses}")
    return losses, rep.stats.step_times, wall


def train_fitting(tag, batch, min_batch, make):
    """``train`` a fresh ``make(batch)`` session, halving ``batch`` (down to
    ``min_batch``) while the step does not fit in device memory; returns
    (session, batch, what ``train`` returned)."""
    while True:
        sess = make(batch)
        try:
            return sess, batch, train(sess)
        except Exception as e:  # noqa: BLE001 — only device OOM is retried
            if not is_oom(e) or batch <= min_batch:
                raise
            log(f"{tag} batch={batch} does not fit: {str(e).splitlines()[0]}")
        del sess
        gc.collect()
        batch //= 2


def report_run(tag, sess, losses, step_times, wall):
    log(f"{tag}: kernel_backend={sess.workload.engine.kernel_backend} "
        f"store={sess.workload.npcfg.store} "
        f"batch={sess.workload.shape.global_batch}")
    log(f"{tag}: losses={losses!r}")
    log(f"{tag}: step_s={step_times!r} (first {WARMUP_STEPS} warm-up); "
        f"timed mean {sum(step_times[WARMUP_STEPS:]) / TIMED_STEPS!r} s; "
        f"run wall {wall!r} s")


# ---------------------------------------------------------------------------
# phase 0: dispatched kernels, pallas vs reference
# ---------------------------------------------------------------------------


def kernel_phase(rows: int, dim: int, n_idx: int, buf_rows: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch

    def diff(op, *args):
        got = op(*args, backend="pallas")
        want = op(*args, backend="reference")
        check(got.shape == want.shape and got.dtype == want.dtype,
              f"{op.__name__}: {got.shape}/{got.dtype} vs "
              f"{want.shape}/{want.dtype}")
        return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))

    key = jax.random.PRNGKey(7)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    table = jax.random.normal(k1, (rows, dim), jnp.float32)
    idx = jax.random.randint(k2, (n_idx,), 0, rows + rows // 8)  # ~1/9 miss
    d = diff(dispatch.gather_rows, table, idx)
    log(f"kernel gather_rows f32 ({rows}x{dim}, n={n_idx}): max_abs_diff={d!r}")
    check(d == 0.0, "gather_rows f32 differs from reference")
    flat = table[:n_idx].astype(jnp.bfloat16)
    d = diff(dispatch.gather_rows, flat, idx % n_idx)
    log(f"kernel gather_rows bf16 ({n_idx}x{dim}): max_abs_diff={d!r}")
    check(d == 0.0, "gather_rows bf16 differs from reference")
    del table, flat

    vals = jax.random.normal(k3, (buf_rows, dim), jnp.float32)
    ids = jax.random.randint(k4, (buf_rows,), 0, buf_rows + buf_rows // 8)
    d = diff(dispatch.segment_rowsum, vals, ids, buf_rows)
    log(f"kernel segment_rowsum f32 N(0,1) (L=S={buf_rows}, D={dim}): "
        f"max_abs_diff={d!r} (tol {SEGSUM_TOL})")
    check(d <= SEGSUM_TOL, "segment_rowsum exceeds its tolerance")
    ints = jnp.round(vals * 4.0)
    d = diff(dispatch.segment_rowsum, ints, ids, buf_rows)
    log(f"kernel segment_rowsum f32 integer-valued: max_abs_diff={d!r}")
    check(d == 0.0, "segment_rowsum on integer rows differs from reference")
    demb = vals[: buf_rows // 4].astype(jnp.bfloat16)
    d = diff(dispatch.segment_rowsum, demb, ids[: buf_rows // 4] % (buf_rows // 4),
             buf_rows // 4)
    log(f"kernel segment_rowsum bf16 (L=S={buf_rows // 4}): max_abs_diff={d!r}"
        f" (tol {SEGSUM_TOL})")
    check(d <= SEGSUM_TOL, "segment_rowsum bf16 exceeds its tolerance")

    src = jnp.where(ids < buf_rows, ids, buf_rows)  # ~1/9 miss
    d = diff(dispatch.buffer_sync, vals, ints, src)
    log(f"kernel buffer_sync f32 ({buf_rows}x{dim}): max_abs_diff={d!r}")
    check(d == 0.0, "buffer_sync differs from reference")
    del vals, ints, demb, ids, src
    gc.collect()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def one_chip() -> None:
    import jax
    import numpy as np

    arch = chip_share_arch(1)
    cfg = arch.config
    log("config: " + ", ".join(
        f"{t.name}={t.vocab_size}x{t.dim}" for t in cfg.tables)
        + f"; d_model={cfg.d_model} layers={cfg.n_layers} heads={cfg.n_heads}"
        f" d_ff={cfg.d_ff} seq_len={cfg.seq_len} compute={cfg.compute_dtype}")

    rows = sum(t.vocab_size for t in cfg.tables)
    keys = DEPLOYMENT_BATCH // 4 * cfg.seq_len  # one micro-batch's positions
    kernel_phase(rows, cfg.max_table_dim, int(keys * 1.5),
                 int(keys * 1.5) * 4)

    # (a) device store, default kernel backend
    sess, batch, (losses_a, times, wall) = train_fitting(
        "(a)", DEPLOYMENT_BATCH, 4, lambda b: session(arch, b))
    check(sess.workload.engine.kernel_backend == "pallas",
          f"default kernel backend on TPU is "
          f"{sess.workload.engine.kernel_backend!r}, not 'pallas'")
    report_run("(a) device store", sess, losses_a, times, wall)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"(a) peak_bytes_in_use={stats.get('peak_bytes_in_use')!r} "
        f"bytes_limit={stats.get('bytes_limit')!r}")

    # (d) serve the trained table
    rep = sess.serve_embeddings(num_requests=64, max_batch=16,
                                check_exact=True)
    s = rep.summary
    log(f"(d) serve: requests={int(s['requests_done'])} windows="
        f"{int(s['windows'])} exact={s['exact']} "
        f"max_abs_diff={s['max_abs_diff']!r} qps={s['qps']!r}")
    check(s["exact"] == 1, "served embeddings differ from the master table")
    del sess, rep
    gc.collect()

    # (b) jnp reference kernels
    sess = session(arch, batch, backend="reference")
    losses_b, times, wall = train(sess)
    report_run("(b) reference kernels", sess, losses_b, times, wall)
    dev = float(np.max(np.abs(np.subtract(losses_a, losses_b))))
    log(f"(b) max |loss(pallas) - loss(reference)| = {dev!r} (tol {LOSS_TOL})")
    check(dev <= LOSS_TOL, "pallas and reference losses diverge")
    del sess
    gc.collect()

    # (c) host store
    sess = session(arch, batch, store="host")
    losses_c, times, wall = train(sess)
    report_run("(c) host store", sess, losses_c, times, wall)
    log(f"(c) host losses bit-equal to device losses: {losses_c == losses_a}")
    check(losses_c == losses_a, "host-store losses differ from device store")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chips() -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    arch = chip_share_arch(4)
    devices = jax.devices()[:4]

    def mesh(shape):
        return Mesh(np.asarray(devices).reshape(shape), ("data", "model"))

    runs = {}
    batch = 4 * DEPLOYMENT_BATCH
    for tag, shape, store in (("(1,4) device", (1, 4), "device"),
                              ("(1,4) sharded host", (1, 4), "host"),
                              ("(2,2) 2D device", (2, 2), "device")):
        # every run after the first keeps the first run's batch
        sess, batch, (losses, times, wall) = train_fitting(
            tag, batch, batch if runs else 16,
            lambda b: session(arch, b, store=store, mesh=mesh(shape),
                              sparse_axes=("data", "model")))
        report_run(tag, sess, losses, times, wall)
        runs[tag] = losses
        del sess
        gc.collect()
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')!r}")
    base = runs["(1,4) device"]
    for tag, losses in runs.items():
        log(f"{tag}: bit-equal to (1,4) device: {losses == base}")
        check(losses == base, f"{tag} losses differ from the (1,4) device run")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2x2-host phase (sharded stores, 2D grid)")
    args = p.parse_args(argv)

    try:
        from repro.launch.compile_cache import counts, enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] FAIL: repro package not found under {ROOT}/src "
              f"({e}); run from a checkout of the repository", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    need = 4 if args.four_chips else 1
    if platform != "tpu":
        print(f"[chip_smoke] FAIL: platform is {platform!r}, not 'tpu'; this "
              "script runs only on a TPU and never falls back",
              file=sys.stderr)
        return 2
    if len(devices) < need:
        print(f"[chip_smoke] FAIL: needs {need} TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    try:
        four_chips() if args.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile cache: hits={counts['hits']} writes={counts['writes']}; "
        f"wall {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
