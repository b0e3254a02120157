"""The Session facade: one front door for train / serve / bench.

Composes workload resolution (``launch.build.resolve``), stream construction
(``api.streams``), state init/restore, the execution strategy
(``api.strategies``) and the checkpoint + fault policy (``repro.dist``)
behind one object:

    from repro.api import Session

    sess = Session.from_arch("hstu-industrial", mode="nestpipe", reduced=True)
    report = sess.train(steps=200)
    print(report.summary)
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import NestPipeConfig, OptimizerConfig, ShapeConfig
from ..configs.registry import ArchSpec
from ..core.dbp.pipeline import PipelineStats
from ..core.embedding import init_table_state
from ..dist.checkpoint import (
    latest_step,
    restore_checkpoint,
    restore_latest_verifiable,
    save_checkpoint,
)
from ..dist.fault import PreemptionGuard, StepWatchdog
from ..dist.inject import FaultInjector, resolve_fault_inject
from ..launch.build import Workload, resolve
from ..train.state import TrainState
from .strategies import Strategy, get_strategy
from .streams import resolve_stream


@dataclass
class TrainReport:
    """What a train/bench run produced: final state + pipeline statistics."""

    state: TrainState
    stats: PipelineStats
    wall_s: float
    stragglers: int
    summary: Dict[str, Any] = field(default_factory=dict)


@dataclass
class ServeReport:
    """Generated tokens (B, gen) + latency summary from a serve run."""

    tokens: np.ndarray
    summary: Dict[str, Any] = field(default_factory=dict)


@dataclass
class EmbedServeReport:
    """Per-request results (rid order) + latency/cache summary from an
    embedding-serving run (:meth:`Session.serve_embeddings`)."""

    results: np.ndarray  # (n, F, D) embeddings or (n,) dlrm logits
    summary: Dict[str, Any] = field(default_factory=dict)


class Session:
    """A training/serving session over one resolved workload.

    Construction goes through :meth:`from_arch` (registry archs) or
    :meth:`from_workload` (hand-assembled workloads). The session owns:

    - the resolved :class:`~repro.launch.build.Workload` (``.workload``)
    - the execution :class:`~repro.api.strategies.Strategy` (``.strategy``)
    - the train state (``.state``), lazily initialized on first use
    - the data stream cursor — after a restore, training resumes at batch
      index ``state.step``, so restarts are exact in serial mode
    - the checkpoint policy (``ckpt_dir``/``ckpt_every``) and fault policy
      (preemption guard + step watchdog), which no caller has to wire again
    """

    def __init__(
        self,
        workload: Workload,
        *,
        opt_cfg: Optional[OptimizerConfig] = None,
        seed: int = 0,
        data_seed: Optional[int] = None,
        ckpt_dir: str = "",
        ckpt_every: int = 0,
        strategy: Optional[Strategy] = None,
        watchdog_factor: float = 3.0,
        preemption_signals: tuple = (),
        reduced: bool = False,
        metrics_every: Optional[int] = None,
    ):
        self.workload = workload
        self.reduced = reduced
        self.strategy = strategy or get_strategy(workload.mode)
        self.opt_cfg = opt_cfg or OptimizerConfig()
        self.seed = seed
        self.data_seed = seed if data_seed is None else data_seed
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.metrics_every = metrics_every
        self.guard = PreemptionGuard(signals=preemption_signals)
        self.watchdog = StepWatchdog(factor=watchdog_factor)
        # One injector for the session's checkpoint I/O, armed by the same
        # resolved spec the store's stage hooks use (dist/inject.py) — but
        # a SEPARATE instance, so a "ckpt_torn:step=0" schedule counts
        # checkpoint saves, not store stage calls.
        self.ckpt_injector = FaultInjector.from_spec(
            resolve_fault_inject(workload.npcfg.fault_inject))
        self._fns = None  # training step fns built on first train/bench
        self._optimizer = None
        self._state: Optional[TrainState] = None

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_arch(
        cls,
        arch: Union[str, ArchSpec],
        *,
        mode: str = "nestpipe",
        reduced: bool = False,
        shape: str = "train_4k",
        mesh=None,
        global_batch: Optional[int] = None,
        seq_len: Optional[int] = None,
        n_micro: int = 4,
        clustering: str = "keycentric",
        unroll: bool = True,
        bucket_slack: float = 4.0,
        t_chunk: int = 64,
        store: str = "auto",
        cache_rows: int = 0,
        cache_chunk_rows: int = 0,
        cache_policy: str = "auto",
        prefetch_ahead: int = 1,
        sparse_comm: str = "auto",
        dense_comm: str = "auto",
        async_stages: str = "auto",
        stage_workers: int = 1,
        fault_inject: str = "auto",
        npcfg: Optional[NestPipeConfig] = None,
        opt_cfg: Optional[OptimizerConfig] = None,
        lr: Optional[float] = None,
        seed: int = 0,
        data_seed: Optional[int] = None,
        ckpt_dir: str = "",
        ckpt_every: int = 0,
        preemption_signals: tuple = (),
        metrics_every: Optional[int] = None,
        sparse_axes: Optional[tuple] = None,
    ) -> "Session":
        """Resolve a registry arch (by id, or an ``ArchSpec`` built outside
        the registry) into a ready session.

        ``mode`` must name a registered strategy (``repro.api.strategies``).
        ``global_batch``/``seq_len`` override the named ``shape`` with a
        CPU-scale custom shape; leave them None to use the production shape.
        ``metrics_every`` sets the driver's deferred metric-drain cadence
        (loss/timing stay on device between drains; None = strategy default).
        Note the step watchdog then sees span-AVERAGED step times — a single
        slow step inside a span is diluted by a factor of ``metrics_every``;
        pass ``metrics_every=1`` when per-step watchdog sensitivity matters
        more than pipeline overlap.

        ``store`` picks the embedding storage tier for the pipelined modes
        (``"device" | "host" | "cached"``; ``"auto"`` resolves
        ``$REPRO_STORE`` then the device tier — see ``repro.core.store``).
        With a ``mesh``, host/cached select the SHARDED tier: the DRAM
        master row-shards per host over the workload's sparse axes, each
        shard behind its own local host/cached slice (same names; the
        summary reports ``store_shards``).
        ``cache_rows`` sizes the CachedStore HBM hot-cache (0 = auto) and
        ``prefetch_ahead`` sets the DBP retrieval lookahead depth k.
        ``cache_chunk_rows`` sets the cache's admission/eviction grain
        (0 = config default; 1 = the row-granular seed behaviour) and
        ``cache_policy`` picks the victim-selection scheme
        (``"freq" | "lfu" | "lru" | "oracle"``; ``"auto"`` resolves
        ``$REPRO_CACHE_POLICY`` then freq — ``repro.core.store.policy``).
        Every policy replays the host tier bit for bit: policies decide
        WHERE rows live, never what they are.
        ``async_stages`` moves the host-side plan/retrieve/commit stages
        onto background worker threads (bit-exact — the epoch-fenced
        executor in ``repro.core.store.async_exec``; ``"auto"`` resolves
        ``$REPRO_ASYNC_STAGES`` then off) and ``stage_workers`` sizes its
        plan/retrieve pool.
        ``sparse_comm`` selects sparse-path compression for the host-side
        tiers (``"off" | "pack" | "int8"``; ``"auto"`` resolves
        ``$REPRO_SPARSE_COMM`` then off — ``repro.core.store.comm``).
        ``pack`` is lossless and replays ``off`` bit for bit; ``int8`` is
        explicitly approximate (quantized rows + frequency-aware selective
        sync with error feedback).
        ``dense_comm`` re-reduces the dense-path gradients through the
        int8 quantized ring (``"off" | "int8"``; ``"auto"`` resolves the
        config default off — ``repro.dist.compressed``). Exact on a
        1-replica axis; approximate across replicas (residual dropped).
        ``sparse_axes`` overrides the workload's sparse mesh axes (in
        order). A 2-axis tuple over a 2D mesh selects 2D sparse
        parallelism: ownership factors table-group x row
        (``routing.owner_of_2d``; axis 0 = the column dimension), the
        stage-3 exchange runs one All2All per sub-axis, and the sharded
        tiers report the grid as ``store_shard_grid`` plus per-axis
        ``wire_bytes_ax0``/``wire_bytes_ax1``. None keeps the arch's
        default parallelism (recsys archs already default to ALL mesh
        axes, so a (2, 2) mesh is 2D out of the box).
        ``fault_inject`` arms deterministic fault injection at the store's
        stage boundaries and the session's checkpoint I/O (spec grammar in
        ``repro.dist.inject``; ``"auto"`` resolves ``$REPRO_FAULT_INJECT``
        then off). Injected stage faults are absorbed by the store's
        bounded retries — the run replays the fault-free trajectory bit
        for bit and the summary reports the recovery counters.
        """
        strategy = get_strategy(mode)  # fail fast on unknown modes
        npcfg = npcfg or NestPipeConfig(
            fwp_microbatches=n_micro, bucket_slack=bucket_slack,
            clustering=clustering, fwp_unroll=unroll,
        )
        # Overlay only the kwargs the caller actually set — a provided
        # npcfg keeps its own values for everything left at the default.
        overlay = {}
        if store != "auto":
            overlay["store"] = store
        if cache_rows != 0:
            overlay["cache_rows"] = cache_rows
        if cache_chunk_rows != 0:
            overlay["cache_chunk_rows"] = cache_chunk_rows
        if cache_policy != "auto":
            overlay["cache_policy"] = cache_policy
        if prefetch_ahead != 1:
            overlay["prefetch_ahead"] = prefetch_ahead
        if sparse_comm != "auto":
            overlay["sparse_comm"] = sparse_comm
        if dense_comm != "auto":
            overlay["dense_comm"] = dense_comm
        if async_stages != "auto":
            overlay["async_stages"] = async_stages
        if stage_workers != 1:
            overlay["stage_workers"] = stage_workers
        if fault_inject != "auto":
            overlay["fault_inject"] = fault_inject
        if overlay:
            npcfg = dataclasses.replace(npcfg, **overlay)
        npcfg = strategy.configure(npcfg)
        shape_override = None
        if global_batch is not None or seq_len is not None:
            shape_override = ShapeConfig(
                "api", kind="train",
                seq_len=seq_len or 32, global_batch=global_batch or 32)
        wl = resolve(
            arch, shape, mesh=mesh, mode=mode, npcfg=npcfg, reduced=reduced,
            t_chunk=t_chunk, shape_override=shape_override,
            sparse_axes=sparse_axes,
        )
        if lr is not None:
            opt_cfg = dataclasses.replace(opt_cfg or OptimizerConfig(), lr=lr)
        return cls(
            wl, opt_cfg=opt_cfg, seed=seed, data_seed=data_seed,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, strategy=strategy,
            preemption_signals=preemption_signals, reduced=reduced,
            metrics_every=metrics_every,
        )

    @classmethod
    def from_workload(cls, workload: Workload, **kwargs) -> "Session":
        """Wrap a hand-assembled Workload (custom configs outside the
        registry, e.g. the 100M-param HSTU example)."""
        return cls(workload, **kwargs)

    # ------------------------------------------------------------------
    # state + checkpoints
    # ------------------------------------------------------------------

    @property
    def fns(self):
        if self._fns is None:
            self._fns, self._optimizer = self.workload.step_fns(self.opt_cfg)
        return self._fns

    @property
    def optimizer(self):
        self.fns  # build the (fns, optimizer) pair lazily together
        return self._optimizer

    @property
    def state(self) -> TrainState:
        if self._state is None:
            self._state = self.workload.init_state(
                jax.random.PRNGKey(self.seed), self.optimizer)
        return self._state

    @state.setter
    def state(self, value: TrainState) -> None:
        self._state = value

    def save(self, step: Optional[int] = None) -> str:
        """Checkpoint the current state (atomic manifest write)."""
        if not self.ckpt_dir:
            raise ValueError("Session has no ckpt_dir configured")
        s = int(self.state.step) if step is None else int(step)
        return save_checkpoint(self.ckpt_dir, self.state, s)

    def restore(self, step: Optional[int] = None) -> TrainState:
        """Restore state from ``ckpt_dir`` (latest step by default). The next
        ``train()`` resumes the data stream at batch index ``state.step``."""
        if not self.ckpt_dir:
            raise ValueError("Session has no ckpt_dir configured")
        self._state = restore_checkpoint(self.ckpt_dir, self.state, step)
        return self._state

    def restore_if_available(self) -> Optional[int]:
        """Restore the newest VERIFIABLE checkpoint when one exists;
        returns its step (None when the directory holds nothing usable).

        Walks past checkpoints whose payload fails the manifest CRC pass
        (torn write on a preemption kill, bit rot) — falling back a step
        is always safe because the trajectory is deterministic."""
        if not self.ckpt_dir:
            return None
        if latest_step(self.ckpt_dir) is None:
            return None
        try:
            self._state, step = restore_latest_verifiable(
                self.ckpt_dir, self.state)
        except FileNotFoundError:
            return None
        return step

    # ------------------------------------------------------------------
    # train / bench
    # ------------------------------------------------------------------

    def train(self, steps: int, *, resume: bool = False,
              checkpoint_final: bool = False) -> TrainReport:
        """Run ``steps`` training steps from the current state.

        The stream starts at batch index ``state.step`` (exact restart in
        serial mode; pipelined modes re-prime the carry one batch early by
        construction). Periodic checkpoints every ``ckpt_every`` steps and a
        final save on preemption are handled here.

        The current state's buffers are DONATED to the jitted steps (updated
        in place); ``self.state`` is rebound to the returned state, but any
        outside references to the pre-train state arrays become invalid.
        """
        if resume:
            self.restore_if_available()
        start = int(self.state.step)
        stream = resolve_stream(self.workload, self.data_seed,
                                start_step=start)

        def on_ckpt(st, _step_no):
            if self.ckpt_dir:
                save_checkpoint(self.ckpt_dir, st, int(st.step),
                                injector=self.ckpt_injector)

        # The driver polls the guard at step boundaries (preemption notice
        # -> checkpoint via on_ckpt + clean exit) and feeds the watchdog
        # from its metric drain, so watchdog events and the driver's
        # straggler stats agree by construction.
        driver_kw = {"guard": self.guard, "watchdog": self.watchdog}
        if self.metrics_every is not None:
            driver_kw["metrics_every"] = self.metrics_every
        on_checkpoint = on_ckpt if self.ckpt_dir else None
        driver = self.strategy.build_driver(
            self.fns, stream, self.workload,
            on_checkpoint=on_checkpoint,
            ckpt_every=self.ckpt_every if self.ckpt_dir else 0,
            **driver_kw,
        )
        events_before = len(self.watchdog.events)
        t0 = time.time()
        state, stats = driver.run(self.state, max(int(steps), 0))
        wall = time.time() - t0
        self._state = state

        flagged = len(self.watchdog.events) - events_before
        if self.ckpt_dir and stats.preempted_at is None \
                and (checkpoint_final or self.guard.should_checkpoint):
            # preempted runs already saved through the driver's exit path;
            # this covers checkpoint_final and a notice that landed after
            # the last step boundary
            self.save()

        summary = stats.summary()
        summary.update({
            "arch": self.workload.arch.name,
            "mode": self.strategy.name,
            "wall_s": round(wall, 2),
            "stragglers_flagged": flagged,
        })
        return TrainReport(state=state, stats=stats, wall_s=wall,
                           stragglers=flagged, summary=summary)

    def bench(self, steps: int = 10) -> TrainReport:
        """Short measured run with no checkpointing — the benchmark path."""
        ckpt_dir, ckpt_every = self.ckpt_dir, self.ckpt_every
        self.ckpt_dir, self.ckpt_every = "", 0
        try:
            return self.train(steps)
        finally:
            self.ckpt_dir, self.ckpt_every = ckpt_dir, ckpt_every

    # ------------------------------------------------------------------
    # serve
    # ------------------------------------------------------------------

    def serve(self, *, batch: int = 4, prompt_len: int = 16, gen: int = 8,
              seed: Optional[int] = None) -> ServeReport:
        """Batched prefill + greedy KV-cache decode through the embedding
        engine (the LLM-arch serving path).

        There are two serving paths, split by arch kind:

        - **LLM archs** (``kind != "recsys"``) — THIS method: resolve a
          decode-shaped workload and run prefill + greedy KV-cache decode,
          reusing the session's trained dense params + master table when
          the specs match (fresh init otherwise).
        - **Recsys archs** (``dlrm-*``) — :meth:`serve_embeddings`: a
          request-level embedding inference path through ``repro.serve``
          (read-only FrozenStoreView over the configured store tier,
          window-coalescing batcher, embedding or dlrm head).

        Calling the wrong one raises with a pointer to the other.
        """
        if self.workload.arch.kind == "recsys":
            raise ValueError(
                f"{self.workload.arch.name} is a recsys arch: no KV-cache "
                "decode path to serve (use .serve_embeddings())")
        if self.workload.mesh is not None:
            raise ValueError(
                "serve() runs the CPU-scale single-device decode path; a "
                "mesh-trained session's table is sharded under a different "
                "mega-table layout — checkpoint and restore into a mesh-less "
                "Session first")
        seed = self.seed if seed is None else seed
        max_len = prompt_len + gen
        try:
            wl = resolve(
                self.workload.arch.name, "decode_32k", mesh=None,
                reduced=self.reduced,
                npcfg=NestPipeConfig(bucket_slack=4.0), t_chunk=64,
                shape_override=ShapeConfig("api-serve", kind="decode",
                                           seq_len=max_len, global_batch=batch),
            )
        except KeyError:
            raise ValueError(
                f"serve() needs a registry arch to resolve a decode workload; "
                f"{self.workload.arch.name!r} is not registered "
                "(from_workload sessions are train/bench only)") from None
        cfg = wl.bundle.cfg
        bundle = wl.bundle
        engine = wl.engine
        rng = np.random.default_rng(seed)
        spec_matches = (
            wl.spec.padded_rows == self.workload.spec.padded_rows
            and wl.spec.dim == self.workload.spec.dim
            and wl.spec.num_shards == self.workload.spec.num_shards
        )
        if self._state is not None and spec_matches:
            # serve the trained weights from this session
            params, table = self._state.dense, self._state.table
        else:
            params = bundle.init_params(jax.random.PRNGKey(seed))
            table = init_table_state(jax.random.PRNGKey(1), wl.spec, None,
                                     engine.sparse_axes)

        toks = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len))
        keys = np.asarray(wl.spec.scramble(jnp.asarray(toks.astype(np.int32))))

        @jax.jit
        def prefill_fn(params, table, keys, extras):
            emb, _ = engine.lookup_from_master(table, keys)
            if bundle.kind == "encdec":
                logits, cache = bundle.prefill(
                    params, emb, frames=extras["frames"], cache_len=max_len)
            elif getattr(cfg, "frontend", None) is not None:
                full = jnp.concatenate(
                    [extras["patches"].astype(emb.dtype), emb], 1)
                logits, cache = bundle.prefill(params, full, cache_len=max_len)
            else:
                logits, cache = bundle.prefill(params, emb, cache_len=max_len)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        @jax.jit
        def decode_fn(params, table, cache, keys):
            emb, _ = engine.lookup_from_master(table, keys)
            logits, cache = bundle.decode_step(params, emb, cache)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        extras = {}
        if bundle.kind == "encdec":
            enc_d = cfg.encoder.d_model or cfg.d_model
            extras["frames"] = jnp.asarray(
                rng.normal(size=(batch, cfg.encoder.n_frames, enc_d)),
                jnp.float32) * 0.02
        elif getattr(cfg, "frontend", None) is not None:
            extras["patches"] = jnp.asarray(
                rng.normal(size=(batch, cfg.frontend.n_positions, cfg.d_model)),
                jnp.float32) * 0.02

        t0 = time.time()
        next_tok, cache = prefill_fn(params, table, jnp.asarray(keys), extras)
        next_tok.block_until_ready()
        t_prefill = time.time() - t0

        generated = [np.asarray(next_tok)]
        t1 = time.time()
        for _ in range(gen - 1):
            k = wl.spec.scramble(next_tok[:, None])
            next_tok, cache = decode_fn(params, table, cache, k)
            generated.append(np.asarray(next_tok))
        jax.block_until_ready(next_tok)
        t_decode = time.time() - t1

        out = np.stack(generated, axis=1)
        summary = {
            "arch": self.workload.arch.name, "batch": batch,
            "prompt_len": prompt_len, "generated": gen,
            "prefill_s": round(t_prefill, 3), "decode_s": round(t_decode, 3),
            "tokens_per_s": round(
                batch * (gen - 1) / max(t_decode, 1e-9), 1),
            "sample_tokens": out[0, :8].tolist(),
        }
        return ServeReport(tokens=out, summary=summary)

    def serve_embeddings(
        self,
        *,
        num_requests: int = 256,
        max_batch: int = 32,
        max_wait_ms: float = 2.0,
        qps: Optional[float] = None,
        zipf_a: Optional[float] = None,
        head: str = "embedding",
        store: Optional[str] = None,
        sparse_comm: Optional[str] = None,
        check_exact: bool = False,
        seed: Optional[int] = None,
    ) -> EmbedServeReport:
        """Serve a zipf embedding-request stream (the recsys serving path).

        Resolves a serve-shaped workload under the ``'serve'`` strategy
        (``fwp_microbatches=1``, no dual-buffer pipelining), builds the
        session's configured store tier (``store`` overrides; mesh-aware
        via ShardedStore), ingests the trained master table (fresh init if
        the session never trained or the specs differ), freezes it behind
        a :class:`~repro.serve.FrozenStoreView`, and pumps ``num_requests``
        synthetic zipf requests through a window-coalescing
        :class:`~repro.serve.ServeRouter`.

        ``qps=None`` runs closed-loop (sustained-throughput mode);
        a positive ``qps`` paces arrivals open-loop so p50/p99 reflect the
        max-wait/max-batch policy. ``head`` is ``"embedding"`` (raw (F, D)
        rows per request) or ``"dlrm"`` (full dense forward, one logit per
        request). ``check_exact`` recomputes every result from the master
        table via ``lookup_from_master`` and reports
        ``exact``/``max_abs_diff`` (serving is bit-exact by construction).
        ``sparse_comm`` overrides the session's sparse-path compression for
        the read path (``"pack"`` keeps serving bit-exact — the view's
        ``metrics()`` surfaces ``wire_bytes``/``idx_bytes`` savings).
        """
        from ..serve import build_router, run_closed_loop, run_open_loop, \
            synthetic_requests

        if self.workload.arch.kind != "recsys":
            raise ValueError(
                f"{self.workload.arch.name} is not a recsys arch: "
                "serve_embeddings() serves per-request embedding lookups "
                "(use .serve() for the KV-cache decode path)")
        seed = self.seed if seed is None else seed
        strategy = get_strategy("serve")
        npcfg = self.workload.npcfg
        if store is not None and store != "auto":
            npcfg = dataclasses.replace(npcfg, store=store)
        if sparse_comm is not None and sparse_comm != "auto":
            npcfg = dataclasses.replace(npcfg, sparse_comm=sparse_comm)
        npcfg = strategy.configure(npcfg)
        wl = resolve(
            self.workload.arch, mesh=self.workload.mesh,
            mode=self.workload.mode, npcfg=npcfg, reduced=self.reduced,
            shape_override=ShapeConfig(
                "api-serve-emb", kind="train",
                seq_len=self.workload.shape.seq_len, global_batch=max_batch),
        )
        engine = wl.engine
        spec_matches = (
            wl.spec.padded_rows == self.workload.spec.padded_rows
            and wl.spec.dim == self.workload.spec.dim
            and wl.spec.num_shards == self.workload.spec.num_shards
        )
        if self._state is not None and spec_matches:
            params, table = self._state.dense, self._state.table
        else:
            params = wl.bundle.init_params(jax.random.PRNGKey(seed))
            table = init_table_state(jax.random.PRNGKey(1), wl.spec, None,
                                     engine.sparse_axes)

        fns, _ = wl.step_fns(self.opt_cfg)
        view = strategy.build_view(fns, wl, table)
        router = build_router(wl, view, params=params, head=head,
                              max_wait_ms=max_wait_ms)
        requests = synthetic_requests(wl, num_requests, zipf_a=zipf_a,
                                      seed=seed)
        if qps is None:
            summary = run_closed_loop(router, requests)
        else:
            summary = run_open_loop(router, requests, qps)

        results = np.stack([router.results[r] for r in range(num_requests)])
        summary.update({
            "arch": self.workload.arch.name, "store": view.tier,
            "sparse_comm": view.sparse_comm,
            "head": head, "max_batch": max_batch,
            "max_wait_ms": max_wait_ms,
        })
        if check_exact:
            diff = self._serve_ground_truth_diff(
                wl, params, table, requests, results, head)
            summary["max_abs_diff"] = float(diff)
            summary["exact"] = int(diff == 0.0)
        return EmbedServeReport(results=results, summary=summary)

    @staticmethod
    def _serve_ground_truth_diff(wl, params, table, requests, results,
                                 head) -> float:
        """Max |served - lookup_from_master ground truth| over every
        request, chunked at the serve batch shape."""
        from ..models.dlrm import dlrm_forward

        engine = wl.engine
        cdtype = getattr(engine, "compute_dtype", jnp.float32)
        cfg = wl.bundle.cfg
        b = wl.batch_shapes["keys"][0][1]

        # Ground truth mirrors the router's two-jit head split (lookup jit
        # + standalone dlrm jit): identical standalone HLO on bit-identical
        # embeddings keeps even the dlrm logits exactly comparable.
        @jax.jit
        def emb_ref(table, keys):
            emb, _ = engine.lookup_from_master(table, keys)
            return emb.astype(cdtype)

        dlrm_ref = jax.jit(lambda params, emb, dense: dlrm_forward(
            params, cfg, emb.astype(jnp.float32), dense))

        def ref_fn(table, keys, dense):
            emb = emb_ref(table, keys)
            if head == "dlrm":
                return dlrm_ref(params, emb, dense)
            return emb

        n = len(requests)
        diff = 0.0
        for lo in range(0, n, b):
            idx = [min(lo + i, n - 1) for i in range(b)]  # pad by repeat
            keys = np.stack([requests[i][0] for i in idx])
            dense = np.stack([requests[i][1] for i in idx])
            ref = np.asarray(jax.device_get(
                ref_fn(table, jnp.asarray(keys), jnp.asarray(dense))))
            got = results[idx]
            diff = max(diff, float(np.max(np.abs(
                got.astype(np.float64) - ref.astype(np.float64)))))
        return diff
