"""Workload builder: assembles (arch x shape x mesh x mode) into concrete
jittable steps + input specs. Shared by the dry-run, the trainer, the
server and the benchmarks.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import (
    ModelConfig,
    NestPipeConfig,
    OptimizerConfig,
    ParallelConfig,
    ShapeConfig,
)
from ..configs.registry import ArchSpec, default_parallel, get_arch
from ..configs.shapes import SHAPES, shape_applicable
from ..core.baselines import sparse_axes_for_mode
from ..core.embedding import (
    EmbeddingEngine,
    init_table_state,
    make_mega_table_spec,
    table_pspecs,
)
from ..models import ModelBundle, batch_pspecs, build_model, train_batch_shapes
from ..models.encdec import EncDecCache
from ..train import build_step_fns, constant_lr, make_optimizer
from ..train.optim import AdamState
from ..train.state import TrainState

# Recsys training shape: industrial CTR/sequence batches are per-worker
# hundreds of samples (paper Fig. 9 uses batch 512); 256 samples/worker x
# 256 workers. seq_len is taken from the model config, not this value.
RECSYS_TRAIN_SHAPE = ShapeConfig("train_rec", kind="train", seq_len=1024,
                                 global_batch=65536)


def _axes_entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


@dataclass
class Workload:
    arch: ArchSpec
    shape: ShapeConfig
    mode: str
    mesh: Optional[Mesh]
    parallel: ParallelConfig
    npcfg: NestPipeConfig
    bundle: ModelBundle
    spec: Any  # MegaTableSpec
    engine: EmbeddingEngine
    n_micro: int
    batch_shapes: Dict[str, Tuple[Tuple[int, ...], Any]]
    keys_pspec: P

    @property
    def sparse_axes(self) -> Tuple[str, ...]:
        """Mesh axes the mega-table is row-sharded over (the engine's
        ownership domain; also where the sharded DRAM-master tier places
        its per-host shards — core/store/sharded.py)."""
        return self.engine.sparse_axes

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------

    def step_fns(self, opt_cfg: Optional[OptimizerConfig] = None):
        opt_cfg = opt_cfg or OptimizerConfig()
        optimizer = make_optimizer(opt_cfg)
        mb_keys_shape = self.batch_shapes["keys"][0][1:]
        fns = build_step_fns(
            self.engine, self.bundle.loss_fn, optimizer,
            constant_lr(opt_cfg.lr), self.n_micro, mb_keys_shape,
            unroll=self.npcfg.fwp_unroll,
            dense_comm=self.npcfg.dense_comm,
        )
        return fns, optimizer

    def state_shardings(self, optimizer) -> TrainState:
        """NamedSharding pytree for TrainState on this mesh."""
        assert self.mesh is not None
        params_ps = self.bundle.param_pspecs()
        t_ps = table_pspecs(self.engine.sparse_axes)
        ns = lambda spec: NamedSharding(self.mesh, spec)
        params_sh = jax.tree.map(ns, params_ps, is_leaf=lambda x: isinstance(x, P))
        opt_ps = (self.bundle.opt_pspecs() if self.bundle.opt_pspecs is not None
                  else params_ps)
        opt_leaf_sh = jax.tree.map(ns, opt_ps, is_leaf=lambda x: isinstance(x, P))
        opt_sh = AdamState(
            step=ns(P()),
            mu=opt_leaf_sh,
            nu=opt_leaf_sh,
        )
        return TrainState(
            dense=params_sh, opt=opt_sh,
            table=jax.tree.map(ns, t_ps, is_leaf=lambda x: isinstance(x, P)),
            step=ns(P()),
        )

    def state_shapes(self, optimizer) -> TrainState:
        """ShapeDtypeStructs of the full train state (no allocation)."""
        params = jax.eval_shape(self.bundle.init_params, jax.random.PRNGKey(0))
        opt = jax.eval_shape(optimizer.init, params)
        vp, d = self.spec.padded_rows, self.spec.dim
        from ..core.embedding.table import EmbeddingTableState

        table = EmbeddingTableState(
            rows=jax.ShapeDtypeStruct((vp, d), jnp.float32),
            accum=jax.ShapeDtypeStruct((vp,), jnp.float32),
        )
        return TrainState(params, opt, table,
                          jax.ShapeDtypeStruct((), jnp.int32))

    def batch_sds(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return {
            k: jax.ShapeDtypeStruct(shape, dtype)
            for k, (shape, dtype) in self.batch_shapes.items()
        }

    def batch_shardings(self) -> Dict[str, NamedSharding]:
        assert self.mesh is not None
        specs = batch_pspecs(self.bundle, self.parallel, self.keys_pspec)
        return {k: NamedSharding(self.mesh, s) for k, s in specs.items()}

    def init_state(self, rng, optimizer) -> TrainState:
        """Real (allocating) init — smoke/e2e use only, small configs."""
        params = self.bundle.init_params(rng)
        if self.mesh is not None:
            sh = self.state_shardings(optimizer)
            params = jax.tree.map(jax.device_put, params, sh.dense)
        opt = optimizer.init(params)
        table = init_table_state(
            jax.random.split(rng)[0], self.spec, self.mesh,
            self.engine.sparse_axes,
        )
        return TrainState(params, opt, table, jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------

    def build_prefill_step(self):
        bundle, engine, cfg = self.bundle, self.engine, self.bundle.cfg
        shape = self.shape

        def prefill_step(params, table, batch):
            emb, _ = engine.lookup_from_master(table, batch["keys"])
            if bundle.kind == "encdec":
                logits, cache = bundle.prefill(
                    params, emb, frames=batch["frames"], cache_len=shape.seq_len
                )
            elif isinstance(cfg, ModelConfig) and cfg.frontend is not None:
                full = jnp.concatenate(
                    [batch["patches"].astype(emb.dtype), emb], axis=1
                )
                logits, cache = bundle.prefill(params, full, cache_len=shape.seq_len)
            else:
                logits, cache = bundle.prefill(params, emb, cache_len=shape.seq_len)
            return jnp.argmax(logits, -1), cache

        return prefill_step

    def build_serve_step(self):
        """decode_*: one new token against a seq_len KV cache."""
        bundle, engine = self.bundle, self.engine

        def serve_step(params, table, cache, keys):
            emb, _ = engine.lookup_from_master(table, keys)
            logits, cache = bundle.decode_step(params, emb, cache)
            return jnp.argmax(logits, -1), cache

        return serve_step

    def serve_input_sds(self):
        """(cache_sds, keys_sds) + shardings for the decode dry-run."""
        cfg = self.bundle.cfg
        b = self.shape.global_batch
        s = self.shape.seq_len
        cdt = jnp.dtype(cfg.compute_dtype)
        if self.bundle.kind == "encdec":
            a = cfg.attention
            enc_d = cfg.encoder.d_model or cfg.d_model
            nl = cfg.n_layers
            cache = EncDecCache(
                self_k=jax.ShapeDtypeStruct((nl, b, s, a.n_kv_heads, a.head_dim), cdt),
                self_v=jax.ShapeDtypeStruct((nl, b, s, a.n_kv_heads, a.head_dim), cdt),
                mem_k=jax.ShapeDtypeStruct(
                    (nl, b, cfg.encoder.n_frames, a.n_heads, a.head_dim), cdt),
                mem_v=jax.ShapeDtypeStruct(
                    (nl, b, cfg.encoder.n_frames, a.n_heads, a.head_dim), cdt),
                length=jax.ShapeDtypeStruct((), jnp.int32),
            )
            ba = _axes_entry(self.parallel.batch_axes) if b > 1 else None
            kv_spec = P(None, ba, None, None, None)
            cache_specs = EncDecCache(kv_spec, kv_spec, kv_spec, kv_spec, P())
        else:
            cache = jax.eval_shape(
                lambda: self.bundle.init_cache(b, s, cdt)
            )
            cache_specs = self.bundle.cache_pspecs()
            if b == 1:  # long_500k: batch dim (axis 1) cannot be sharded
                def _unshard_batch(sp):
                    entries = list(tuple(sp))
                    if len(entries) >= 2:
                        entries[1] = None
                    return P(*entries)

                cache_specs = jax.tree.map(
                    _unshard_batch, cache_specs,
                    is_leaf=lambda x: isinstance(x, P),
                )
        keys = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        return cache, cache_specs, keys

    def prefill_input_sds(self):
        cfg = self.bundle.cfg
        b, s = self.shape.global_batch, self.shape.seq_len
        ba = _axes_entry(self.parallel.batch_axes)
        out = {}
        specs = {}
        if self.bundle.kind == "encdec":
            enc_d = cfg.encoder.d_model or cfg.d_model
            out["keys"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.n_frames, enc_d), jnp.float32)
            specs["keys"] = P(ba, None)
            specs["frames"] = P(ba, None, None)
        elif isinstance(cfg, ModelConfig) and cfg.frontend is not None:
            n_p = cfg.frontend.n_positions
            out["keys"] = jax.ShapeDtypeStruct((b, s - n_p), jnp.int32)
            out["patches"] = jax.ShapeDtypeStruct((b, n_p, cfg.d_model), jnp.float32)
            specs["keys"] = P(ba, _axes_entry(self.parallel.tensor_axes))
            specs["patches"] = P(ba, None, None)
        else:
            out["keys"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
            specs["keys"] = P(ba, _axes_entry(self.parallel.tensor_axes))
        return out, specs


def resolve(
    arch_name: Union[str, ArchSpec],
    shape_name: str = "train_4k",
    *,
    mesh: Optional[Mesh] = None,
    multi_pod: bool = False,
    mode: str = "nestpipe",
    npcfg: Optional[NestPipeConfig] = None,
    parallel: Optional[ParallelConfig] = None,
    reduced: bool = False,
    t_chunk: int = 512,
    shape_override: Optional[ShapeConfig] = None,
    sparse_axes: Optional[Tuple[str, ...]] = None,
) -> Workload:
    arch = get_arch(arch_name)
    arch_name = arch.name
    if shape_override is not None:
        shape = shape_override
    elif arch.kind == "recsys":
        shape = RECSYS_TRAIN_SHAPE if shape_name in ("train_4k", "train_rec") \
            else SHAPES[shape_name]
    else:
        shape = SHAPES[shape_name]
    cfg_model = arch.reduced if reduced else arch.config
    if isinstance(cfg_model, ModelConfig):
        ok, reason = shape_applicable(cfg_model, shape)
        if not ok:
            raise ValueError(f"{arch_name} x {shape_name} skipped: {reason}")

    parallel = parallel or default_parallel(arch, multi_pod=multi_pod)
    # Decode KV-cache layout: shard kv heads over the tensor axes when they
    # divide; otherwise fall back to seq-sharded caches with flash-decoding
    # combine (required for every kv=8 arch on 16-way TP, and for long_500k).
    if (shape.kind == "decode" and isinstance(cfg_model := (arch.reduced if reduced else arch.config), ModelConfig)
            and cfg_model.attention is not None and mesh is not None):
        ts = 1
        for a in parallel.tensor_axes:
            ts *= mesh.shape[a]
        if cfg_model.attention.n_kv_heads % ts != 0 or shape.seq_len >= 262144:
            parallel = dataclasses.replace(parallel, kv_shard="seq")
    npcfg = npcfg or NestPipeConfig()
    if mode in ("serial", "2dsp"):
        npcfg = dataclasses.replace(npcfg, dbp=False)
    if sparse_axes is not None:
        # explicit sparse-grid override (e.g. a 2D table-wise x row-wise
        # grid over ("data", "model")): the engine/store ownership grid
        # follows these axes IN ORDER — axis 0 is the column dimension
        parallel = dataclasses.replace(parallel,
                                       sparse_axes=tuple(sparse_axes))
    sparse_axes = sparse_axes_for_mode(mode, parallel.sparse_axes)
    # serving has no micro-batching; training uses the FWP window
    n_micro = npcfg.fwp_microbatches if shape.kind == "train" else 1

    bundle = build_model(arch, parallel, mesh, reduced=reduced, t_chunk=t_chunk)
    cfg = bundle.cfg

    n_shards = 1
    if mesh is not None:
        for a in sparse_axes:
            n_shards *= mesh.shape[a]
    if arch.kind == "recsys":
        spec = make_mega_table_spec(cfg.tables, num_shards=n_shards)
    else:
        spec = make_mega_table_spec(None, vocab_size=cfg.vocab_size,
                                    dim=bundle.emb_dim, num_shards=n_shards)

    batch_shapes = train_batch_shapes(bundle, shape.global_batch, shape.seq_len,
                                      n_micro)
    ba = _axes_entry(parallel.batch_axes) if shape.global_batch > 1 else None
    keys_rank = len(batch_shapes["keys"][0]) - 1  # rank of per-mb keys
    if arch.kind == "recsys":
        keys_pspec = P(*([ba] + [None] * (keys_rank - 1)))
    elif shape.kind == "train" or shape.kind == "prefill":
        # (B, T): batch over batch axes, seq over tensor axes (engine lookup
        # is token-parallel within the model group)
        ma = _axes_entry(parallel.tensor_axes)
        keys_pspec = P(ba, ma) if keys_rank == 2 else P(ba)
    else:  # decode: (B, 1)
        keys_pspec = P(ba, None)

    engine = EmbeddingEngine(
        spec, mesh, sparse_axes, keys_pspec, npcfg,
        compute_dtype=jnp.dtype(cfg.compute_dtype),
    )
    return Workload(
        arch=arch, shape=shape, mode=mode, mesh=mesh, parallel=parallel,
        npcfg=npcfg, bundle=bundle, spec=spec, engine=engine, n_micro=n_micro,
        batch_shapes=batch_shapes, keys_pspec=keys_pspec,
    )
