"""Architecture registry: ``--arch <id>`` -> full/reduced configs + family
metadata. One module per assigned architecture (see files in this package).
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

from .base import ModelConfig, ParallelConfig, RecsysModelConfig

_LM_MODULES = {
    "stablelm-3b": "stablelm_3b",
    "stablelm-12b": "stablelm_12b",
    "nemotron-4-340b": "nemotron_4_340b",
    "yi-34b": "yi_34b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "whisper-base": "whisper_base",
    "mamba2-370m": "mamba2_370m",
    "pixtral-12b": "pixtral_12b",
    "grok-1-314b": "grok_1_314b",
    "olmoe-1b-7b": "olmoe_1b_7b",
}

_RECSYS = {
    "hstu-industrial": ("HSTU_INDUSTRIAL", "HSTU_REDUCED"),
    "fuxi-kuairand": ("FUXI_KUAIRAND", "FUXI_REDUCED"),
    "dlrm-ctr": ("DLRM_CTR", "DLRM_REDUCED"),
    # routing-dominated perf-bench cell (CPU-runnable at full size)
    "dlrm-routing": ("DLRM_ROUTING", "DLRM_ROUTING"),
    # cache-dominated perf-bench cell: steep-zipf keys for the CachedStore
    "dlrm-cached": ("DLRM_CACHED", "DLRM_CACHED"),
    # non-stationary streams: the cache-policy bench/test cells
    "dlrm-drift": ("DLRM_DRIFT", "DLRM_DRIFT"),
    "dlrm-growth": ("DLRM_GROWTH", "DLRM_GROWTH"),
}

ASSIGNED_LM_ARCHS: Tuple[str, ...] = tuple(_LM_MODULES)
RECSYS_ARCHS: Tuple[str, ...] = tuple(_RECSYS)
ALL_ARCHS: Tuple[str, ...] = ASSIGNED_LM_ARCHS + RECSYS_ARCHS


@dataclass(frozen=True)
class ArchSpec:
    name: str
    kind: str  # "lm" | "encdec" | "recsys"
    config: Union[ModelConfig, RecsysModelConfig]
    reduced: Union[ModelConfig, RecsysModelConfig]

    @property
    def is_big(self) -> bool:
        """>=30B params => bf16 + FSDP + full remat by default."""
        if isinstance(self.config, ModelConfig):
            return self.config.param_count() >= 25_000_000_000
        return False


def get_arch(name: Union[str, ArchSpec]) -> ArchSpec:
    """Registry lookup by id; an :class:`ArchSpec` passes through unchanged
    (a configuration built outside the registry, e.g. a chip-share cut)."""
    if isinstance(name, ArchSpec):
        return name
    if name in _LM_MODULES:
        mod = importlib.import_module(f".{_LM_MODULES[name]}", __package__)
        kind = "encdec" if mod.CONFIG.encoder is not None else "lm"
        return ArchSpec(name, kind, mod.CONFIG, mod.REDUCED)
    if name in _RECSYS:
        mod = importlib.import_module(".recsys_archs", __package__)
        full, red = _RECSYS[name]
        return ArchSpec(name, "recsys", getattr(mod, full), getattr(mod, red))
    raise KeyError(f"unknown arch '{name}'; available: {sorted(ALL_ARCHS)}")


def default_parallel(arch: ArchSpec, *, multi_pod: bool = False) -> ParallelConfig:
    """Production-mesh parallelism defaults per arch family (DESIGN.md §3)."""
    batch = ("pod", "data") if multi_pod else ("data",)
    if arch.kind == "recsys":
        # Paper's hybrid decentralized architecture: sparse over ALL workers,
        # dense replicated, batch over all workers.
        all_axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return ParallelConfig(
            batch_axes=all_axes, tensor_axes=("model",), sparse_axes=all_axes,
            fsdp_axes=(), expert_axes=("model",), scan_layers=True, remat="full",
        )
    big = arch.is_big
    # ZeRO policy: ZeRO-1 (moments sharded, params whole per model shard)
    # only when the bf16 params fit comfortably next to activations —
    # <= 8 GiB per model shard. Above that (nemotron-340b, grok-314b) params
    # must stay ZeRO-3/FSDP-sharded (measured: ZeRO-1 on nemotron blew peak
    # memory 93 -> 197 GiB/device; see EXPERIMENTS.md §Perf notes).
    params_per_shard = 0
    if isinstance(arch.config, ModelConfig):
        params_per_shard = arch.config.param_count() * 2 / 16  # bf16 / TP16
    zero1 = params_per_shard <= 8 * 2 ** 30
    # remat "full" universally: without it, per-layer attention intermediates
    # saved for backward blow activation memory past HBM even for 3B models
    # (measured: stablelm-3b train_4k 81 GiB/device without remat).
    return ParallelConfig(
        batch_axes=batch,
        tensor_axes=("model",),
        sparse_axes=("model",),
        fsdp_axes=("data",) if big else (),
        expert_axes=("model",),
        scan_layers=True,
        remat="full",
        zero1=zero1,
    )
