"""Backend dispatch for the sparse hot-path kernels.

This module is the ONE place that decides how the embedding engine's three
hotspots execute — the owner-side row serve (``gather_rows``), the sparse
gradient aggregation (``segment_rowsum``) and the dual-buffer intersection
copy (``buffer_sync``). Every call site in ``core/embedding/engine.py``
routes through here instead of picking an implementation inline.

Backends
--------
``"pallas"``
    The Pallas TPU kernels (``embedding_gather.py`` / ``segment_rowsum.py``
    / ``buffer_sync.py``) compiled for real — only valid on TPU hosts.
``"interpret"``
    The same Pallas kernels under the Pallas interpreter. Slow; exists so
    the exact kernel code paths can be validated on CPU (tests use this).
    Refused on a TPU host, where it would hide the compiled kernels.
``"reference"``
    The pure-jnp oracles from ``ref.py`` — the fastest choice on CPU and
    the ground truth the kernels are swept against.
``"auto"`` (the default)
    ``"pallas"`` when ``jax.default_backend() == "tpu"``, else
    ``"reference"``. Override per-process with the ``REPRO_KERNEL_BACKEND``
    environment variable or :func:`set_default_backend`, per-workload with
    ``NestPipeConfig.kernel_backend``, or per-call with the ``backend=``
    keyword.

Contract
--------
All three ops keep the engine's sentinel conventions regardless of backend:

- ``gather_rows(rows, idx)``: out-of-range ``idx`` (sentinel slots,
  ``idx >= rows.shape[0]`` or negative) yields a zero row. The Pallas kernel
  itself is branch-free over pre-clamped indices; this wrapper clamps and
  re-masks so callers never see clamp artifacts.
- ``segment_rowsum(values, ids, num_segments)``: rows with
  ``ids >= num_segments`` are dropped; accumulation is f32 regardless of
  the input dtype. Ids do NOT have to be sorted (the kernel sorts them).
- ``buffer_sync(active_rows, prefetch_rows, src)``: per prefetch row,
  ``src[i] < len(active_rows)`` selects the active row, anything else keeps
  the prefetch row.

``gather_rows`` and ``buffer_sync`` move rows without arithmetic, so they
are bit-identical across backends. ``segment_rowsum`` is bit-identical
whenever f32 sums its rows exactly (``tests/test_dispatch.py`` checks
integer-valued rows); otherwise its sum order differs from the scatter-add
reference and results agree to f32 rounding.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .buffer_sync import buffer_sync_rows as _buffer_sync_kernel
from .embedding_gather import embedding_gather as _gather_kernel
from .segment_rowsum import segment_rowsum as _segsum_kernel

BACKENDS = ("pallas", "interpret", "reference")

_default_override: Optional[str] = None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend name: explicit arg > set_default_backend() >
    $REPRO_KERNEL_BACKEND > auto-detect. ``"auto"``/None fall through.
    On a TPU host ``"interpret"`` is refused."""
    for cand in (backend, _default_override,
                 os.environ.get("REPRO_KERNEL_BACKEND")):
        if cand and cand != "auto":
            if cand not in BACKENDS:
                raise ValueError(
                    f"unknown kernel backend {cand!r}; expected one of "
                    f"{BACKENDS} or 'auto'")
            if cand == "interpret" and _on_tpu():
                raise ValueError(
                    "kernel backend 'interpret' is refused on a TPU host: "
                    "use 'pallas' (compiled kernels) or 'reference'")
            return cand
    return "pallas" if _on_tpu() else "reference"


def set_default_backend(backend: Optional[str]) -> None:
    """Process-wide override (None restores auto-detection)."""
    global _default_override
    if backend is not None and backend != "auto" and backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}")
    _default_override = None if backend in (None, "auto") else backend


# ---------------------------------------------------------------------------
# dispatched ops
# ---------------------------------------------------------------------------


def gather_rows(rows: jax.Array, idx: jax.Array, *,
                backend: Optional[str] = None) -> jax.Array:
    """``rows[idx]`` with out-of-range -> zero row (sentinel-safe gather)."""
    b = resolve_backend(backend)
    if b == "reference":
        return jnp.take(rows, idx, axis=0, mode="fill", fill_value=0)
    n_rows = rows.shape[0]
    valid = (idx >= 0) & (idx < n_rows)
    clamped = jnp.clip(idx, 0, n_rows - 1).astype(jnp.int32)
    out = _gather_kernel(rows, clamped, interpret=(b != "pallas"))
    return jnp.where(valid[:, None], out, jnp.zeros((), out.dtype))


def segment_rowsum(values: jax.Array, ids: jax.Array, num_segments: int, *,
                   backend: Optional[str] = None) -> jax.Array:
    """Sum (L, D) rows into (num_segments, D) f32 buckets; ids >= S drop."""
    b = resolve_backend(backend)
    if b == "reference":
        return ref.segment_rowsum_ref(values, ids, num_segments)
    return _segsum_kernel(values, ids, num_segments,
                          interpret=(b != "pallas"))


def buffer_sync(active_rows: jax.Array, prefetch_rows: jax.Array,
                src: jax.Array, *, backend: Optional[str] = None) -> jax.Array:
    """DBP intersection copy: src[i] < len(active) picks the active row."""
    b = resolve_backend(backend)
    if b == "reference":
        return ref.buffer_sync_ref(active_rows, prefetch_rows, src)
    return _buffer_sync_kernel(active_rows, prefetch_rows, src,
                               interpret=(b != "pallas"))
