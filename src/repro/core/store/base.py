"""Tiered embedding storage: ONE protocol for where the master rows live.

The paper's bottleneck at O(1k) accelerators is embedding *data movement*:
DBP exists to hide the DRAM->HBM retrieval stage, and FWP's freezing
observation says a small hot set dominates accesses. This package turns
"where do master rows live" into a seam — every tier implements the same
:class:`EmbeddingStore` contract and the DBP driver composes around it:

    ``plan(keys)``        DBP stage 3: route a window, and (for host tiers)
                          pull the owner-side union key list to the host.
    ``retrieve(plan)``    DBP stage 4a: master rows -> a fresh
                          :class:`~repro.core.embedding.engine.DualBuffer`.
    ``commit(buffer, plan)``  DBP stage 5'': persist the updated buffer
                          back into the master tier (in place where the
                          tier is device-resident — see train/step.py's
                          donation contract).

Tiers
-----
``DeviceStore``  master in HBM — the N=1 trivial plan (no host keys, no
                 staging); retrieval/writeback are the engine's sharded ops.
``HostStore``    master in host DRAM (absorbs the old
                 ``core.embedding.hierarchical.HostTierTable``); retrieval
                 gathers on the host and ships only the compact buffer H2D.
``CachedStore``  ``HostStore`` plus a frequency-admitted HBM hot-cache:
                 hit rows are served from device (kernels/dispatch), only
                 misses are staged H2D, and evictions write back to DRAM.
``ShardedStore`` the host/cached tiers on a mesh: the DRAM master
                 row-sharded per host over ``sparse_axes``, each shard's
                 slice behind its own local host/cached tier (selected
                 automatically by :func:`build_store` when ``mesh`` is
                 given — the tier NAMES stay "host"/"cached").

Because the paper's consistency argument lives entirely in the buffer
domain (sync happens between HBM buffers), swapping the master tier is
invisible to DBP/FWP semantics — ``tests/test_hierarchical.py`` replays a
training run through all three tiers bit-for-bit.

Selection mirrors ``kernel_backend``: ``NestPipeConfig.store`` ("auto"
falls through to ``$REPRO_STORE``, then "device"), overridable per driver
with an explicit store instance.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..embedding.engine import DualBuffer, WindowPlan
from ..embedding.table import EmbeddingTableState

STORES = ("device", "host", "cached")

# Per-stage wall-time counter keys every tier reports through ``metrics()``:
# plan (stage 3 routing + host key copy), retrieve (stage 4a gather +
# staging), commit (the stage-6 epilogue: D2H + master scatter) and the H2D
# slice of retrieve (device_put dispatch; includes the transfer itself when
# the pooled staging path blocks for reuse safety). On the device tier
# these measure jit DISPATCH time only — the device work is async.
STAGE_TIMER_KEYS = ("plan_ms", "retrieve_ms", "commit_ms", "h2d_ms")


class StageTimers:
    """Cumulative per-stage wall-time counters (milliseconds).

    Each timed interval is also a profiler span named ``dbp.<stage>``
    (``dbp.plan``, ``dbp.retrieve``, ``dbp.commit``, ``dbp.h2d``), on the
    clock of the device ops when a trace is running; with none running a
    span costs one inactive ``TraceMe``.

    Thread-safe: with the async stage executor, plan/retrieve run on stage
    threads while commit runs on the commit thread, so increments race.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._ms = {k: 0.0 for k in STAGE_TIMER_KEYS}

    def add(self, key: str, seconds: float) -> None:
        with self._lock:
            self._ms[key] += seconds * 1e3

    @contextmanager
    def timed(self, key: str):
        with jax.profiler.TraceAnnotation("dbp." + key.removesuffix("_ms")):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(key, time.perf_counter() - t0)

    def as_dict(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._ms)


class StagePool:
    """Double-buffered staging-array pool for the async executor's workers.

    ``HostStore.stage`` deliberately allocates FRESH numpy arrays per call:
    ``device_put`` is async, and once the resulting buffers are donated
    downstream nothing can observe whether the H2D copy out of the source
    completed — reuse would be an unobservable use-after-reuse race. The
    pool is safe ONLY because the pooled path blocks (``block_until_ready``
    on the staged device arrays) before an array returns here, so every
    pooled array is provably copied out. That block runs on a stage WORKER
    thread, off the driver's critical path — which is exactly why the pool
    is an executor-mode feature and fresh allocation stays the rule for the
    synchronous loop. It additionally requires a backend whose
    ``device_put`` really COPIES a numpy source: the CPU backend zero-copy
    aliases aligned host buffers, making reuse unsafe at any blocking
    discipline (and pointless — there is no copy to elide), so
    ``HostStore.use_stage_pool`` probes before engaging.

    Keyed by (shape, dtype): the host tier stages one fixed buffer shape,
    the cached tier a handful of bucket-padded miss shapes. At most
    ``slots`` arrays are retained per key (double buffering).
    """

    def __init__(self, slots: int = 2):
        self.slots = max(int(slots), 1)
        self._lock = threading.Lock()
        self._free: Dict[tuple, list] = {}

    def take(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype))
        with self._lock:
            bucket = self._free.get(key)
            if bucket:
                return bucket.pop()
        return np.empty(shape, dtype)

    def give(self, *arrays: np.ndarray) -> None:
        with self._lock:
            for a in arrays:
                bucket = self._free.setdefault(
                    (a.shape, a.dtype), [])
                if len(bucket) < self.slots:
                    bucket.append(a)


class FetchPlan(NamedTuple):
    """One lookahead batch's routing artifacts, as a store needs them.

    ``window`` stays on device (it is also the window plan the FWP step
    consumes); ``host_keys`` is the host copy of the owner-side union key
    list — ``None`` on the device tier, which never needs keys on the host.
    """

    window: WindowPlan
    host_keys: Optional[np.ndarray]


@runtime_checkable
class EmbeddingStore(Protocol):
    """Contract every storage tier implements (see module docstring).

    Lifecycle: the driver ``ingest``s the master out of the
    :class:`~repro.train.state.TrainState` at the start of a run (the state
    keeps a zero-row placeholder so the steady-state jit signature is
    tier-independent), calls plan/retrieve/commit per step, may
    ``export_table`` mid-run for checkpoints (non-destructive; cache and
    frequency state are NOT part of the export), and ``release``s the
    master back into the state at the end.
    """

    tier: str
    owns_master: bool

    def ingest(self, table: EmbeddingTableState) -> EmbeddingTableState: ...

    def plan(self, keys) -> FetchPlan: ...

    # plan, split for the async executor: ``route`` is the stage-3 jit
    # DISPATCH (driver thread — preserves XLA queue order ahead of the
    # window jit) and ``plan_from_window`` the host half (D2H key-list
    # pull; a stage-worker wait). plan == plan_from_window(route(keys)).
    def route(self, keys) -> Any: ...

    def plan_from_window(self, window) -> FetchPlan: ...

    def retrieve(self, plan: FetchPlan) -> DualBuffer: ...

    def commit(self, buffer: DualBuffer, plan: FetchPlan) -> None: ...

    def export_table(self) -> EmbeddingTableState: ...

    def release(self) -> EmbeddingTableState: ...

    def metrics(self) -> Dict[str, float]: ...


def placeholder_table(table: EmbeddingTableState) -> EmbeddingTableState:
    """Zero-row stand-in kept in TrainState while a store owns the master.

    Shape/dtype-stable across steps so the steady-state jit signature (and
    its donation aliasing) is identical for every tier.
    """
    d = table.rows.shape[-1]
    return EmbeddingTableState(
        rows=jnp.zeros((0, d), table.rows.dtype),
        accum=jnp.zeros((0,), jnp.float32),
    )


def resolve_store(store: Optional[str] = None) -> str:
    """Resolve a store tier name: explicit arg > $REPRO_STORE > "device".

    ``"auto"``/None fall through — exactly the ``kernel_backend``
    resolution order (kernels/dispatch.py).
    """
    for cand in (store, os.environ.get("REPRO_STORE")):
        if cand and cand != "auto":
            if cand not in STORES:
                raise ValueError(
                    f"unknown embedding store {cand!r}; expected one of "
                    f"{STORES} or 'auto'")
            return cand
    return "device"


def build_store(
    name: Optional[str],
    spec: Any,  # MegaTableSpec
    fns: Any,  # train.step.StepFns
    *,
    donate: bool = True,
    mesh: Any = None,
    sparse_axes: tuple = (),
    cache_rows: int = 0,
    cache_admit: int = 1,
    cache_chunk_rows: int = 8,
    cache_policy: Optional[str] = None,
    prefetch_ahead: int = 1,
    kernel_backend: Optional[str] = None,
    sparse_comm: Optional[str] = None,
    fault_inject: Optional[str] = None,
) -> EmbeddingStore:
    """Construct the store for a resolved tier name (see :func:`resolve_store`).

    On a mesh the host/cached tiers route to :class:`ShardedStore`: the
    DRAM master is row-sharded per host over ``sparse_axes`` (the engine's
    ownership hashing; TWO axes select the 2D table-group x row grid of
    ``routing.owner_of_2d``) and each shard wraps its slice in its own
    local host/cached tier. Genuinely unsupported combos stay loud errors — the
    serial driver rejects every non-device store (DBPDriver / strategies),
    and a mesh whose sparse axes don't match the spec's shard count fails
    in the ShardedStore constructor.

    ``sparse_comm`` selects the sparse-path compression mode (comm.py);
    the device tier has no host exchange to compress, so it resolves the
    mode only to reject bad names and stays ``"off"``. ``cache_policy``
    resolves the same way (policy.py) — validated on every tier, acted on
    only where a cache exists. ``prefetch_ahead`` sizes the cached tier's
    rolling lookahead horizon (the oracle policy's admission window) to
    the Prefetcher's actual in-flight depth.

    ``fault_inject`` arms the chaos seam (dist/inject.py): the resolved
    spec string builds ONE :class:`~repro.dist.inject.FaultInjector`
    shared by every hook point of the constructed store. The device tier
    has no host stages to fault, so it parses the spec only to reject a
    typo'd schedule loudly.
    """
    from ...dist.inject import FaultInjector, resolve_fault_inject
    from .cached import CachedStore
    from .comm import SparseComm, resolve_sparse_comm
    from .device import DeviceStore
    from .host import HostStore
    from .policy import resolve_cache_policy
    from .sharded import ShardedStore

    tier = resolve_store(name)
    resolve_cache_policy(cache_policy)  # validate even where it's a no-op
    injector = FaultInjector.from_spec(resolve_fault_inject(fault_inject))
    if tier == "device":
        resolve_sparse_comm(sparse_comm)  # validate even where it's a no-op
        return DeviceStore(fns, donate=donate)
    if mesh is not None:
        return ShardedStore(
            spec, fns, mesh, sparse_axes, local_tier=tier,
            cache_rows=cache_rows, cache_admit=cache_admit,
            cache_chunk_rows=cache_chunk_rows, cache_policy=cache_policy,
            prefetch_ahead=prefetch_ahead,
            donate=donate, kernel_backend=kernel_backend,
            sparse_comm=sparse_comm, injector=injector,
        )
    if tier == "host":
        return HostStore(spec, fns, comm=SparseComm(sparse_comm),
                         injector=injector)
    return CachedStore(
        spec, fns, capacity=cache_rows, admit_threshold=cache_admit,
        chunk_rows=cache_chunk_rows, policy=cache_policy,
        horizon_windows=prefetch_ahead + 1,
        donate=donate, kernel_backend=kernel_backend,
        comm=SparseComm(sparse_comm), injector=injector,
    )
