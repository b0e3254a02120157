"""The NestPipe sharded embedding engine.

Implements the decentralized embedding data path of the paper on a JAX SPMD
mesh: fixed-capacity key dedup + owner bucketing, key All2All (DBP stage 3),
owner-side retrieval into dual buffers, embedding All2All (forward),
gradient All2All (backward), and owner-side frozen-window updates — all with
static shapes.

Layout (DESIGN.md §3): the master table is a global ``(Vp, D)`` array
row-sharded over ``sparse_axes``. Callers hand the engine *local* keys in a
fixed batch partitioning (``keys_pspec``) and receive local embeddings for
exactly those keys. When the table is replicated over some batch axes (LM
mode: sharded over "model", replicated over "data"), gradients are combined
with a ``psum`` over those axes *in buffer/row space* so updates stay
replica-consistent; buffer key sets are unioned over those axes for the same
reason.

Grad-consistency note: gradient packets from different data rows have
different (S, C) key layouts, so they are only ever summed after being
segment-keyed into a space whose key list is identical across replicas
(the dual buffer, or the shard's row space).

Storage note: this engine is the DEVICE half of the storage stack — its
``retrieve``/``writeback`` ops are the HBM-master tier used by
``core.store.DeviceStore``. Host-DRAM and cached tiers implement the same
``EmbeddingStore`` contract in ``core/store`` (there is deliberately no
table-type branching here: everything above the engine talks to a store).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ...configs.base import NestPipeConfig
from ...kernels import dispatch
from ...utils import cdiv, round_up
from .routing import (
    SENTINEL,
    bucket_by_owner_window,
    fixed_unique,
    fixed_unique_window,
    intersect_sorted,
)
from .table import EmbeddingTableState, MegaTableSpec


class LookupPlan(NamedTuple):
    """Per-device routing artifacts for one lookup unit (one micro-batch)."""

    inverse: jax.Array  # (L,) position -> unique slot (U for invalid)
    slot_of_unique: jax.Array  # (U,) unique slot -> flat send slot (S*C for invalid)
    recv_keys: jax.Array  # (S, C) keys this shard must serve (owner side)
    # (S, C) row each received key is served from: its slot in the window's
    # buffer keys (K for a sentinel) for a route_window plan; its row of the
    # master shard (rows_per_shard for a sentinel) for a plan routed alone
    buffer_slot: jax.Array
    overflow: jax.Array  # () int32 routing overflow (must be 0)


class WindowPlan(NamedTuple):
    """Routing for a whole FWP window of N micro-batches (DBP stage 3)."""

    plans: LookupPlan  # leaves stacked along leading N axis
    buffer_keys: jax.Array  # (K,) owner-side union of requested keys (sorted)


class GradPacket(NamedTuple):
    """Owner-side gradient fragment produced by one micro-batch's All2All."""

    keys: jax.Array  # (S, C) int32
    buffer_slot: jax.Array  # (S, C) int32, the plan's buffer_slot
    grads: jax.Array  # (S, C, D) f32


class DualBuffer(NamedTuple):
    """Compact owner-side HBM row cache (DBP active / prefetch buffer)."""

    keys: jax.Array  # (K,) sorted unique, SENTINEL-padded
    rows: jax.Array  # (K, D)
    accum: jax.Array  # (K,) rowwise adagrad state


def buffer_pspecs(sparse_axes: Tuple[str, ...]) -> DualBuffer:
    """PartitionSpecs of a :class:`DualBuffer` on a mesh: every leaf is
    row-partitioned over the sparse axes (shard s's slice is the key/row
    set it OWNS under :func:`routing.owner_of` — the layout contract the
    sharded host tier relies on to slice per-owner key lists).

    With TWO sparse axes this is the 2D-sparse-parallel layout: a
    ``P((ax0, ax1))`` leaf is blocked axis-0-major, so device ``(i, j)``
    holds flat shard ``i * mesh.shape[ax1] + j`` — exactly the
    ``(col_shard, row_shard)`` coordinate of :func:`routing.owner_of_2d`
    (ax0 = the table-group/column axis, ax1 = the row axis)."""
    axes = sparse_axes if len(sparse_axes) > 1 else sparse_axes[0]
    return DualBuffer(keys=P(axes), rows=P(axes, None), accum=P(axes))


@dataclass(frozen=True)
class EngineDims:
    l_local: int  # flattened local positions per micro-batch
    u_max: int  # unique capacity per micro-batch
    cap: int  # per-destination All2All capacity C
    num_shards: int  # S
    n_micro: int  # N
    buffer_cap: int  # K — owner-side union capacity


class EmbeddingEngine:
    """Builds jittable sharded lookup/update ops for one mega-table.

    One instance per (model, shape): the batch partitioning ``keys_pspec``
    and the micro-batch count are fixed at construction so every op has
    static shapes.
    """

    def __init__(
        self,
        spec: MegaTableSpec,
        mesh: Optional[Mesh],
        sparse_axes: Tuple[str, ...],
        keys_pspec: P,
        np_cfg: NestPipeConfig,
        *,
        compute_dtype=jnp.bfloat16,
        sparse_lr: float = 0.05,
        sparse_eps: float = 1e-8,
    ):
        self.spec = spec
        self.mesh = mesh
        self.sparse_axes = tuple(sparse_axes)
        self.keys_pspec = keys_pspec
        self.cfg = np_cfg
        self.compute_dtype = compute_dtype
        self.sparse_lr = float(sparse_lr)
        self.sparse_eps = float(sparse_eps)
        # Hot-path kernel backend, resolved once (see kernels/dispatch.py).
        self.kernel_backend = dispatch.resolve_backend(
            getattr(np_cfg, "kernel_backend", None))

        if mesh is not None:
            self.num_shards = 1
            for a in self.sparse_axes:
                self.num_shards *= mesh.shape[a]
        else:
            self.num_shards = 1
        assert spec.num_shards == self.num_shards, (spec.num_shards, self.num_shards)
        # Axes the grads vary over but the table is replicated over. No mesh
        # means no named axes are ever bound (single-device; _smap is a
        # passthrough), so psum/all_gather over them must be disabled.
        self.psum_axes = () if mesh is None else tuple(
            a for a in self._pspec_axes(keys_pspec) if a not in self.sparse_axes
        )
        self.union_size = 1
        if mesh is not None:
            for a in self.psum_axes:
                self.union_size *= mesh.shape[a]

    # ------------------------------------------------------------------
    # static plumbing
    # ------------------------------------------------------------------

    @staticmethod
    def _pspec_axes(pspec: P) -> Tuple[str, ...]:
        axes = []
        for entry in pspec:
            if entry is None:
                continue
            axes.extend(entry if isinstance(entry, (tuple, list)) else [entry])
        return tuple(axes)

    def dims(self, keys_shape: Tuple[int, ...], n_micro: int = 1) -> EngineDims:
        """Derive static capacities from the *global* per-micro-batch keys shape."""
        l_local = 1
        pspec = tuple(self.keys_pspec) + (None,) * (len(keys_shape) - len(self.keys_pspec))
        for dim, entry in zip(keys_shape, pspec):
            sh = 1
            if self.mesh is not None and entry is not None:
                for a in entry if isinstance(entry, (tuple, list)) else (entry,):
                    sh *= self.mesh.shape[a]
            assert dim % sh == 0, (keys_shape, self.keys_pspec)
            l_local *= dim // sh
        u = min(round_up(max(int(l_local * self.cfg.unique_capacity_factor), 8), 8),
                self.spec.padded_rows)
        c = min(round_up(cdiv(int(u * self.cfg.bucket_slack), self.num_shards), 8),
                self.spec.rows_per_shard)
        k = min(self.union_size * n_micro * self.num_shards * c, self.spec.rows_per_shard)
        k = round_up(k, 8)
        return EngineDims(l_local, u, c, self.num_shards, n_micro, k)

    def _axis(self):
        return self.sparse_axes if len(self.sparse_axes) > 1 else self.sparse_axes[0]

    def _a2a(self, x: jax.Array) -> jax.Array:
        """Owner exchange over the leading (S,) destination axis.

        One sparse axis -> a single flat All2All. Two sparse axes -> the
        2D-sparse-parallel factored exchange: reshape (S, ...) into
        (S0, S1, ...) and run one All2All per mesh sub-axis (a table-group
        exchange over ax0, then a row-group exchange over ax1), each
        confined to its mesh sub-axis so each hop crosses only
        ``size(ax) - 1`` peers instead of ``S - 1``. Because the flat
        shard id is axis-0-major (``_shard_id``), chunk ``(j0, j1)`` of
        device ``(i0, i1)`` lands exactly where the flat tuple-axis
        exchange would put chunk ``j0 * S1 + j1`` — the factored form is
        pure routing, bit-identical to the flat one. Size-1 axes are
        skipped (no collective at all on that hop).
        """
        if self.num_shards == 1:
            return x
        if len(self.sparse_axes) == 1:
            return jax.lax.all_to_all(x, self.sparse_axes[0], 0, 0, tiled=True)
        sizes = tuple(self.mesh.shape[a] for a in self.sparse_axes)
        y = x.reshape(sizes + x.shape[1:])
        for d, a in enumerate(self.sparse_axes):
            if sizes[d] > 1:
                y = jax.lax.all_to_all(y, a, d, d, tiled=True)
        return y.reshape(x.shape)

    def _shard_id(self):
        if self.mesh is None or self.num_shards == 1:
            return jnp.int32(0)
        return self._axes_index(self.sparse_axes)

    def _axes_index(self, axes: Tuple[str, ...]):
        """This device's axis-0-major flat index over ``axes``: its block of
        a tiled ``all_gather`` over them."""
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _smap(self, f, in_specs, out_specs):
        if self.mesh is None:
            return f
        return shard_map(
            f, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )

    # Pspec helpers: local per-device arrays round-trip through shard_map
    # boundaries as rank-1-concatenated globals along the covered axes.
    def _local_spec(self) -> P:
        axes = self._pspec_axes(self.keys_pspec)
        return P(tuple(axes)) if axes else P()

    def _table_pspecs(self) -> EmbeddingTableState:
        axes = self.sparse_axes if len(self.sparse_axes) > 1 else self.sparse_axes[0]
        return EmbeddingTableState(rows=P(axes, None), accum=P(axes))

    def _buffer_pspecs(self) -> DualBuffer:
        # Buffers vary per sparse shard; replicated over psum axes after union.
        return buffer_pspecs(self.sparse_axes)

    def _plan_pspecs(self) -> LookupPlan:
        s = self._local_spec()
        return LookupPlan(inverse=s, slot_of_unique=s, recv_keys=s,
                          buffer_slot=s, overflow=s)

    def _packet_pspecs(self) -> GradPacket:
        s = self._local_spec()
        return GradPacket(keys=s, buffer_slot=s, grads=s)

    def _stack(self, pspec_tree, extra_dims=1):
        """Prefix ``extra_dims`` None axes (stacked micro-batch leading dims)."""
        return jax.tree.map(
            lambda s: P(*(None,) * extra_dims + tuple(s)), pspec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    # ==================================================================
    # Device-local building blocks (run inside shard_map)
    # ==================================================================

    def _route_plans(self, kf: jax.Array, dims: EngineDims) -> LookupPlan:
        """Fused routing for an (N, L) key block: one window-wide sort-based
        dedup + owner bucketing pass (no per-micro-batch loop) and ONE key
        All2All covering all N lookup units (DBP stage 3)."""
        n = kf.shape[0]
        uniq = fixed_unique_window(kf, dims.u_max)  # leaves (N, ...)
        buck = bucket_by_owner_window(
            uniq.unique_keys, dims.num_shards, dims.cap, self.spec.rows_per_shard
        )
        # Fused key exchange: (S, N*C) single All2All. send_keys is (N, S, C);
        # lay the N axis out along the per-destination columns.
        send = jnp.moveaxis(buck.send_keys, 0, 1).reshape(
            dims.num_shards, n * dims.cap)
        recv = self._a2a(send).reshape(dims.num_shards, n, dims.cap)
        recv_per_mb = jnp.moveaxis(recv, 1, 0)  # (N, S, C)
        return LookupPlan(
            inverse=uniq.inverse,
            slot_of_unique=buck.slot_of_unique,
            recv_keys=recv_per_mb,
            buffer_slot=self._master_local_idx(recv_per_mb),
            overflow=(uniq.overflow + buck.overflow)[:, None],  # (N, 1)
        )

    def _route_one(self, keys_flat: jax.Array, dims: EngineDims) -> LookupPlan:
        """Single lookup unit (serial mode / serving): the N=1 view of the
        same fused window route."""
        plans = self._route_plans(keys_flat[None], dims)
        return jax.tree.map(lambda x: x[0], plans)

    def _route_window_local(self, keys: jax.Array, dims: EngineDims) -> WindowPlan:
        """Route all N micro-batches in one fused pass, then union the
        owner-side key sets (over micro-batches AND replicated axes). The
        union's inverse is each received key's buffer slot: the window
        serves and updates the buffer at those slots, with no search."""
        plans = self._route_plans(keys.reshape(dims.n_micro, -1), dims)

        all_keys = plans.recv_keys.reshape(-1)
        n_local = all_keys.shape[0]
        if self.psum_axes:
            # Union over replicated axes so buffers are replica-identical.
            gathered = jax.lax.all_gather(all_keys, self.psum_axes, tiled=True)
            all_keys = gathered.reshape(-1)
        union = fixed_unique(all_keys, dims.buffer_cap)
        slot = union.inverse
        if self.psum_axes:
            # this device's own block of the gathered keys
            start = self._axes_index(self.psum_axes) * n_local
            slot = jax.lax.dynamic_slice_in_dim(slot, start, n_local)
        plans = plans._replace(buffer_slot=slot.reshape(plans.recv_keys.shape))
        return WindowPlan(plans, union.unique_keys)

    def _serve_rows(self, rows_src: jax.Array, local_idx: jax.Array,
                    shape: Tuple[int, ...]) -> jax.Array:
        served = dispatch.gather_rows(rows_src, local_idx.reshape(-1),
                                      backend=self.kernel_backend)
        return served.reshape(*shape, rows_src.shape[-1]).astype(self.compute_dtype)

    def _master_local_idx(self, recv_keys: jax.Array) -> jax.Array:
        shard_id = self._shard_id()
        valid = recv_keys != SENTINEL
        return jnp.where(
            valid, recv_keys - shard_id * self.spec.rows_per_shard,
            self.spec.rows_per_shard,
        )

    def _assemble(self, plan: LookupPlan, served: jax.Array) -> jax.Array:
        back = self._a2a(served)  # (S, C, D)
        flat = back.reshape(-1, back.shape[-1])
        unique_emb = dispatch.gather_rows(flat, plan.slot_of_unique,
                                          backend=self.kernel_backend)
        return dispatch.gather_rows(unique_emb, plan.inverse,
                                    backend=self.kernel_backend)  # (L, D)

    def _grads_out(self, plan: LookupPlan, demb: jax.Array, dims: EngineDims) -> GradPacket:
        """Source-side segment-sum to uniques + gradient All2All to owners."""
        uniq_grads = dispatch.segment_rowsum(demb, plan.inverse, dims.u_max,
                                             backend=self.kernel_backend)
        send = jnp.zeros((dims.num_shards * dims.cap, demb.shape[-1]), jnp.float32)
        send = send.at[plan.slot_of_unique].set(uniq_grads, mode="drop")
        recv = self._a2a(send.reshape(dims.num_shards, dims.cap, -1))
        return GradPacket(keys=plan.recv_keys, buffer_slot=plan.buffer_slot,
                          grads=recv)

    def _window_grads_to_buffer_space(
        self, buffer_slot: jax.Array, grads: jax.Array, num_rows: int
    ) -> jax.Array:
        """Segment all window packets into buffer space (by each key's
        buffer slot from routing) and combine replicas."""
        flat_grads = grads.reshape(-1, grads.shape[-1])
        total = dispatch.segment_rowsum(
            flat_grads, buffer_slot.reshape(-1), num_rows,
            backend=self.kernel_backend)  # (K, D) f32
        if self.psum_axes:
            total = jax.lax.psum(total, self.psum_axes)
        return total

    def _rowwise_adagrad(self, rows, accum, total, touched):
        new_accum = accum + jnp.where(touched, jnp.mean(total * total, -1), 0.0)
        scale = self.sparse_lr / (jnp.sqrt(jnp.maximum(new_accum, 0.0)) + self.sparse_eps)
        new_rows = rows - (jnp.where(touched, scale, 0.0)[:, None] * total).astype(rows.dtype)
        return new_rows, new_accum

    # ==================================================================
    # Public jittable ops
    # ==================================================================

    def route_window(self, keys: jax.Array, n_micro: int) -> WindowPlan:
        """DBP stage 3 for a whole window. ``keys``: (N, *batch_shape) global."""
        dims = self.dims(keys.shape[1:], n_micro)
        in_spec = self._stack(self.keys_pspec)
        out_specs = WindowPlan(
            plans=self._stack(self._plan_pspecs()),
            buffer_keys=self._buffer_pspecs().keys,
        )
        f = self._smap(
            lambda k: self._route_window_local(k, dims), (in_spec,), out_specs
        )
        return f(keys)

    def retrieve(self, table: EmbeddingTableState, window: WindowPlan) -> DualBuffer:
        """DBP stage 4a: owner-side gather master rows + adagrad state into a
        fresh prefetch buffer."""
        t_specs = self._table_pspecs()
        b_specs = self._buffer_pspecs()

        def _f(rows, accum, bkeys):
            local_idx = self._master_local_idx(bkeys)
            # master precision: the buffer is updated and written back, so
            # it must not pass through the compute dtype (_serve_rows)
            brows = dispatch.gather_rows(rows, local_idx,
                                         backend=self.kernel_backend)
            baccum = jnp.take(accum, local_idx, mode="fill", fill_value=0.0)
            return DualBuffer(bkeys, brows, baccum)

        f = self._smap(
            _f,
            (t_specs.rows, t_specs.accum, b_specs.keys),
            b_specs,
        )
        return f(table.rows, table.accum, window.buffer_keys)

    def sync_buffers(self, active: DualBuffer, prefetch: DualBuffer) -> DualBuffer:
        """DBP stage 4b — dual-buffer intersection synchronization.

        Rows of the *active* buffer (just updated by batch t-1) overwrite
        matching rows of the *prefetch* buffer (serving batch t), exactly the
        paper's K(B_{t-1}) ∩ K(B_t) copy (Prop. 1)."""
        b_specs = self._buffer_pspecs()

        def _f(ak, ar, aa, pk, pr, pa):
            idx = intersect_sorted(ak, pk)  # (K_p,) -> slot in active or K_a
            hit = idx < ak.shape[0]
            src = jnp.minimum(idx, ak.shape[0] - 1)
            rows = dispatch.buffer_sync(ar, pr, idx, backend=self.kernel_backend)
            accum = jnp.where(hit, aa[src], pa)
            return DualBuffer(pk, rows, accum)

        f = self._smap(_f, tuple(b_specs) + tuple(b_specs), b_specs)
        return f(*active, *prefetch)

    def lookup_from_buffer(
        self, buffer: DualBuffer, plan: LookupPlan, keys_shape: Tuple[int, ...],
        n_micro: int,
    ) -> jax.Array:
        """FWP forward for one micro-batch: embedding All2All served from the
        (synced) buffer. Returns local embeddings (*keys_shape, D)."""
        b_specs = self._buffer_pspecs()
        p_specs = self._plan_pspecs()
        out_spec = P(*tuple(self.keys_pspec) + (None,))

        def _f(br, *plan_leaves):
            plan_l = LookupPlan(*plan_leaves)
            served = self._serve_rows(br, plan_l.buffer_slot,
                                      plan_l.recv_keys.shape)
            emb = self._assemble(plan_l, served)
            return emb.reshape(*[s for s in self._local_shape(keys_shape)], -1)

        f = self._smap(_f, (b_specs.rows,) + tuple(p_specs), out_spec)
        return f(buffer.rows, *plan)

    def lookup_from_master(
        self, table: EmbeddingTableState, keys: jax.Array
    ) -> Tuple[jax.Array, LookupPlan]:
        """Serial-mode lookup straight from the master table (baseline path;
        also used for serving)."""
        dims = self.dims(keys.shape, 1)
        t_specs = self._table_pspecs()
        out_specs = (P(*tuple(self.keys_pspec) + (None,)), self._plan_pspecs())

        def _f(rows, accum, k):
            plan = self._route_one(k.reshape(-1), dims)
            served = self._serve_rows(rows, plan.buffer_slot,
                                      plan.recv_keys.shape)
            emb = self._assemble(plan, served)
            return emb.reshape(*self._local_shape(keys.shape), -1), plan

        f = self._smap(_f, (t_specs.rows, t_specs.accum, self.keys_pspec), out_specs)
        return f(table.rows, table.accum, keys)

    def _local_shape(self, keys_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        if self.mesh is None:
            return tuple(keys_shape)
        out = []
        pspec = tuple(self.keys_pspec) + (None,) * (len(keys_shape) - len(self.keys_pspec))
        for dim, entry in zip(keys_shape, pspec):
            sh = 1
            if entry is not None:
                for a in entry if isinstance(entry, (tuple, list)) else (entry,):
                    sh *= self.mesh.shape[a]
            out.append(dim // sh)
        return tuple(out)

    def grads_to_owner(
        self, plan: LookupPlan, demb: jax.Array, keys_shape: Tuple[int, ...],
        n_micro: int,
    ) -> GradPacket:
        """FWP backward for one micro-batch: gradient All2All to owners."""
        dims = self.dims(keys_shape, n_micro)
        p_specs = self._plan_pspecs()
        demb_spec = P(*tuple(self.keys_pspec) + (None,))

        def _f(*leaves):
            *plan_leaves, g = leaves
            return self._grads_out(LookupPlan(*plan_leaves),
                                   g.reshape(-1, g.shape[-1]), dims)

        f = self._smap(_f, tuple(p_specs) + (demb_spec,),
                       self._packet_pspecs())
        return f(*plan, demb)

    def apply_window_to_buffer(
        self, buffer: DualBuffer, packets: GradPacket
    ) -> DualBuffer:
        """Frozen-window end: aggregate all packets by buffer slot, psum across
        replicas, apply rowwise adagrad once to the active buffer."""
        b_specs = self._buffer_pspecs()
        pkt_specs = self._stack(self._packet_pspecs())

        def _f(bk, br, ba, pslot, pgrads):
            total = self._window_grads_to_buffer_space(pslot, pgrads,
                                                       bk.shape[0])
            touched = jnp.any(total != 0.0, axis=-1)
            # Count-based touched is wrong for exactly-zero grads; that only
            # skips a zero update, which is a no-op anyway.
            rows, accum = self._rowwise_adagrad(br, ba, total, touched)
            return DualBuffer(bk, rows, accum)

        f = self._smap(
            _f, tuple(b_specs) + (pkt_specs.buffer_slot, pkt_specs.grads),
            b_specs)
        return f(*buffer, packets.buffer_slot, packets.grads)

    def writeback(self, table: EmbeddingTableState, buffer: DualBuffer) -> EmbeddingTableState:
        """DBP epilogue: scatter updated buffer rows back to the master shard."""
        t_specs = self._table_pspecs()
        b_specs = self._buffer_pspecs()

        def _f(rows, accum, bk, br, ba):
            local_idx = self._master_local_idx(bk)
            rows = rows.at[local_idx].set(br.astype(rows.dtype), mode="drop")
            accum = accum.at[local_idx].set(ba, mode="drop")
            return EmbeddingTableState(rows, accum)

        f = self._smap(_f, tuple(t_specs) + tuple(b_specs), t_specs)
        return f(table.rows, table.accum, *buffer)

    def apply_packets_to_master(
        self, table: EmbeddingTableState, packets: GradPacket
    ) -> EmbeddingTableState:
        """Serial-mode update: window packets -> shard row space (replica
        aligned) -> rowwise adagrad. Used by the non-DBP baseline."""
        t_specs = self._table_pspecs()
        pkt_specs = self._stack(self._packet_pspecs())

        def _f(rows, accum, pkeys, pgrads):
            local_idx = self._master_local_idx(pkeys).reshape(-1)
            flat = pgrads.reshape(-1, pgrads.shape[-1])
            total = dispatch.segment_rowsum(flat, local_idx,
                                            self.spec.rows_per_shard,
                                            backend=self.kernel_backend)
            if self.psum_axes:
                total = jax.lax.psum(total, self.psum_axes)
            touched = jnp.any(total != 0.0, axis=-1)
            new_rows, new_accum = self._rowwise_adagrad(rows, accum, total, touched)
            return EmbeddingTableState(new_rows, new_accum)

        f = self._smap(_f, tuple(t_specs) + (pkt_specs.keys, pkt_specs.grads),
                       t_specs)
        return f(table.rows, table.accum, packets.keys, packets.grads)

    # -- metrics --------------------------------------------------------

    def overflow_metric(self, plan_or_window) -> jax.Array:
        """Global max overflow across devices (must stay 0)."""
        ovf = (
            plan_or_window.plans.overflow
            if isinstance(plan_or_window, WindowPlan)
            else plan_or_window.overflow
        )
        return jnp.max(ovf)

    def buffer_keys_valid(self, buffer: DualBuffer) -> jax.Array:
        """The buffer's non-sentinel keys, summed over shards: the window's
        unique keys, of the ``buffer.keys.shape[0]`` rows that retrieval
        gathers and the dual-buffer sync copies."""
        return jnp.sum(buffer.keys != SENTINEL, dtype=jnp.int32)
