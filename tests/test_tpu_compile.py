"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (``tests/test_kernels.py``, ``tests/test_dispatch.py``)
checks what the kernels compute; it cannot see what the chip's compiler
refuses: blocks that break the (8, 128) tiling rule, SMEM or VMEM
overflow, unaligned DMAs. Here each dispatched op is compiled ahead of
time with ``backend="pallas"`` for a described (not attached) v5e chip,
at the shapes one chip runs in ``chip_smoke.py``: hstu-industrial's tables
cut to one chip's share of the 256-worker mesh, the engine's capacities at
the smoke's training batch, and D in {64, 128, 512}. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import NestPipeConfig
from repro.core.embedding.engine import EmbeddingEngine
from repro.core.embedding.table import make_mega_table_spec
from repro.kernels import dispatch

CHIP_SHARE_ROWS = 390_625 + 195_313 + 3_907  # items + users + context
SEQ_LEN = 1024
BATCH = 256  # sequences per chip per step (chip_smoke phase a)
DIMS = (64, 128, 512)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep it out of any cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def engine_dims(dim):
    spec = make_mega_table_spec(None, vocab_size=CHIP_SHARE_ROWS, dim=dim,
                                num_shards=1)
    cfg = NestPipeConfig()
    eng = EmbeddingEngine(spec, None, ("model",), P(None, None), cfg)
    n = cfg.fwp_microbatches
    return eng.dims((BATCH // n, SEQ_LEN), n)


def compile_pallas(sharding, op, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(lambda *a: op(*a, backend="pallas", **kw)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text, f"{op.__name__}: no Pallas kernel"
    return text


def test_capacities_are_the_smoke_shapes():
    d = engine_dims(512)
    assert (d.l_local, d.u_max, d.cap, d.buffer_cap) == (
        65536, 65536, 98304, 393216)


@pytest.mark.parametrize("dim", DIMS)
@pytest.mark.parametrize("site", ["retrieve", "serve"])
def test_gather_rows_f32(one_chip, dim, site):
    """Master rows into the dual buffer (retrieve) and buffer rows to the
    lookup (serve), both at master precision."""
    d = engine_dims(dim)
    rows, n = ((CHIP_SHARE_ROWS, d.buffer_cap) if site == "retrieve"
               else (d.buffer_cap, d.cap))
    text = compile_pallas(one_chip, dispatch.gather_rows,
                          ((rows, dim), jnp.float32), ((n,), jnp.int32))
    if dim % 128 == 0:  # lane-aligned: the table reaches the kernel as is
        whole = f"f32[{rows},{dim}]"
        assert not [l for l in text.splitlines()
                    if " copy(" in l and whole in l], "table copied"


@pytest.mark.parametrize("dim", DIMS)
def test_gather_rows_bf16(one_chip, dim):
    """The assemble gathers over compute-dtype rows: served -> uniques ->
    positions."""
    d = engine_dims(dim)
    compile_pallas(one_chip, dispatch.gather_rows,
                   ((d.cap, dim), jnp.bfloat16), ((d.u_max,), jnp.int32))
    compile_pallas(one_chip, dispatch.gather_rows,
                   ((d.u_max, dim), jnp.bfloat16), ((d.l_local,), jnp.int32))


@pytest.mark.parametrize("dim", DIMS)
@pytest.mark.parametrize("site", ["grads_out", "window"])
def test_segment_rowsum(one_chip, dim, site):
    """Source-side sum of bf16 position grads into uniques (grads_out) and
    the f32 window packets into buffer space (window)."""
    d = engine_dims(dim)
    if site == "grads_out":
        rows, n, segs, dt = d.l_local, d.l_local, d.u_max, jnp.bfloat16
    else:
        rows = n = d.n_micro * d.num_shards * d.cap
        segs, dt = d.buffer_cap, jnp.float32
    compile_pallas(one_chip, dispatch.segment_rowsum,
                   ((rows, dim), dt), ((n,), jnp.int32), num_segments=segs)


@pytest.mark.parametrize("dim", DIMS)
def test_buffer_sync(one_chip, dim):
    d = engine_dims(dim)
    k = d.buffer_cap
    compile_pallas(one_chip, dispatch.buffer_sync, ((k, dim), jnp.float32),
                   ((k, dim), jnp.float32), ((k,), jnp.int32))


def test_window_program_keeps_kernel_names(one_chip):
    """The named scopes of the FWP window (``fwp_sparse`` around the lookup
    and the gradient packets) change metadata only: the compiled window
    program still names its Pallas calls after their jitted wrappers, as
    the benchmark's kernel readers match them."""
    from repro.api import Session

    sess = Session.from_arch(
        "hstu-industrial", mode="nestpipe", reduced=True, global_batch=8,
        seq_len=32, n_micro=2, npcfg=NestPipeConfig(kernel_backend="pallas"))
    batch = {k: jax.ShapeDtypeStruct(*v)
             for k, v in sess.workload.batch_shapes.items()}
    buf, plan = jax.eval_shape(sess.fns.init_carry, sess.state.table,
                               batch["keys"])
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (sess.state, buf, plan, batch))
    text = jax.jit(sess.fns.window_step).lower(*args).compile().as_text()
    calls = [l.split(" = ", 1)[0].strip().lstrip("%")
             for l in text.splitlines() if "tpu_custom_call" in l
             and " = " in l]
    for marker in ("embedding_gather", "segment_rowsum"):
        named = [c for c in calls if re.fullmatch(marker + r"\.\d+", c)]
        assert named, (marker, calls)
    # the scope is in the calls' op_name path, not in their names
    paths = re.findall(r'op_name="([^"]*pallas_call)"', text)
    assert paths and all("fwp_sparse" in p.split("/") for p in paths), paths
