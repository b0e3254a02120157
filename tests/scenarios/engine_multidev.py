"""8-device engine validation: LM mode (2 data x 4 model) + recsys flat (8)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs.base import NestPipeConfig
from repro.core.embedding import (
    EmbeddingEngine, init_table_state, make_mega_table_spec,
)

def run_case(name, mesh, sparse_axes, keys_pspec, keys_shape):
    S = 1
    for a in sparse_axes:
        S *= mesh.shape[a]
    V, D, N = 256, 16, 2
    spec = make_mega_table_spec(None, vocab_size=V, dim=D, num_shards=S)
    table = init_table_state(jax.random.PRNGKey(0), spec, mesh, sparse_axes)
    cfg = NestPipeConfig(bucket_slack=float(S), unique_capacity_factor=1.0)
    eng = EmbeddingEngine(spec, mesh, sparse_axes, keys_pspec, cfg,
                          compute_dtype=jnp.float32)

    rng = np.random.default_rng(1)
    kw_raw = rng.integers(0, V, size=(N,) + keys_shape).astype(np.int32)
    kw = np.asarray(spec.scramble(jnp.asarray(kw_raw)))
    kw_dev = jax.device_put(jnp.asarray(kw), NamedSharding(mesh, P(*(None,) + tuple(keys_pspec))))

    window = jax.jit(lambda k: eng.route_window(k, N))(kw_dev)
    assert int(jnp.max(window.plans.overflow)) == 0, "routing overflow"
    buf = jax.jit(eng.retrieve)(table, window)

    rows_np = np.asarray(table.rows)
    packets = []
    demb_val = 0.01
    for i in range(N):
        pl = jax.tree.map(lambda x: x[i], window.plans)
        emb = eng.lookup_from_buffer(buf, pl, keys_shape, N)
        ok = np.allclose(np.asarray(emb), rows_np[kw[i]], atol=1e-6)
        print(f"  [{name}] mb{i} lookup exact: {ok}")
        assert ok
        demb = jnp.full(keys_shape + (D,), demb_val, jnp.float32)
        packets.append(eng.grads_to_owner(pl, demb, keys_shape, N))
    pkts = jax.tree.map(lambda *xs: jnp.stack(xs), *packets)
    buf2 = eng.apply_window_to_buffer(buf, pkts)
    table2 = eng.writeback(table, buf2)

    # reference rowwise adagrad
    counts = np.zeros(spec.padded_rows)
    for k in kw.reshape(-1):
        counts[k] += 1.0
    g = counts[:, None] * demb_val
    g2 = np.mean(g * g, axis=1)
    touched = counts > 0
    accum_ref = np.where(touched, g2, 0)
    scale = 0.05 / (np.sqrt(accum_ref) + 1e-8)
    rows_ref = rows_np - np.where(touched, scale, 0)[:, None] * g
    got = np.asarray(table2.rows)
    ok = np.allclose(got, rows_ref, atol=1e-5)
    print(f"  [{name}] window update exact: {ok}  maxdiff={np.abs(got-rows_ref).max():.2e}")
    assert ok

    t3 = eng.apply_packets_to_master(table, pkts)
    ok = np.allclose(np.asarray(t3.rows), rows_ref, atol=1e-5)
    print(f"  [{name}] serial update exact: {ok}")
    assert ok

mesh_lm = jax.make_mesh((2, 4), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
# LM: keys (B, T), batch over data, seq over model
run_case("lm", mesh_lm, ("model",), P("data", "model"), (4, 8))
# recsys: flat keys (B*F,), batch over everything
run_case("recsys", mesh_lm, ("data", "model"), P(("data", "model")), (32,))
print("ALL MULTIDEVICE CASES PASS")
