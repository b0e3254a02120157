"""8-device scenario: the buffer slot routing hands the window, where the
buffer keys are a union over replicated (psum) axes, equals a binary search
of each device's own buffer keys at every received position."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import NestPipeConfig
from repro.core.embedding import EmbeddingEngine, make_mega_table_spec
from repro.core.embedding.routing import sorted_lookup


def shards_by_device(arr):
    return {s.device: np.asarray(s.data) for s in arr.addressable_shards}


def run_case(name, mesh, sparse_axes, keys_pspec, keys_shape, factor):
    S = 1
    for a in sparse_axes:
        S *= mesh.shape[a]
    V, N = 256, 2
    spec = make_mega_table_spec(None, vocab_size=V, dim=8, num_shards=S)
    cfg = NestPipeConfig(bucket_slack=float(S), unique_capacity_factor=factor)
    eng = EmbeddingEngine(spec, mesh, sparse_axes, keys_pspec, cfg,
                          compute_dtype=jnp.float32)
    assert eng.psum_axes, name
    rng = np.random.default_rng(3)
    kw = np.asarray(spec.scramble(jnp.asarray(
        rng.integers(0, V, size=(N,) + keys_shape).astype(np.int32))))
    kw = jax.device_put(jnp.asarray(kw), NamedSharding(
        mesh, P(*(None,) + tuple(keys_pspec))))
    window = jax.jit(lambda k: eng.route_window(k, N))(kw)
    overflow = int(jnp.max(window.plans.overflow))
    assert (overflow > 0) == (factor < 1.0), (name, overflow)

    bkeys = shards_by_device(window.buffer_keys)
    recv = shards_by_device(window.plans.recv_keys)
    slot = shards_by_device(window.plans.buffer_slot)
    for dev, rk in recv.items():
        want = np.asarray(sorted_lookup(jnp.asarray(bkeys[dev]),
                                        jnp.asarray(rk.reshape(-1))))
        np.testing.assert_array_equal(slot[dev], want.reshape(rk.shape))
    print(f"  [{name}] psum_axes={eng.psum_axes} overflow={overflow}: "
          f"buffer_slot == search on {len(recv)} devices")


auto = jax.sharding.AxisType.Auto
mesh_lm = jax.make_mesh((2, 4), ("data", "model"), axis_types=(auto,) * 2)
# LM: keys (B, T), batch over data (replicated table), seq over model
run_case("lm", mesh_lm, ("model",), P("data", "model"), (4, 8), 1.0)
run_case("lm-overflow", mesh_lm, ("model",), P("data", "model"), (8, 64),
         0.25)
# two replicated axes: the gathered block order is axis-0-major over both
mesh_3d = jax.make_mesh((2, 2, 2), ("a", "b", "c"), axis_types=(auto,) * 3)
run_case("two-psum-axes", mesh_3d, ("c",), P(("a", "b"), "c"), (4, 8), 1.0)
print("BUFFER SLOT OK")
