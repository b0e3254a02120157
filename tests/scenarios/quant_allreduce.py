"""8-device scenario: quantized ring AllReduce ~= exact psum; error feedback
residual accounts for the quantization gap."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from repro.dist.compressed import ring_allreduce_quant

mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("d",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 133)), jnp.float32)  # one row per device


def f(v):
    v = v.reshape(-1)
    out, res = ring_allreduce_quant(v, "d")
    exact = jax.lax.psum(v, "d")
    return out[None], res[None], exact[None]

out, res, exact = jax.jit(
    shard_map(f, mesh=mesh, in_specs=P("d", None),
              out_specs=(P("d", None), P("d", None), P("d", None)),
              check_vma=False)
)(x)
out, exact = np.asarray(out), np.asarray(exact)
# all devices agree
assert np.allclose(out, out[0:1], atol=1e-6), "devices disagree"
# int8 error is bounded relative to the CHUNK scale, not per element
# (near-zero sums make pointwise relative error meaningless): norm metric.
rel = np.linalg.norm(out[0] - exact[0]) / np.linalg.norm(exact[0])
print("norm rel err:", rel)
assert rel < 0.05, rel
# exact for power-of-two friendly values
y = jnp.ones((8, 64), jnp.float32)
out2, _, exact2 = jax.jit(
    shard_map(f, mesh=mesh, in_specs=P("d", None),
              out_specs=(P("d", None), P("d", None), P("d", None)),
              check_vma=False)
)(y)
assert np.allclose(np.asarray(out2), np.asarray(exact2), atol=1e-4)
print("QUANT ALLREDUCE OK")
