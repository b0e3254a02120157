"""Paper Table III: scaling 8 -> 512 workers.

Spawns subprocess dry-runs (device count locks at jax init, so each mesh
size gets its own process) of the reduced HSTU workload across mesh sizes,
derives per-step time models from the roofline terms:

    t_serial   = t_compute + t_collective            (everything exposed)
    t_nestpipe = t_compute + t_collective / N        (FWP boundary exposure;
                                                      DBP hides lookup)

and reports QPS + scaling factor normalized to the smallest mesh —
the dry-run-level reproduction of the paper's scaling table.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from .common import emit

_SCRIPT = r"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
sys.path.insert(0, r"{src}")
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs.base import NestPipeConfig, ShapeConfig
from repro.launch.dryrun import dryrun_cell

shape_axes = {shape_axes}
mesh = Mesh(np.asarray(jax.devices()[:int(np.prod([s for s,_ in shape_axes]))]).reshape(
    [s for s, _ in shape_axes]), tuple(a for _, a in shape_axes))
per_worker_batch = 64
workers = mesh.devices.size
rec = dryrun_cell("hstu-industrial", "train_rec", mesh=mesh, n_micro=4,
                  reduced=True, verbose=False)
print("RESULT" + json.dumps({{"workers": workers, "roofline": rec["roofline"],
                              "tokens": rec["tokens_per_step"]}}))
"""


def run_mesh(shape_axes):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SCRIPT.format(src=os.path.abspath(src), shape_axes=shape_axes)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # a simulated CPU mesh by design: never compete with the parent for
    # an accelerator it may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=560, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"scaling subprocess failed: {proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT"):
            return json.loads(line[len("RESULT"):])
    raise RuntimeError("no RESULT line")


def main():
    meshes = [
        [(2, "data"), (4, "model")],
        [(4, "data"), (8, "model")],
        [(8, "data"), (16, "model")],
        [(16, "data"), (16, "model")],
    ]
    base_qps = {}
    n_micro = 4
    for shape_axes in meshes:
        r = run_mesh(shape_axes)
        w = r["workers"]
        rl = r["roofline"]
        t_comp, t_coll = rl["compute_s"], rl["collective_s"]
        t_serial = t_comp + t_coll
        t_nest = t_comp + t_coll / n_micro
        for name, t in (("torchrec", t_serial), ("nestpipe", t_nest)):
            qps = r["tokens"] / max(t, 1e-12)
            if (name, "base") not in base_qps:
                base_qps[(name, "base")] = (w, qps)
            w0, q0 = base_qps[(name, "base")]
            scaling = (qps / q0) / (w / w0)
            emit(
                f"table3_scaling_{name}_w{w}",
                t * 1e6,
                f"qps={qps:.3e};scaling_factor={scaling:.3f};"
                f"t_compute_us={t_comp*1e6:.1f};t_coll_us={t_coll*1e6:.1f}",
            )


if __name__ == "__main__":
    main()
