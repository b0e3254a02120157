"""Paper Table IV / RQ5: NestPipe + 2D sparse parallelism, REAL store.

Two `bench_step_latency`-style subprocess mesh cells on 4 simulated CPU
devices (``--xla_force_host_platform_device_count``), both running the
real sharded-host tier end to end:

``table4_nestpipe``
    the flat 1D layout — a (1, 4) mesh, all 4 shards on one sparse axis,
    the stage-3 owner exchange is one global All2All.
``table4_nestpipe_2dsp``
    the 2D layout — a (2, 2) mesh over the same 4 devices; the recsys
    archs' sparse axes default to ALL mesh axes, so ownership factors
    table-group x row (``routing.owner_of_2d``) and the exchange runs as
    two sub-axis All2Alls.

Each cell records the per-axis off-device exchange bytes
(``wire_ax0``/``wire_ax1`` from the store's comm ledger) and two
loss-equality flags: ``loss_equal_device`` (the sharded run replays its
same-mesh DeviceStore run bit for bit) and, on the 2dsp cell,
``loss_equal_1d`` (the (2, 2) trajectory equals the (1, 4) one — same
flat device order, same batch slices, routing-identical exchange). The
honest claim is per axis: the factored exchange's LARGEST hop
(``wire_ax_max``) is strictly below the 1D cell's at equal loss — the
factored TOTAL is never smaller than the flat exchange, so CI asserts
the max-axis comparison and the equality flags, never a latency ratio
(the CPU mesh is a simulation).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Optional

from .common import emit, make_bench_mesh, run_driver

ARCH = "dlrm-cached"
_MARKER = "2DSP_CELLS_JSON:"


def _worker(steps: int, global_batch: int) -> None:
    """Subprocess body (4 forced devices): the 1D and 2x2 sharded-host
    cells plus their same-mesh device twins. Emits one marked JSON line."""
    cells: Dict[str, dict] = {}
    losses: Dict[str, List[float]] = {}
    for cell, grid in (("nestpipe", (1, 4)), ("nestpipe_2dsp", (2, 2))):
        mesh = make_bench_mesh(4, grid=grid)
        _, stats_d, _ = run_driver(ARCH, mode="nestpipe", steps=steps,
                                   n_micro=4, global_batch=global_batch,
                                   store="device", mesh=mesh)
        _, stats, _ = run_driver(ARCH, mode="nestpipe", steps=steps,
                                 n_micro=4, global_batch=global_batch,
                                 store="host", mesh=mesh)
        s = stats.summary()
        losses[cell] = [float(x) for x in stats.losses]
        s["loss_equal_device"] = int(
            losses[cell] == [float(x) for x in stats_d.losses])
        s["wire_ax_max"] = max(s.get("wire_bytes_ax0", 0.0),
                               s.get("wire_bytes_ax1", 0.0))
        cells[cell] = s
    cells["nestpipe_2dsp"]["loss_equal_1d"] = int(
        losses["nestpipe_2dsp"] == losses["nestpipe"])
    print(_MARKER + json.dumps(cells))


def main(argv: Optional[List[str]] = None):
    argv = argv if argv is not None else []
    steps = int(os.environ.get("REPRO_BENCH_STEPS", "8"))
    global_batch = int(os.environ.get("REPRO_BENCH_BATCH", "32")) * 4
    if argv[:1] == ["--2dsp-worker"]:  # subprocess entry
        _worker(int(argv[1]), int(argv[2]))
        return

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    # a simulated CPU mesh by design: never compete with this process for
    # an accelerator it may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_2dsp", "--2dsp-worker",
         str(steps), str(global_batch)],
        capture_output=True, text=True, env=env, cwd=root, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"2dsp subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith(_MARKER)][-1]
    cells = json.loads(line[len(_MARKER):])

    for cell, s in cells.items():
        derived = (
            f"final_loss={s['final_loss']:.4f}"
            f";grid={s['store_shard_grid']}"
            f";loss_equal_device={s['loss_equal_device']}"
            f";wire_ax0={int(s.get('wire_bytes_ax0', 0))}"
            f";wire_ax1={int(s.get('wire_bytes_ax1', 0))}"
            f";wire_ax_max={int(s['wire_ax_max'])}"
            f";wire_bytes={int(s['wire_bytes'])}"
        )
        if "loss_equal_1d" in s:
            derived += f";loss_equal_1d={s['loss_equal_1d']}"
        emit(
            f"table4_{cell}", s["mean_step_s"] * 1e6, derived,
            config={"arch": ARCH, "mode": "nestpipe", "steps": steps,
                    "global_batch": global_batch, "n_micro": 4,
                    "store": "host", "mesh_devices": 4,
                    "grid": s["store_shard_grid"], "reduced": True},
        )


if __name__ == "__main__":
    main(sys.argv[1:])
