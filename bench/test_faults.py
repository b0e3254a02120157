"""A whole run with the program broken underneath reads ``correct: false``.

Each test drives ``run.run_cell`` past its look for a chip, at a tiny
HSTU size on the CPU, with one fault planted in the program: a step that
returns its state unchanged, half of the batch left out with the mean taken
over the rest, or the rows the gather kernel serves altered. A sound run
under the same limits reads ``correct: true``, and the control (the
reference with float8 operands in the program's place) fails the limits
too. The limits are the cell's own (``workloads/<cell>.json``) once they
are set; the tiny configuration computes in float32, where the program
agrees with the reference to rounding.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from bench import check, reference, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "hstu-industrial.device"


def tiny_cell(limits) -> spec.Cell:
    with open(os.path.join(HERE, "configs", "hstu-industrial.json")) as f:
        cfg = json.load(f)
    cfg.update(d_model=64, n_layers=2, n_heads=4, seq_len=32,
               compute_dtype="float32",
               tables=[{"name": "items", "vocab_size": 4096, "dim": 32},
                       {"name": "users", "vocab_size": 64, "dim": 32}])
    traffic = {"store": "device", "mesh": [1, 1], "batch_per_chip": 16,
               "zipf_a": 1.2, "fwp_microbatches": 4}
    return spec.Cell(name="tiny", chips=1, config=cfg, traffic=traffic,
                     cell={"ref_steps": 3, "ref_block": 4, "limits": limits},
                     end_to_end=[], per_layer=[])


# float32 rounding over three steps (as in test_reference.py): what the
# tiny float32 configuration must meet while the cell's limits are unset
F32 = {"loss_gap": 1e-5, "grad_gap": 1e-5, "change_gap": 1e-4,
       "routing_overflow": 0.0}


@pytest.fixture(scope="module")
def limits():
    """The cell's limits where every one is set, else float32 rounding."""
    with open(os.path.join(HERE, "workloads", CELL + ".json")) as f:
        got = json.load(f)["limits"]
    return got if all(got.get(k) is not None for k in check.NUMBERS) else F32


def _state_unchanged(sess):
    fns = sess.fns
    window = fns.window_step

    def frozen(state, buffer, plan, batch):
        _, aux, _ = window(state, buffer, plan, batch)
        return state, aux, buffer

    sess._fns = fns._replace(window_step=frozen)


def _half_batch(sess):
    bundle = sess.workload.bundle
    loss_fn = bundle.loss_fn

    def half(params, emb, mb):
        keep = emb.shape[0] // 2
        return loss_fn(params, emb[:keep],
                       {k: v[:keep] for k, v in mb.items()})

    sess.workload.bundle = dataclasses.replace(bundle, loss_fn=half)


def _rows_altered(monkeypatch):
    from repro.kernels import dispatch

    gather = dispatch.gather_rows

    def altered(rows, idx, **kw):
        # an eighth of the rows served are each the next row's
        k = max(1, idx.shape[0] // 8)
        return gather(rows, idx.at[:k].set((idx[:k] + 1) % rows.shape[0]),
                      **kw)

    monkeypatch.setattr(dispatch, "gather_rows", altered)


def _run(cell, tamper=None):
    return run.run_cell(cell, 2**31 + 99, 0.5, False, require_chip=False,
                        tamper=tamper)


def test_sound_run_is_correct(limits):
    r = _run(tiny_cell(limits))
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "rows_altered"])
def test_fault_reads_incorrect(limits, fault, monkeypatch):
    tamper = {"state_unchanged": _state_unchanged,
              "half_batch": _half_batch}.get(fault)
    if fault == "rows_altered":
        _rows_altered(monkeypatch)
    r = _run(tiny_cell(limits), tamper)
    assert not r["correct"], r["checks"]


def test_control_fails_the_limits(limits):
    cell = tiny_cell(limits)
    ref = reference.train(3, cell.config, cell.traffic, 1, steps=3, block=4)
    ctl = reference.train(3, cell.config, cell.traffic, 1, steps=3, block=4,
                          precision="fp8")
    ok, checks = check.verdict(check.numbers(ctl, ref), limits)
    assert not ok, checks


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr
