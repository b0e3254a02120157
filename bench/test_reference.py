"""The float32 reference against ``Session.train`` at the program's
``*-reduced`` presets (float32 compute there, so the two agree to float32
rounding), on the CPU."""
from __future__ import annotations

import json
import os

import pytest

from bench import check, generator, program, reference, spec

HERE = os.path.dirname(os.path.abspath(__file__))
# float32 rounding of a different summation order, over three steps
TOL = {"loss_gap": 1e-5, "grad_gap": 1e-5, "change_gap": 1e-4,
       "routing_overflow": 0.0}


def preset_cell(name: str, batch: int = 16) -> spec.Cell:
    """A cell at the program's reduced preset ``name``, with the bench
    settings of the configuration file of the preset's backbone."""
    program.import_program()
    from repro.configs.registry import RECSYS_ARCHS, get_arch

    pre = next(a.reduced for a in map(get_arch, RECSYS_ARCHS)
               if a.reduced.name == name)
    for fname in sorted(os.listdir(os.path.join(HERE, "configs"))):
        with open(os.path.join(HERE, "configs", fname)) as f:
            cfg = json.load(f)
        if cfg["backbone"] == pre.backbone:
            break
    else:
        raise LookupError(f"no configuration of backbone {pre.backbone!r}")
    cfg.update(
        d_model=pre.d_model, n_layers=pre.n_layers, n_heads=pre.n_heads,
        d_ff=pre.d_ff, seq_len=pre.seq_len, norm_eps=pre.norm_eps,
        compute_dtype=pre.compute_dtype, param_dtype=pre.param_dtype,
        tables=[{"name": t.name, "vocab_size": t.vocab_size, "dim": t.dim}
                for t in pre.tables])
    traffic = {"store": "device", "mesh": [1, 1], "batch_per_chip": batch,
               "zipf_a": 1.2, "fwp_microbatches": 4}
    return spec.Cell(name=name, chips=1, config=cfg, traffic=traffic,
                     cell={"ref_steps": 3, "ref_block": 4, "limits": TOL},
                     end_to_end=[], per_layer=[])


def program_readings(cell: spec.Cell, seed: int):
    sess = program.build_session(cell, seed)
    init = program.Initial(sess, cell, seed)
    sess.state = init.state()
    with program.bench_stream(sess, cell, seed):
        return program.check_steps(sess, cell, init)


@pytest.mark.parametrize("preset", ["hstu-reduced", "fuxi-reduced"])
def test_reference_matches_session_train(preset):
    cell = preset_cell(preset)
    assert cell.config["compute_dtype"] == "float32"
    seed = 2**31 + 17
    prog = program_readings(cell, seed)
    ref = reference.train(seed, cell.config, cell.traffic, 1, steps=3,
                          block=4)
    ok, checks = check.verdict(check.numbers(prog, ref), TOL)
    assert ok, checks
    # the leaves compared are the same, and every one of them moved
    assert set(prog["change_norms"]) == set(ref["change_norms"])
    assert all(v > 0 for v in ref["change_norms"].values())


def test_blocks_do_not_change_the_result():
    cell = preset_cell("hstu-reduced", batch=8)
    a = reference.train(5, cell.config, cell.traffic, 1, steps=2, block=8)
    b = reference.train(5, cell.config, cell.traffic, 1, steps=2, block=2)
    assert a["losses"] == pytest.approx(b["losses"], rel=1e-6)
    for k in a["change_norms"]:
        assert a["change_norms"][k] == pytest.approx(b["change_norms"][k],
                                                     rel=1e-4)


def test_batches_depend_on_seed_and_step_only():
    kw = dict(batch=4, seq_len=8, n_items=1000, zipf_a=1.2)
    a = generator.item_batch(2**33 + 1, 7, **kw)
    assert (a == generator.item_batch(2**33 + 1, 7, **kw)).all()
    assert not (a == generator.item_batch(2**33 + 1, 8, **kw)).all()
    assert not (a == generator.item_batch(2**33 + 2, 7, **kw)).all()
    assert a.min() >= 0 and a.max() < 1000
