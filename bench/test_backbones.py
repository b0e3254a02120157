"""Everything the harness knows of one model family is in one module,
``bench/backbones/<backbone>.py``, found by the configuration's
``backbone`` (``spec.backbone``). So a configuration of a new backbone
enters as new files only; the harness's own files name no backbone; and
moving hstu and fuxi into their modules moved none of the reference's
readings (CPU only)."""
from __future__ import annotations

import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import reference, spec
from bench.test_reference import preset_cell

HERE = os.path.dirname(os.path.abspath(__file__))

# reference.train(SEED, <preset_cell(preset)>, 1 chip, steps=3, block=4) on
# the CPU at commit b230b9c4e22609f098bc386ddb550aa711575888, where the
# layer equations of both backbones were still inside reference.py
SEED = 2**31 + 17
PARENT_READINGS = {
    "hstu-reduced": {
        "losses": [5.777541160583496, 5.686675071716309, 5.459817409515381],
        "grad_norms": {
            "final_norm/bias": 0.005815634038299322,
            "final_norm/scale": 0.006422529928386211,
            "in_proj": 0.9710653424263,
            "layers/norm/bias[0]": 0.051668696105480194,
            "layers/norm/bias[1]": 0.01183803379535675,
            "layers/norm/scale[0]": 0.019387084990739822,
            "layers/norm/scale[1]": 0.007884836755692959,
            "layers/out_norm/bias[0]": 0.14636950194835663,
            "layers/out_norm/bias[1]": 0.006425632629543543,
            "layers/out_norm/scale[0]": 0.008032510988414288,
            "layers/out_norm/scale[1]": 0.0050627002492547035,
            "layers/w_o[0]": 0.06745529919862747,
            "layers/w_o[1]": 0.0436648353934288,
            "layers/w_uvqk[0]": 0.1438821256160736,
            "layers/w_uvqk[1]": 0.07067269086837769,
            "table": 58.140933990478516,
        },
        "change_norms": {
            "final_norm/bias": 0.005514462012797594,
            "final_norm/scale": 0.0053619579412043095,
            "in_proj": 0.027125567197799683,
            "layers/norm/bias[0]": 0.004204960074275732,
            "layers/norm/bias[1]": 0.005083907395601273,
            "layers/norm/scale[0]": 0.0049872491508722305,
            "layers/norm/scale[1]": 0.004540196619927883,
            "layers/out_norm/bias[0]": 0.005245883017778397,
            "layers/out_norm/bias[1]": 0.004803417716175318,
            "layers/out_norm/scale[0]": 0.004877167753875256,
            "layers/out_norm/scale[1]": 0.005310055334120989,
            "layers/w_o[0]": 0.03763455152511597,
            "layers/w_o[1]": 0.040216732770204544,
            "layers/w_uvqk[0]": 0.07535795867443085,
            "layers/w_uvqk[1]": 0.07633136212825775,
            "table": 5.838911056518555,
        },
    },
    "fuxi-reduced": {
        "losses": [5.063265800476074, 5.458962440490723, 5.867110252380371],
        "grad_norms": {
            "final_norm/scale": 0.017669595777988434,
            "in_proj": 0.9549543857574463,
            "layers/attn/wk[0]": 0.004054113291203976,
            "layers/attn/wk[1]": 0.0022356652189046144,
            "layers/attn/wo[0]": 0.10901277512311935,
            "layers/attn/wo[1]": 0.023643549531698227,
            "layers/attn/wq[0]": 0.0035452747251838446,
            "layers/attn/wq[1]": 0.002901920583099127,
            "layers/attn/wv[0]": 0.1018957868218422,
            "layers/attn/wv[1]": 0.02380242943763733,
            "layers/norm1/scale[0]": 0.013748649507761002,
            "layers/norm1/scale[1]": 0.003864995902404189,
            "layers/norm2/scale[0]": 0.015022125095129013,
            "layers/norm2/scale[1]": 0.011002265848219395,
            "layers/w_down[0]": 0.1661170870065689,
            "layers/w_down[1]": 0.11986769735813141,
            "layers/w_fi0[0]": 0.01713644526898861,
            "layers/w_fi0[1]": 0.014539649710059166,
            "layers/w_fi1[0]": 0.018685979768633842,
            "layers/w_fi1[1]": 0.014038355089724064,
            "layers/w_fi2[0]": 0.018258316442370415,
            "layers/w_fi2[1]": 0.014631611295044422,
            "layers/w_up[0]": 0.1067124754190445,
            "layers/w_up[1]": 0.09389113634824753,
            "table": 52.53254318237305,
        },
        "change_norms": {
            "final_norm/scale": 0.004841632675379515,
            "in_proj": 0.027236010879278183,
            "layers/attn/wk[0]": 0.03623300418257713,
            "layers/attn/wk[1]": 0.03844733536243439,
            "layers/attn/wo[0]": 0.03985969349741936,
            "layers/attn/wo[1]": 0.03926748037338257,
            "layers/attn/wq[0]": 0.03607822209596634,
            "layers/attn/wq[1]": 0.039206117391586304,
            "layers/attn/wv[0]": 0.04009471461176872,
            "layers/attn/wv[1]": 0.03896549344062805,
            "layers/norm1/scale[0]": 0.005035319831222296,
            "layers/norm1/scale[1]": 0.0047435699962079525,
            "layers/norm2/scale[0]": 0.005049224477261305,
            "layers/norm2/scale[1]": 0.0050685168243944645,
            "layers/w_down[0]": 0.05514311417937279,
            "layers/w_down[1]": 0.05472969263792038,
            "layers/w_fi0[0]": 0.077705979347229,
            "layers/w_fi0[1]": 0.07765946537256241,
            "layers/w_fi1[0]": 0.0780172348022461,
            "layers/w_fi1[1]": 0.07759165018796921,
            "layers/w_fi2[0]": 0.07802394032478333,
            "layers/w_fi2[1]": 0.07754770666360855,
            "layers/w_up[0]": 0.05557422339916229,
            "layers/w_up[1]": 0.055862098932266235,
            "table": 5.893096923828125,
        },
    },
}


@pytest.mark.parametrize("preset", ["hstu-reduced", "fuxi-reduced"])
def test_reference_readings_did_not_move(preset):
    cell = preset_cell(preset)
    got = reference.train(SEED, cell.config, cell.traffic, 1, steps=3,
                          block=4)
    assert got == PARENT_READINGS[preset]


def test_unknown_backbone_is_a_spec_error():
    with pytest.raises(spec.SpecError, match=r"bench/backbones/no_such\.py"):
        spec.backbone({"backbone": "no_such"})


@pytest.mark.parametrize("name", ["reference.py", "program.py",
                                  "generator.py", "spec.py", "run.py",
                                  "calibrate.py", "check.py", "trace.py"])
def test_harness_file_names_no_backbone(name):
    with open(os.path.join(HERE, name)) as f:
        text = f.read().lower()
    for word in ("hstu", "fuxi", "backbone =="):
        assert word not in text, (name, word)


# run in a copy of the harness (its root on the path in place of this one),
# with the program's package from this checkout
DRIVE = """
import json
from bench import check, program, reference, spec
from bench.test_reference import TOL

cell = spec.load_cell("toy-lm.tiny")
seed = 2**31 + 5
ref = reference.train(seed, cell.config, cell.traffic, cell.chips,
                      steps=cell.cell["ref_steps"],
                      block=cell.cell["ref_block"])
program.import_program()
sess = program.build_session(cell, seed)
init = program.Initial(sess, cell, seed)
sess.state = init.state()
with program.bench_stream(sess, cell, seed):
    prog = program.check_steps(sess, cell, init)
ok, checks = check.verdict(check.numbers(prog, ref), TOL)
print(json.dumps({"root": spec.ROOT, "correct": ok, "checks": checks,
                  "flops": spec.flops_per_sample(cell.config),
                  "ref": ref, "prog_leaves": sorted(prog["change_norms"])}))
"""


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_a_new_backbone_is_new_files_only(tmp_path):
    """A tiny dense decoder on the program's ``lm`` kind (test data under
    ``testdata/toy-lm/``) trains through the harness with nothing added to
    a copy of it but its own files and ``BENCHMARK.json`` entries, and
    agrees with its reference at float32 rounding."""
    root = str(tmp_path)
    harness = sorted(glob.glob(os.path.join(HERE, "*.py")))
    os.makedirs(os.path.join(root, "bench"))
    for path in harness:
        shutil.copy(path, os.path.join(root, "bench"))
    data = os.path.join(HERE, "testdata", "toy-lm")
    for sub in ("configs", "traffic", "workloads", "backbones", "flops"):
        shutil.copytree(os.path.join(data, sub),
                        os.path.join(root, "bench", sub))
    bench = spec.benchmark()
    with open(os.path.join(data, "benchmark.json")) as f:
        added = json.load(f)
    for key in ("configs", "workloads"):
        bench[key] = bench[key] + added[key]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    os.symlink(os.path.join(spec.ROOT, "src"), os.path.join(root, "src"))

    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-6000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["root"] == root
    assert got["correct"], got["checks"]
    assert got["flops"] > 0
    # the leaves compared are the same, the blocks split per layer, and
    # every one of them moved
    ref = got["ref"]
    assert got["prog_leaves"] == sorted(ref["change_norms"])
    assert "blocks/0/attn/wq[0]" in ref["change_norms"]
    assert all(v > 0 for v in ref["change_norms"].values())
    for path in harness:
        copy = os.path.join(root, "bench", os.path.basename(path))
        assert _digest(copy) == _digest(path), path
