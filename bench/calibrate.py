"""Readings that the limits of ``correct`` are set from (not part of a run).

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--control 3]

For each seed, in one process: the program's first three steps
(``program.check_steps``) against the float32 reference, which is what a
run compares. For the first ``--control`` seeds also the control (the
reference computed with float8 operands in the program's place) and the
planted faults that can be read in the reference put in the program's
place: half of the batch left out with the mean taken over the rest, and
an eighth of the batch served the wrong rows (each id's next row). (A
step that returns its state unchanged reads 1 on ``change_gap`` by
construction.) One JSON line per seed and reading on standard output;
the last line sums them up: the largest program reading and the smallest
control and fault readings of each number.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

if __name__ == "__main__":
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path = [p for p in sys.path if os.path.abspath(p or ".") != _here]
    sys.path.insert(0, os.path.dirname(_here))

from bench import check, program, reference, spec  # noqa: E402
from bench.run import find_devices, log, use_compile_cache  # noqa: E402


# the reference's options for each control or fault, given the cell
VARIANTS = {
    "control_fp8": lambda cell: dict(precision="fp8"),
    "fault_half_batch": lambda cell: dict(keep=cell.global_batch // 2),
    "fault_rows_altered": lambda cell: dict(alter=True),
}


def readings(cell: spec.Cell, seeds, n_control: int, require_chip=True,
             kinds=("program",) + tuple(VARIANTS)):
    program.import_program()
    if require_chip:
        use_compile_cache()
    find_devices(cell, require_chip)
    steps, block = cell.cell["ref_steps"], cell.cell["ref_block"]
    out = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if "program" in kinds:
            sess = program.build_session(cell, seed)
            init = program.Initial(sess, cell, seed)
            sess.state = init.state()
            with program.bench_stream(sess, cell, seed):
                prog = program.check_steps(sess, cell, init)
            del sess, init
            gc.collect()
        t1 = time.perf_counter()
        ref = reference.train(seed, cell.config, cell.traffic, cell.chips,
                              steps=steps, block=block)
        t2 = time.perf_counter()
        if "program" in kinds:
            row = {"seed": seed, "kind": "program",
                   **check.numbers(prog, ref), "program_s": t1 - t0,
                   "reference_s": t2 - t1, "losses": prog["losses"],
                   "ref_losses": ref["losses"]}
            out.append(row)
            print(json.dumps(row), flush=True)
        if i >= n_control:
            continue
        for kind, options in VARIANTS.items():
            if kind not in kinds:
                continue
            other = reference.train(seed, cell.config, cell.traffic,
                                    cell.chips, steps=steps, block=block,
                                    **options(cell))
            other["overflow"] = 0
            row = {"seed": seed, "kind": kind, **check.numbers(other, ref)}
            out.append(row)
            print(json.dumps(row), flush=True)
    summary = {}
    for kind in sorted({r["kind"] for r in out}):
        rows = [r for r in out if r["kind"] == kind]
        agg = max if kind == "program" else min
        summary[kind] = {k: agg(r[k] for r in rows) for k in check.NUMBERS}
        summary[kind]["seeds"] = len(rows)
    return out, summary


# a step that returns its state unchanged leaves Adam's moment, the Adagrad
# accumulator and every leaf where they started: it reads 1 on these
STATE_UNCHANGED = {"grad_gap": 1.0, "change_gap": 1.0}
EXACT = {"routing_overflow": 0.0}


def limits_from(summary):
    """Each number's limit from its two readings: the lower is the largest
    that the program gave; the upper the least of the control's (where at
    least 3x the lower), each planted fault's (where at least 10x; the
    unchanged state's where at least 3x). The limit lies between them at
    ``lower**0.4 * upper**0.6``, two significant figures up: more room
    above the lower reading than below the upper. A number with no upper
    reading is not compared. Returns ``(limits, not_compared, why)``."""
    prog = summary["program"]
    limits, not_compared, why = dict(EXACT), [], {}
    for k in check.NUMBERS:
        if k in EXACT:
            continue
        lower = prog[k]
        uppers = {}
        for kind, s in summary.items():
            need = 3 if kind.startswith("control") else 10
            if kind != "program" and s[k] >= need * lower:
                uppers[kind] = s[k]
        if k in STATE_UNCHANGED and STATE_UNCHANGED[k] >= 3 * lower:
            uppers["fault_state_unchanged"] = STATE_UNCHANGED[k]
        why[k] = {"lower": lower, "uppers": uppers}
        if not uppers:
            not_compared.append(k)
            continue
        mid = lower ** 0.4 * min(uppers.values()) ** 0.6
        e = 10.0 ** (math.floor(math.log10(mid)) - 1)
        limits[k] = float(f"{math.ceil(mid / e) * e:.2g}")
    return limits, not_compared, why


def failed_by(summary, limits):
    """For the control and each fault, the numbers it fails at ``limits``
    (each has to fail one)."""
    out = {kind: [k for k, lim in limits.items() if s[k] > lim]
           for kind, s in summary.items() if kind != "program"}
    out["fault_state_unchanged"] = [k for k, v in STATE_UNCHANGED.items()
                                    if k in limits and v > limits[k]]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--kinds", nargs="+", default=None,
                   choices=("program",) + tuple(VARIANTS))
    p.add_argument("--write-limits", action="store_true",
                   help="set the cell's limits from these readings "
                        "(limits_from) in its workloads/<cell>.json")
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)
    kw = {"kinds": tuple(args.kinds)} if args.kinds else {}
    _, summary = readings(cell, args.seeds, args.control, **kw)
    log(json.dumps(summary))
    print(json.dumps({"summary": summary}))
    if args.write_limits:
        limits, not_compared, why = limits_from(summary)
        path = os.path.join(spec.BENCH_DIR, "workloads", cell.name + ".json")
        with open(path) as f:
            entry = json.load(f)
        entry.update(limits=limits, not_compared=not_compared)
        with open(path, "w") as f:
            json.dump(entry, f)
            f.write("\n")
        print(json.dumps({"limits": limits, "not_compared": not_compared,
                          "readings": why,
                          "failed_by": failed_by(summary, limits)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
