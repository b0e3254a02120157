"""Shared arithmetic of the kernel roofline readers (not a metric).

A kernel's calls are found in the trace by the name XLA gives the
instruction (``embedding_gather.3``: the program's jitted wrapper of the
Pallas call), in the operation's name or at the head of its HLO text;
their shapes come from that text.
The share is the least time of the required work at the chip's peaks
(``bench/flops/kernels.py``) over the kernel's device time, summed over
the window's calls and averaged over the cell's devices.
"""
import importlib.util
import os
import re

from bench import trace as tr

_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8)\[([0-9,]*)\]")
ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1}


def work_model():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "flops", "kernels.py")
    spec = importlib.util.spec_from_file_location("bench_flops_kernels", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hlo_text(op):
    """The HLO instruction text the trace keeps for an operation, if any: on
    a TPU the operation's name is that text, on the CPU a stat holds it."""
    for v in (op.name, *op.stats.values()):
        if isinstance(v, str) and "custom-call" in v and "[" in v:
            return v
    return None


def shapes(text):
    """[(dtype, dims)] in order: the result first, then the operands."""
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _SHAPE.findall(text)]


def is_call(op, marker):
    """``op`` is a call named ``marker`` or ``marker.<n>``."""
    names = [op.name]
    text = hlo_text(op)
    if text:
        names.append(text.lstrip().lstrip("%").split(" ", 1)[0])
    return any(n == marker or n.startswith(marker + ".") for n in names)


def roofline_pct(w, marker, work_of):
    """``work_of(shapes) -> {"bytes", "flops"}`` or None for a call whose
    shapes it cannot read, which is an error: the metric would vanish."""
    t = w.trace
    if t is None or w.peaks is None:
        return None
    model = work_model()
    per_dev = []
    for d in t.devices:
        ops = [o for o in t.ops[d] if is_call(o, marker)]
        if not ops:
            continue
        least = spent = 0.0
        for o in ops:
            text = hlo_text(o)
            work = work_of(shapes(text)) if text else None
            if work is None:
                raise ValueError(
                    f"{marker}: the trace has operation {o.name!r} of this "
                    "kernel but no shapes that give its work")
            s, e = tr.clip(o, t.window)
            least += model.least_seconds(work, w.peaks)
            spent += (e - s) * 1e-9
        if spent > 0:
            per_dev.append(100.0 * least / spent)
    return sum(per_dev) / len(per_dev) if per_dev else None
