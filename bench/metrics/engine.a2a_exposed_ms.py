"""Device milliseconds per step in which an all-to-all runs on a device and
nothing else does, on the device where that is longest: the part of the
key and row exchange that FWP did not hide."""
from bench import trace as tr


def _is_a2a(o):
    return "all-to-all" in o.name or "all_to_all" in o.name


def read(w):
    t = w.trace
    if t is None:
        return None
    worst = None
    for d in t.devices:
        a2a = tr.union(tr.clip(o, t.window) for o in t.ops[d] if _is_a2a(o))
        if not a2a:
            continue
        rest = tr.union(tr.clip(o, t.window) for o in t.ops[d]
                        if not _is_a2a(o))
        exposed = tr.length(a2a) - tr.length(tr.intersect(a2a, rest))
        worst = exposed if worst is None else max(worst, exposed)
    return None if worst is None else 1e3 * worst * 1e-9 / w.steps
