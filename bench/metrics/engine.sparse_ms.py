"""Device milliseconds per step of the sparse path's programs outside the
FWP window: routing, retrieval, buffer sync and commit, by program name,
averaged over the cell's devices."""
from bench import trace as tr

PROGRAMS = ("route_window", "retrieve", "sync_buffers", "commit")


def read(w):
    t = w.trace
    if t is None:
        return None
    per_dev = [tr.op_seconds(t, d, lambda o: any(p in o.module
                                                 for p in PROGRAMS))
               for d in t.devices]
    return 1e3 * sum(per_dev) / len(per_dev) / w.steps
