"""Device milliseconds per step of the FWP window program (``window_step``:
the dense forward and backward over the micro-batches, the embedding
exchange inside it, and the optimizer updates), averaged over devices."""
from bench import trace as tr


def read(w):
    t = w.trace
    if t is None:
        return None
    per_dev = [tr.op_seconds(t, d, lambda o: "window_step" in o.module)
               for d in t.devices]
    if not any(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / w.steps
