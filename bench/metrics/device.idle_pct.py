"""Share of the traced window in which no operation runs on a device, in %,
on the cell's idlest device."""
from bench import trace as tr


def read(w):
    t = w.trace
    if t is None:
        return None
    return 100.0 * max(tr.idle_share(t, d) for d in t.devices)
