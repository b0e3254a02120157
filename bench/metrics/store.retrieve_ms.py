"""Host milliseconds per step in the store's stage-4a retrieval (gather and
H2D staging), from its ``retrieve_ms`` stage timer over the window. Read on
the host tiers only: on the device tier the timer measures jit dispatch."""


def read(w):
    t = w.store_timers
    if t is None or w.stats.store_tier == "device" or "retrieve_ms" not in t:
        return None
    return t["retrieve_ms"] / w.steps
