"""Host milliseconds per step in the store's stage-6 commit (D2H pull and
master scatter), from its ``commit_ms`` stage timer over the window. Read
on the host tiers only: on the device tier the timer measures dispatch."""


def read(w):
    t = w.store_timers
    if t is None or w.stats.store_tier == "device" or "commit_ms" not in t:
        return None
    return t["commit_ms"] / w.steps
