"""Share of the dual buffer's rows that the window uses, in %: the
program's count of the buffer's unique keys summed over the window's steps
(``PipelineStats.buffer_keys_valid``), over steps times the buffer's row
capacity K (``PipelineStats.buffer_rows``), which is the rows retrieval
gathers and the dual-buffer sync copies each step. Nothing where the
program keeps no such count."""


def read(w):
    s = w.stats
    rows = getattr(s, "buffer_rows", 0)
    if not rows or not s.losses:
        return None
    return 100.0 * s.buffer_keys_valid / (len(s.losses) * rows)
