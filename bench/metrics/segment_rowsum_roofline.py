"""Share of its roofline that the segment-sum kernel reaches, in %: for
each call in the window, the least time of its required bytes (the rows and
ids it reads, the float32 sums it writes) at the HBM peak, summed, over the
device time of its calls; averaged over the cell's devices."""
from bench.metrics import _kernel

MARKER = "segment_rowsum"


def _work(shapes):
    # result (segments, dim) f32; the values are the 2-D operand of width dim
    (_, out), rest = shapes[0], shapes[1:]
    vals = [(t, s) for t, s in rest if len(s) == 2 and s[1] == out[1]]
    if len(out) != 2 or not vals:
        return None
    t, s = vals[-1]
    return _kernel.work_model().segment_rowsum(
        s[0], out[0], out[1], _kernel.ITEMSIZE[t])


def read(w):
    return _kernel.roofline_pct(w, MARKER, _work)
