"""Share of its roofline that the row-gather kernel reaches, in %: for each
call in the window, the least time of its required bytes (the rows it reads
and writes and its indices) at the HBM peak, summed, over the device time of
its calls; averaged over the cell's devices."""
from bench.metrics import _kernel

MARKER = "embedding_gather"


def _work(shapes):
    # result (n, dim); the table is the first 2-D operand after it
    (out_t, out), rest = shapes[0], shapes[1:]
    tables = [(t, s) for t, s in rest if len(s) == 2 and s[1] == out[1]]
    if len(out) != 2 or not tables:
        return None
    return _kernel.work_model().gather_rows(
        out[0], out[1], _kernel.ITEMSIZE[tables[0][0]],
        _kernel.ITEMSIZE[out_t])


def read(w):
    return _kernel.roofline_pct(w, MARKER, _work)
