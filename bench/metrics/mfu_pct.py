"""Model FLOP utilisation of the whole step, in %: the model FLOPs of the
samples trained in the traced window (``bench/flops``, recompute not
counted) over the window's length, the cell's chips and the bf16 peak."""


def read(w):
    if w.trace is None or w.peaks is None:
        return None
    flops = w.flops_per_sample * w.samples
    return 100.0 * flops / (w.trace.window_s * w.cell.chips
                            * w.peaks["bf16_flops"])
