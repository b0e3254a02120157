"""Mean host wait for the next input batch per step of the window, in ms:
the DBP driver's own clock around its queue read
(``PipelineStats.input_wait_times``, ``core/dbp/pipeline.py``)."""


def read(w):
    waits = w.stats.input_wait_times
    return 1e3 * sum(waits) / len(waits) if waits else None
