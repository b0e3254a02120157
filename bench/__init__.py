"""On-chip benchmark of NestPipe training (see ``BENCHMARK.json``).

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the chips of the machine it is started on. Everything that
belongs to one configuration, traffic mix, cell or per-layer metric lives in
a file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the model as run (published widths, table rows
  cut to the chips' share of the stated deployment);
- ``backbones/<backbone>.py``: all the harness knows of one model family
  (the reference's weights, equations and loss, its batches, the program's
  ``ArchSpec``), found by the configuration's ``backbone``
  (:func:`bench.spec.backbone`);
- ``traffic/<traffic>.json``: the training job (per-chip batch, key skew,
  store tier, mesh), read by the backbone's ``batch`` and
  :mod:`bench.generator`;
- ``workloads/<cell>.json``: the cell's limits for ``correct`` and the size
  of the reference's row blocks;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``flops/<backbone>.py``: model FLOPs per sample, counted from shapes;
- ``peaks.json``: the peak table, keyed by ``device_kind``.

``testdata/`` holds files of the tests, not of the benchmark.
"""
