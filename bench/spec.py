"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Nothing here knows a particular configuration, backbone, traffic mix or
metric: each is found by its name, so a new one is new files plus new
``BENCHMARK.json`` entries. A configuration of a backbone the benchmark
already has brings ``configs/<config>.json``; one of a new backbone also
brings ``backbones/<backbone>.py`` (:func:`backbone`) and
``flops/<backbone>.py`` (:func:`flops_per_sample`); a cell brings
``workloads/<cell>.json`` and, for a new mix, ``traffic/<traffic>.json``.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """A cell, file or table entry the benchmark needs is missing or wrong."""


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") from None


def _load_module(path: str, name: str):
    if not os.path.exists(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: Dict[str, Any]  # configs/<config>.json
    traffic: Dict[str, Any]  # traffic/<traffic>.json
    cell: Dict[str, Any]  # workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]  # metric entries this cell reports
    per_layer: List[Dict[str, Any]]

    @property
    def global_batch(self) -> int:
        return int(self.traffic["batch_per_chip"]) * self.chips


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench if bench is not None else benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bdir = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(bdir, "traffic", w["traffic"] + ".json"))
    cell = _read_json(os.path.join(bdir, "workloads", name + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        cell=cell,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """The peak table's entry for ``device_kind``; a kind not in the table
    is an error, never a default."""
    table = _read_json(os.path.join(root, "bench", "peaks.json"))
    kinds = table["devices"]
    if device_kind not in kinds:
        raise SpecError(f"device_kind {device_kind!r} is not in "
                        f"bench/peaks.json (has {sorted(kinds)})")
    return kinds[device_kind]


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``."""
    mod = _load_module(os.path.join(root, "bench", "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_").replace("-", "_"))
    return mod.read


@functools.lru_cache(maxsize=None)
def _backbone(path: str, name: str):
    return _load_module(path, "bench_backbone_" + name.replace("-", "_"))


def backbone(config: Dict[str, Any], root: str = ROOT):
    """The module ``bench/backbones/<backbone>.py`` of the configuration's
    ``backbone``: everything the harness knows of one model family. It has

    - ``param_shapes(cfg, chips)``: the program's dense weights' shapes, by
      name, as the program's tree holds them;
    - ``init_params(key, cfg, chips)`` (optional): the dense weights, where
      ``reference.init_params``'s rule cannot make them;
    - ``losses(params, emb, batch, cfg, ein)``: each sequence's loss, from
      the rows ``emb`` (B, S, D) of the ``ids`` and the batch's further
      fields, with ``ein`` for every matrix product;
    - ``batch(seed, step, cfg, traffic, chips)``: training batch ``step``:
      ``ids`` (B, S) into the first table, and any further field the
      program's loss reads, each with the batch on its first axis;
    - ``program_arch(cfg, chips, traffic)``: the program's ``ArchSpec``;
    - ``stacked(cfg)``: ``(prefix, count)`` pairs: the dense leaves under
      each prefix are stacked over ``count`` layers, and compared per layer.
    """
    name = config["backbone"]
    return _backbone(os.path.join(root, "bench", "backbones", name + ".py"),
                     name)


def flops_per_sample(config: Dict[str, Any], root: str = ROOT) -> float:
    """Model FLOPs of one training sample (forward and backward, recompute
    not counted) from ``bench/flops/<backbone>.py``."""
    backbone = config["backbone"]
    mod = _load_module(os.path.join(root, "bench", "flops", backbone + ".py"),
                       "bench_flops_" + backbone)
    return 3.0 * mod.forward_flops_per_sample(config)
