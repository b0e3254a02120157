"""The training job's input: zipf item-id sequences, drawn from the seed.

One general generator for every traffic file. A traffic file
(``traffic/<name>.json``) gives the per-chip batch and the key skew
(``zipf_a``); the configuration gives the sequence length and the item
table's rows. Batch ``step`` of seed ``seed`` is the same array whatever was
drawn before it, so the reference can draw the batches the program trained
on without taking anything from the program.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def zipf(rng: np.random.Generator, n: int, size, a: float) -> np.ndarray:
    """Ids in [0, n) with p(k) proportional to (k + 1)^-a, by inverse CDF of
    the truncated power law."""
    u = rng.random(size)
    if a == 1.0:
        k = np.exp(u * np.log(n)) - 1
    else:
        k = ((n ** (1 - a) - 1) * u + 1) ** (1 / (1 - a)) - 1
    return np.clip(k.astype(np.int64), 0, n - 1)


def item_batch(seed: int, step: int, *, batch: int, seq_len: int,
               n_items: int, zipf_a: float) -> np.ndarray:
    """(batch, seq_len) int64 item ids of training batch ``step``."""
    rng = np.random.default_rng([int(seed), int(step), 0x5EC])
    return zipf(rng, n_items, (batch, seq_len), zipf_a)


def scramble(ids: np.ndarray, layout: Dict[str, int]) -> np.ndarray:
    """Row of each mega-table id under the program's bijective affine
    layout ``(k * mult + add) mod rows`` (computed in uint64, no wrap)."""
    k = ids.astype(np.uint64)
    return ((k * np.uint64(layout["mult"]) + np.uint64(layout["add"]))
            % np.uint64(layout["rows"])).astype(np.int32)


def program_stream(seed: int, start_step: int, *, batch: int, seq_len: int,
                   n_items: int, zipf_a: float, layout: Dict[str, int],
                   item_offset: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """The batches in the form the program's driver reads: ``keys`` are
    mega-table rows, ``raw_keys`` the item ids (for its clustering)."""
    step = start_step
    while True:
        ids = item_batch(seed, step, batch=batch, seq_len=seq_len,
                         n_items=n_items, zipf_a=zipf_a)
        yield {"keys": scramble(ids + item_offset, layout),
               "raw_keys": ids.astype(np.int32)}
        step += 1

