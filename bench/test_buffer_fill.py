"""The buffer-fill reader: the program's count of the window's unique keys
over the dual buffer's row capacity, and nothing where the program keeps
no such count."""
from __future__ import annotations

import types

import pytest

from bench import spec


def read(steps, stats):
    w = types.SimpleNamespace(trace=None, steps=steps, stats=stats,
                              cell=types.SimpleNamespace(chips=1))
    return spec.metric_reader("engine.buffer_fill_pct")(w)


@pytest.mark.parametrize("steps,valid,rows,want", [
    (4, 4 * 120, 1_000, 12.0),
    (1, 29_400, 589_848, 100 * 29_400 / 589_848),
    (2, 2 * 8, 8, 100.0),
])
def test_buffer_fill_reads_the_programs_counter(steps, valid, rows, want):
    stats = types.SimpleNamespace(losses=[1.0] * steps, buffer_rows=rows,
                                  buffer_keys_valid=valid)
    assert read(steps, stats) == pytest.approx(want)


@pytest.mark.parametrize("stats", [
    types.SimpleNamespace(losses=[1.0, 2.0]),  # a program without the count
    types.SimpleNamespace(losses=[1.0], buffer_rows=0, buffer_keys_valid=0),
    types.SimpleNamespace(losses=[], buffer_rows=8, buffer_keys_valid=0),
])
def test_buffer_fill_reads_nothing_without_a_count(stats):
    assert read(1, stats) is None
