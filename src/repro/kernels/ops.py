"""Public wrappers around the raw Pallas kernels.

These wrappers expose the kernels' native contracts (pre-clamped indices,
explicit ``interpret`` switch) for tests and direct callers. The embedding
engine does NOT call these: its hot paths go through ``kernels/dispatch.py``,
which adds sentinel-safe semantics and the pallas/interpret/reference
backend selection (config- and env-overridable). ``interpret=None`` here
defers to the dispatch layer's backend, resolved at each call, so both
entry points agree on when the real TPU kernels run.
"""
from __future__ import annotations

from .buffer_sync import buffer_sync_rows as _buffer_sync
from .dispatch import resolve_backend
from .embedding_gather import embedding_gather as _gather
from .flash_attention import flash_attention as _flash
from .hstu_attention import hstu_attention as _hstu
from .segment_rowsum import segment_rowsum as _segsum


def _interpret(interpret) -> bool:
    return resolve_backend() != "pallas" if interpret is None else interpret


def embedding_gather(table, idx, *, interpret=None):
    return _gather(table, idx, interpret=_interpret(interpret))


def segment_rowsum(grads, ids, num_segments, *, block_l: int = 1024,
                   s_tile: int = 256, interpret=None):
    return _segsum(grads, ids, num_segments, block_l=block_l, s_tile=s_tile,
                   interpret=_interpret(interpret))


def buffer_sync(active_rows, prefetch_rows, src, *, interpret=None):
    return _buffer_sync(active_rows, prefetch_rows, src,
                        interpret=_interpret(interpret))


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret=None):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=_interpret(interpret))


def hstu_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                   block_k: int = 256, interpret=None):
    return _hstu(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                 interpret=_interpret(interpret))
