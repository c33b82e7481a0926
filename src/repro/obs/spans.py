"""Host spans and per-call counters on the profiler's clock (DESIGN.md §11).

`span(name, **meta)` is a `jax.profiler.TraceAnnotation`: while a
profiler session runs, the span lands in the same `.xplane.pb`, on the
same clock, as the device ops it caused; otherwise entering it is one
no-op call.  `meta` rides on the host event as stats (an `engine.call`
span carries its call's sequence number as `seq`).

The registry keeps counters that only exist after a call returns, such as
an engine call's trip count (`harness.runner`).  It is filled in the order
of calls, holds at most `CAPACITY` entries (the oldest go first) and is
read after the run by `snapshot`.  A value may be an unfetched device
array: `snapshot` fetches it then, and skips one whose buffer a later
call has since donated.
"""
from __future__ import annotations

import collections
import itertools

import jax
import numpy as np

CAPACITY = 4096

_entries = collections.deque(maxlen=CAPACITY)
_seq = itertools.count()


def span(name: str, **meta):
    """A host span named `name` with stats `meta`, as a context manager."""
    return jax.profiler.TraceAnnotation(name, **meta)


def next_seq() -> int:
    """A fresh call sequence number (process-wide, never reused)."""
    return next(_seq)


def record(name: str, seq: int, value) -> None:
    """Keep `value` under (`name`, `seq`); never waits for the device."""
    _entries.append((name, seq, value))


def snapshot() -> list:
    """[(name, seq, numpy value)] in the order recorded, donated values
    left out."""
    out = []
    for name, seq, value in list(_entries):
        if isinstance(value, jax.Array) and value.is_deleted():
            continue
        out.append((name, seq, np.asarray(value)))
    return out


def reset() -> None:
    """Forget every entry (sequence numbers keep counting)."""
    _entries.clear()
