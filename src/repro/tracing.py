"""Spans inside the program, on the JAX profiler's trace.

``span(name, **fields)`` marks a phase of the served path::

    with tracing.span("query.window", q=8) as sp:
        ...
        sp.set(pairs=p0)

While no profiler traces the process it checks ``TraceAnnotation.is_enabled()``
once and does nothing else.  While one traces, the span is a
``jax.profiler.TraceAnnotation`` (so it lies on the same clock as the
device planes of the trace, and its fields are the event's metadata), and
it is also kept as a :class:`Record` on ``time.monotonic`` (the clock of
``Frontend``) in a bounded buffer that :func:`recorded` reads back.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

# about eight spans a batch: a 60 s window at a hundred batches a second
# fits (a window cell at 20 requests/s records about 6,600 in 50 s)
MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    name: str
    t_start: float   # time.monotonic
    t_end: float
    thread: str
    fields: dict


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_lock = threading.Lock()


class _Off:
    """The span while no profiler traces: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **fields) -> None:
        return None


_OFF = _Off()


class _Span:
    __slots__ = ("name", "fields", "_ann", "_t0")

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.fields = fields

    def __enter__(self) -> "_Span":
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        if self.fields:
            self._ann.set_metadata(**self.fields)
        self._ann.__exit__(*exc)
        rec = Record(self.name, self._t0, t1,
                     threading.current_thread().name, dict(self.fields))
        with _lock:
            _records.append(rec)


def span(name: str, **fields):
    """A context manager for one phase; the object it yields takes
    ``set(**fields)`` for fields known only inside it (see the module
    docstring)."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, fields)


def recorded(t0: float, t1: float, name: str | None = None) -> list:
    """The kept records (of ``name`` only, if given) that started inside
    ``[t0, t1]`` on ``time.monotonic``, in the order they ended."""
    with _lock:
        recs = list(_records)
    return [r for r in recs if t0 <= r.t_start <= t1
            and (name is None or r.name == name)]
