"""The program's spans and counters: one registry per process.

A span accumulates the wall clock of a block under its name::

    from repro import trace

    with trace.span("serve.decode", batch=0):
        ...
    trace.count("serve.batches")
    trace.walls()    # {"serve.decode": 1.23}  inclusive seconds per name
    trace.counts()   # {"serve.batches": 1}

Spans are always on and cost about two microseconds each.  When ``jax`` is
already imported, a span also opens a ``jax.profiler.TraceAnnotation``
of the same name (its keywords become the event's metadata), so any
profiler trace shows it on the host plane, on the same clock as the
device's operations; with no profiler running the annotation records
nothing.  This module never imports ``jax`` itself: the simulator's
child processes run numpy only.

Walls are inclusive: a span nested in another adds to both names (the
engine's ``roundtrip`` inside ``solve``).  A span nested in one of its
own name counts its time twice, so each name is opened at one level.
Child processes hand their :func:`walls` / :func:`counts` to the parent,
which folds them in with :func:`merge`.
"""

from __future__ import annotations

import sys
import threading
import time

__all__ = ["span", "count", "walls", "counts", "reset", "merge"]

#: span name -> accumulated seconds in this process
_WALLS: dict[str, float] = {}

#: counter name -> accumulated count in this process
_COUNTS: dict[str, int] = {}

#: guards the read-modify-write updates of both
_LOCK = threading.Lock()


class span:  # lower case: used like a function, ``with span(...)``
    """A context manager that adds its duration to ``walls()[name]``;
    ``meta`` is the trace event's metadata.  After the block,
    ``seconds`` holds the duration."""

    __slots__ = ("name", "meta", "seconds", "_t0", "_note")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self.seconds = 0.0
        self._note = None

    def __enter__(self) -> "span":
        jax = sys.modules.get("jax")
        if jax is not None:
            self._note = jax.profiler.TraceAnnotation(self.name, **self.meta)
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        with _LOCK:
            _WALLS[self.name] = _WALLS.get(self.name, 0.0) + self.seconds
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + int(n)


def walls() -> dict[str, float]:
    with _LOCK:
        return dict(_WALLS)


def counts() -> dict[str, int]:
    with _LOCK:
        return dict(_COUNTS)


def reset() -> None:
    """Clear every span and counter."""
    with _LOCK:
        _WALLS.clear()
        _COUNTS.clear()


def merge(walls: dict[str, float] | None = None,
          counts: dict[str, int] | None = None) -> None:
    """Fold another process's walls and counters into this one's."""
    with _LOCK:
        for k, v in (walls or {}).items():
            _WALLS[k] = _WALLS.get(k, 0.0) + float(v)
        for k, v in (counts or {}).items():
            _COUNTS[k] = _COUNTS.get(k, 0) + int(v)
