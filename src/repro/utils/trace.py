"""Program spans and counters, recorded on the profiler's clock.

    from repro.utils import trace

    with trace.span("alid.round", round=r):          # a host span
        ...
    with trace.span("serve.pack", stats, "pack_s"):   # also feeds a counter
        ...
    trace.count("alid.seeds_valid", n)

Recording follows the JAX profiler: while a profile is being taken
(`jax.profiler.trace(dir)` or `start_trace`), a span opens a
`jax.profiler.TraceAnnotation`, so it lands in the `.xplane.pb` on the host
thread that ran it, on the same clock as the device ops, and it adds its
count, total and self time (total less the time of the spans it encloses
on the same thread) to an in-memory table; `count` adds to a counter in the
same table. `summary()` merges the table over threads; `reset()` clears it.
Outside a profile a span costs one `TraceAnnotation.is_enabled()` call and
records nothing, and `count` returns at once.

A span given a `Counters` object and one of its fields adds its seconds to
that field whether or not a profile is being taken, so a stage is timed
once, by its span.

Each thread writes its own table, so the hot path takes no lock; a lock is
taken only the first time a thread records, and by `summary`/`reset`.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["Counters", "span", "count", "recording", "summary", "reset"]

recording = TraceAnnotation.is_enabled
_clock = time.perf_counter_ns


class Counters:
    """Named counters shared between threads. A subclass lists its fields
    in `_FIELDS`; a field whose name ends in `_s` holds float seconds, the
    others whole counts."""

    _FIELDS: tuple[str, ...] = ()

    def __init__(self) -> None:
        for f in self._FIELDS:
            setattr(self, f, 0.0 if f.endswith("_s") else 0)
        self._lock = threading.Lock()

    def add(self, field: str, amount=1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + amount)

    def peak(self, field: str, value) -> None:
        with self._lock:
            setattr(self, field, max(getattr(self, field), value))

    def snapshot(self) -> dict:
        return {f: (float(v) if isinstance(v := getattr(self, f), float)
                    else int(v)) for f in self._FIELDS}


class _Table(threading.local):
    """One thread's records: name -> (count, total_ns, self_ns), and the
    child time of each open recorded span, innermost last."""

    def __init__(self) -> None:
        self.rows: dict[str, tuple[int, int, int]] = {}
        self.open: list[int] = []
        with _lock:
            _tables.append(self.rows)


_lock = threading.Lock()
_tables: list[dict] = []
_local = _Table()


def span(name: str, stats: Optional[Counters] = None,
         field: Optional[str] = None, **meta):
    """A context manager that times the enclosed block as `name` (see the
    module docstring). `meta` becomes the annotation's metadata while
    recording; the manager's `annotate(**meta)` adds to it once values are
    known inside the block."""
    if recording():
        return _Span(name, stats, field, meta)
    if stats is None:
        return _OFF
    return _Timer(stats, field)


class _Off:
    """Not recording, no counter to feed: nothing to do."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def annotate(self, **meta) -> None:
        pass


_OFF = _Off()


class _Timer(_Off):
    """Not recording: only feeds the counter field."""

    __slots__ = ("stats", "field", "t0")

    def __init__(self, stats: Counters, field: str) -> None:
        self.stats, self.field = stats, field

    def __enter__(self) -> "_Timer":
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.stats.add(self.field, (_clock() - self.t0) * 1e-9)


class _Span(_Off):
    """Recording: an annotation in the profile, a row in this thread's
    table, and the counter field if one is given."""

    __slots__ = ("name", "stats", "field", "ann", "t0")

    def __init__(self, name: str, stats: Optional[Counters],
                 field: Optional[str], meta: dict) -> None:
        self.name, self.stats, self.field = name, stats, field
        self.ann = TraceAnnotation(name, **meta)

    def __enter__(self) -> "_Span":
        self.ann.__enter__()
        _local.open.append(0)
        self.t0 = _clock()
        return self

    def annotate(self, **meta) -> None:
        self.ann.set_metadata(**meta)

    def __exit__(self, *exc) -> None:
        dt = _clock() - self.t0
        self.ann.__exit__(*exc)
        if self.stats is not None:
            self.stats.add(self.field, dt * 1e-9)
        tab = _local
        child = tab.open.pop()
        if tab.open:
            tab.open[-1] += dt
        c, tot, own = tab.rows.get(self.name, (0, 0, 0))
        tab.rows[self.name] = (c + 1, tot + dt, own + dt - child)


def count(name: str, n: int = 1) -> None:
    """Adds `n` to the counter `name` while recording."""
    if recording():
        rows = _local.rows
        c, _, _ = rows.get(name, (0, 0, 0))
        rows[name] = (c + int(n), 0, 0)


def summary() -> dict[str, tuple[int, int, int]]:
    """{name: (count, total_ns, self_ns)} over every thread since the last
    `reset()`. A counter reads (its sum, 0, 0)."""
    out: dict[str, tuple[int, int, int]] = {}
    with _lock:
        tables = [dict(rows) for rows in _tables]
    for rows in tables:
        for name, (c, tot, own) in rows.items():
            c0, t0, s0 = out.get(name, (0, 0, 0))
            out[name] = (c0 + c, t0 + tot, s0 + own)
    return out


def reset() -> None:
    """Forgets every record."""
    with _lock:
        for rows in _tables:
            rows.clear()
