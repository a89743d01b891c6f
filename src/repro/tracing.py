"""Spans and counts of one call of an entry point that runs on the chip.

A span does two things.  It opens a ``jax.profiler.TraceAnnotation``
under its name, so that while a profiler runs the span lies in the
trace, on the device trace's clock and on the caller's thread, nested in
whatever annotation the caller has open.  And it adds its host duration
(``time.perf_counter``) to the call's :class:`Record`, which the entry
point hands back with its result.  There is no switch: a span costs a
few microseconds of host time whether a profiler runs or not.

Span names are ``<entry>.<phase>``; the seconds of every span named
``<entry>.<phase>`` add up under ``<phase>_s``.
"""
from __future__ import annotations

import contextlib
import time

import jax


class Record:
    """The spans and counts of one call, in memory.

    ``spans`` holds ``[name, start, end, parent]`` for each span in the
    order they opened: ``start`` and ``end`` in ``time.perf_counter``
    seconds (``end`` is ``None`` while the span is open), ``parent`` the
    index in ``spans`` of the span open around it, or ``None``."""

    def __init__(self):
        self.spans: list[list] = []
        self.totals: dict = {}
        self._open: list[int] = []

    @contextlib.contextmanager
    def span(self, name: str, count: str | None = None):
        """Time the block as span ``name``; with ``count``, add one to the
        record's count of that name."""
        key = name.rpartition(".")[2] + "_s"
        parent = self._open[-1] if self._open else None
        self._open.append(len(self.spans))
        entry = [name, time.perf_counter(), None, parent]
        self.spans.append(entry)
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            entry[2] = time.perf_counter()
            self._open.pop()
            self.totals[key] = self.totals.get(key, 0.0) + entry[2] - entry[1]
            if count is not None:
                self.count(count)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the record's total ``name``: a count, or seconds
        timed without a span where a span per event would cost too much."""
        self.totals[name] = self.totals.get(name, 0) + n

    def as_dict(self) -> dict:
        """Seconds per phase and counts, with the spans under ``"spans"``."""
        return {**self.totals, "spans": [tuple(s) for s in self.spans]}
