"""Spans and counters of the program's host side, and its compiles.

``span(name)`` times a block of host code: it writes ``repro.<name>``
into the profiler's trace (``jax.profiler.TraceAnnotation``, a no-op
unless a trace is being taken), so the span sits on the host plane on
the device trace's clock, and it records ``(name, t0, t1)`` on
``time.perf_counter()``. ``count(name, n)`` adds to a counter and records
when it did. The newest ``RECORDS`` records of each kind are kept, and
running ``{name: (count, seconds)}`` totals of all of them.

``totals()`` is what an operator reads; ``window(t0, t1)`` sums the
records that fall in a stretch of time, which is how a measurement reads
one part of a run.

Every compile JAX reports (``/jax/core/compile/backend_compile_duration``,
which also times a load from the persistent compilation cache) is a span
``compile.<fun_name>`` ending when it was reported, and the persistent
cache's hits and misses are the counters ``compile_cache.hits`` and
``compile_cache.misses``. The listeners are registered once, when this
module is imported.

Span names, by the layer they time:

  engine.sync     a blocking device->host read of the engine's host loop
                  (counter ``engine.syncs``)
  rank.wait       waiting for the rank program's table
  rank.to_host    the table to a host dict (counter ``rank.rows_exported``)
  persist.pack    the dict to flat arrays
  persist.save    encoding, hashing and writing a checkpoint
                  (counter ``persist.bytes``)
  poll.read       a frontend reading a persisted table
  poll.unpack     the arrays to a dict
  poll.blend      real-time and background tables interpolated
  log.read        a log segment read and decoded (counter ``log.bytes``)
  replay.stack    a log chunk to a device ``TickStack``
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Tuple

import jax

RECORDS = 1 << 16

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "compile_cache.hits",
                "/jax/compilation_cache/cache_misses": "compile_cache.misses"}


class Recorder:
    """Span and count records, bounded, and unbounded running totals."""

    def __init__(self):
        self._lock = threading.Lock()
        self.spans: collections.deque = collections.deque(maxlen=RECORDS)
        self.counts: collections.deque = collections.deque(maxlen=RECORDS)
        self._totals: Dict[str, list] = {}

    def span(self, name: str) -> "_Span":
        return _Span(self, name)

    def add_span(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self.spans.append((name, t0, t1))
            tot = self._totals.setdefault(name, [0, 0.0])
            tot[0] += 1
            tot[1] += t1 - t0

    def count(self, name: str, n: float = 1) -> None:
        t = time.perf_counter()
        with self._lock:
            self.counts.append((name, t, n))
            self._totals.setdefault(name, [0, 0.0])[0] += n

    def totals(self) -> Dict[str, Tuple[float, float]]:
        """``{name: (count, seconds)}`` since the process started; a
        counter's seconds are 0."""
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self._totals.items()}

    def window(self, t0: float, t1: float) -> Dict[str, Tuple[float, float]]:
        """``{name: (count, seconds)}`` of the kept records in
        ``[t0, t1]``: every span that overlaps it, with the seconds of the
        overlap, and every count made inside it."""
        with self._lock:
            spans, counts = list(self.spans), list(self.counts)
        out: Dict[str, Tuple[float, float]] = {}
        for name, a, b in spans:
            lo, hi = max(a, t0), min(b, t1)
            if lo <= hi:
                n, s = out.get(name, (0, 0.0))
                out[name] = (n + 1, s + hi - lo)
        for name, t, k in counts:
            if t0 <= t <= t1:
                n, s = out.get(name, (0, 0.0))
                out[name] = (n + k, s)
        return out


class _Span:
    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self) -> "_Span":
        self.ann = jax.profiler.TraceAnnotation(f"repro.{self.name}")
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.rec.add_span(self.name, self.t0, t1)


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
totals = RECORDER.totals
window = RECORDER.window


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event == COMPILE_EVENT:
        t1 = time.perf_counter()
        RECORDER.add_span(f"compile.{kw.get('fun_name', '')}",
                          t1 - seconds, t1)


def _on_event(event: str, **kw) -> None:
    if event in CACHE_EVENTS:
        RECORDER.count(CACHE_EVENTS[event])


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
