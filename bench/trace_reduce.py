"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU each chip is a plane named ``/device:TPU:<n>`` whose
``XLA Ops`` line holds one event per operation run and whose
``XLA Modules`` line holds one event per program run, named after the
jitted function (``jit_ranking_cycle(17)``). The host plane holds the
benchmark's own spans (``jax.profiler.TraceAnnotation``, named
``bench.<layer>``) on the thread that entered them. Both are on one clock.

What this computes, per device plane, then averaged over the planes:

* busy seconds: the union of the intervals of the operations (programs,
  where a plane has no operation line), clipped to the window;
* device seconds and run count per program name (the ``jit_`` prefix and
  the run id dropped), and the operations that took most time, each
  less the operations nested in it;
* the idle time between busy intervals, cut at the benchmark's span
  boundaries, each piece named after the span that covers it (``host``
  where none does).

The window is the span ``bench.window`` where the trace has one, else
the first to the last event.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_RUN_ID = re.compile(r"\(\d+\)$")
SPAN_PREFIX = "bench."
WINDOW = "window"


def op_name(event_name: str) -> str:
    """``%sort.6 = (f32[...]) sort(...)`` -> ``sort.6``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_ranking_cycle(17)`` -> ``ranking_cycle``."""
    n = _RUN_ID.sub("", event_name.strip())
    return n[4:] if n.startswith("jit_") else n


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(ops) -> List[Tuple[str, float]]:
    """Each operation's time less that of the operations nested in it (a
    while loop holds the runs of its body's operations)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0.0] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(n, e - s - c) for (n, s, e), c in zip(ops, child)]


def _split(g0: float, g1: float, spans) -> List[Tuple[str, float]]:
    """An idle gap cut at the benchmark's span boundaries: each piece
    named after the span that covers it, ``host`` where none does."""
    out, t = [], g0
    for name, s, e in spans:
        if s >= g1:
            break
        lo, hi = max(s, t), min(e, g1)
        if hi <= lo:
            continue
        if lo > t:
            out.append(("host", lo - t))
        out.append((name, hi - lo))
        t = hi
    if g1 > t:
        out.append(("host", g1 - t))
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over device planes
    n_devices: int
    programs: Dict[str, float]          # device seconds, summed over planes
    program_runs: Dict[str, int]
    top_ops: List[Tuple[str, float]]    # device seconds, summed over planes
    idle_gaps: List[Tuple[str, float]]  # longest, named by host span
    idle_by_span: Dict[str, float]      # all gap time, by host span

    def program_s(self, name: str) -> Optional[float]:
        """Device seconds of every program whose name contains ``name``,
        per device; None when no such program ran."""
        hits = [v for k, v in self.programs.items() if name in k]
        return sum(hits) / self.n_devices if hits else None


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read_xplane(path: str):
    """(devices, spans): per device plane its (ops, programs) events, and
    the benchmark's host spans; every event as (name, start s, end s)."""
    import jax

    devices, spans = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops, mods = lines.get("XLA Ops"), lines.get("XLA Modules")
            if ops is not None or mods is not None:
                devices.append((_events(ops) if ops is not None else [],
                                _events(mods) if mods is not None else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(n[len(SPAN_PREFIX):], t0, t1)
                          for n, t0, t1 in _events(ln)
                          if n.startswith(SPAN_PREFIX)]
    return devices, sorted(spans, key=lambda sp: sp[1])


def summarize(path: str, window: Optional[Tuple[float, float]] = None,
              top: int = 10) -> TraceSummary:
    """Reduce the trace at ``path``. ``window`` is (start, end) in the
    trace's own seconds; by default, the first to the last event."""
    devices, spans = read_xplane(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA ops or modules")
    if window is None:
        win = [s for s in spans if s[0] == WINDOW]
        if win:
            window = (win[0][1], win[0][2])
    spans = [s for s in spans if s[0] != WINDOW]
    if window is None:
        ends = [x for ops, mods in devices for ev in (ops or mods)
                for x in ev[1:]] + [x for s in spans for x in s[1:]]
        window = (min(ends), max(ends))
    w0, w1 = window
    programs: Dict[str, float] = defaultdict(float)
    runs: Dict[str, int] = defaultdict(int)
    op_time: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    idle_by_span: Dict[str, float] = defaultdict(float)
    for ops, mods in devices:
        for name, s, e in mods:
            programs[program_name(name)] += e - s
            runs[program_name(name)] += 1
        for name, t in _self_times(ops):
            op_time[op_name(name)] += t
        busy = _union([(max(s, w0), min(e, w1)) for _, s, e in (ops or mods)
                       if e > w0 and s < w1])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            for label, dt in _split(g0, g1, spans):
                gaps.append((label, dt))
                idle_by_span[label] += dt
    n = len(devices)
    return TraceSummary(
        window_s=w1 - w0, busy_s=busy_total / n, n_devices=n,
        programs=dict(programs), program_runs=dict(runs),
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top],
        idle_by_span=dict(idle_by_span))
