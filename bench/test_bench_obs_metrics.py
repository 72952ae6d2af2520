"""The per-layer metrics read from the program's own spans and counters:
each reads only the records inside the benchmark's window (or, for
compile time, before it), and gives None where there is nothing to read,
as in a program without ``repro.obs``."""
from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402
from repro import obs  # noqa: E402

WINDOW_S = 100.0

# metric: (records in the window as (kind, name, value), counters, reading)
CASES = {
    "to_host_s": ([("span", "rank.to_host", 3.0),
                   ("span", "rank.to_host", 3.5)], {"cycles": 2}, 3.25),
    "persist_s": ([("span", "persist.pack", 0.25),
                   ("span", "persist.save", 1.25)], {"cycles": 1}, 1.5),
    "poll_read_s": ([("span", "poll.read", 0.5)], {"cycles": 1}, 0.5),
    "poll_unpack_s": ([("span", "poll.unpack", 2.0),
                       ("span", "poll.blend", 0.5)], {"cycles": 2}, 1.25),
    "host_syncs_per_tick": ([("count", "engine.syncs", 4),
                             ("count", "engine.syncs", 4)],
                            {"ticks": 32}, 0.25),
}
METRICS = sorted(CASES) + ["setup_compile_s"]


def _read(metric, run):
    return harness.load_module("metrics", metric).read(run)


def _run(w0, counters, window=True):
    spans = [("hose", w0 - 60.0, w0 - 50.0)]
    if window:
        spans.append(("window", w0, w0 + WINDOW_S))
    return SimpleNamespace(spans=SimpleNamespace(spans=spans),
                           counters=dict(counters))


def _record(records, at):
    for kind, name, v in records:
        if kind == "span":
            obs.RECORDER.add_span(name, at, at + v)
        else:
            obs.count(name, v)


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reads_only_records_inside_the_window(metric):
    records, counters, want = CASES[metric]
    _record(records, time.perf_counter() - 40.0)        # before
    w0 = time.perf_counter()
    _record(records, w0 + 1.0)                          # inside
    _record([r for r in records if r[0] == "span"],
            w0 + WINDOW_S + 1.0)                        # after
    assert _read(metric, _run(w0, counters)) == pytest.approx(want)


def test_setup_compile_s_reads_compiles_before_the_window():
    w0 = time.perf_counter()
    run = _run(w0, {})
    base = _read("setup_compile_s", run) or 0.0
    obs.RECORDER.add_span("compile.jit(probe)", w0 - 9.0, w0 - 2.0)
    obs.RECORDER.add_span("compile.jit(probe)", w0 + 1.0, w0 + 5.0)
    obs.RECORDER.add_span("setup.other", w0 - 9.0, w0 - 2.0)
    assert _read("setup_compile_s", run) == pytest.approx(base + 7.0)


@pytest.mark.parametrize("metric", METRICS)
def test_none_without_a_window(metric):
    counters = CASES.get(metric, (None, {}, None))[1]
    assert _read(metric, _run(time.perf_counter(), counters,
                              window=False)) is None


@pytest.mark.parametrize("metric", sorted(CASES))
def test_none_when_nothing_recorded_in_the_window(metric):
    counters = CASES[metric][1]
    # a window long before this process recorded anything
    assert _read(metric, _run(-1e6, counters)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_none_for_a_program_without_spans(metric, monkeypatch):
    w0 = time.perf_counter()
    _record(CASES.get(metric, ([], None, None))[0], w0 + 1.0)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    counters = CASES.get(metric, (None, {}, None))[1]
    assert _read(metric, _run(w0, counters)) is None
