"""The ``rank_lookup_full_rounds`` reader: the mean of the program's
``rank.lookup_full_rounds`` counter records inside the benchmark's window,
and None where there is nothing to read, as in a program that does not
count full-width probe rounds or has no ``repro.obs`` at all."""
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
COUNTER = "rank.lookup_full_rounds"


def _read(run):
    return harness.load_module("metrics", "rank_lookup_full_rounds").read(run)


def _run(w0, window=True):
    spans = [("hose", w0 - 60.0, w0 - 50.0)]
    if window:
        spans.append(("window", w0, w0 + WINDOW_S))
    return SimpleNamespace(spans=SimpleNamespace(spans=spans),
                           counters={"cycles": 1})


def test_mean_of_the_records_inside_the_window():
    obs.count(COUNTER, 16)            # before the window: not read
    w0 = time.perf_counter()
    obs.count(COUNTER, 1)             # one record per lookup
    obs.count(COUNTER, 2)
    assert _read(_run(w0)) == pytest.approx(1.5)


def test_none_without_a_window():
    obs.count(COUNTER, 1)
    assert _read(_run(time.perf_counter(), window=False)) is None


def test_none_when_the_counter_is_absent_from_the_window():
    obs.count("engine.syncs", 2)      # other counters are not read
    # a window long before this process recorded anything
    assert _read(_run(-1e6)) is None


def test_none_for_a_program_without_spans(monkeypatch):
    w0 = time.perf_counter()
    obs.count(COUNTER, 1)
    monkeypatch.delattr(sys.modules["repro"], "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read(_run(w0)) is None
