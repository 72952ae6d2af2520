"""The trace reduction, on a small trace recorded on a TPU v5e by
``bench/record_trace.py``: three rounds of a sort program, a 20 ms host
wait and a sum program, each inside a ``bench.`` span."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace_reduce as trace  # noqa: E402

PATH = os.path.join(ROOT, "bench", "testdata", "small_tpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(PATH)


def test_programs_by_name(summary):
    assert summary.n_devices == 1
    assert summary.program_runs == {"scale_sort": 3, "reduce_sum": 3}
    sort_s = summary.program_s("scale_sort")
    # a stable sort of 4M f32 keys takes milliseconds per run on the chip
    assert 3 * 1e-3 < sort_s < 3 * 50e-3
    assert summary.program_s("reduce_sum") < sort_s / 10
    assert summary.program_s("ranking_cycle") is None


def test_busy_is_the_union_of_ops_within_the_window(summary):
    assert 0 < summary.busy_s <= summary.window_s
    ops = dict(summary.top_ops)
    assert any(name.startswith("sort") for name in ops)
    # ops of one program never overlap: busy is their sum
    assert summary.busy_s == pytest.approx(sum(
        v for k, v in summary.top_ops), rel=1e-6)


def test_idle_gaps_named_by_host_span(summary):
    # the three 20 ms host waits are the longest idle gaps
    names = [name for name, _ in summary.idle_gaps[:3]]
    assert names == ["host_wait"] * 3
    assert summary.idle_by_span["host_wait"] == pytest.approx(0.06, rel=0.2)
    idle = sum(summary.idle_by_span.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)


def test_window_clips_busy():
    full = trace.summarize(PATH)
    _, spans = trace.read_xplane(PATH)
    first_sort = next(s for s in spans if s[0] == "sort")
    part = trace.summarize(PATH, window=(first_sort[1], first_sort[2]))
    assert part.window_s == pytest.approx(first_sort[2] - first_sort[1])
    assert 0 < part.busy_s < full.busy_s


def test_union_merges_overlaps():
    assert trace._union([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == [
        (0, 3), (5, 6)]


def test_gap_split_at_span_boundaries():
    spans = [("a", 1.0, 3.0), ("b", 5.0, 12.0)]
    assert trace._split(0.0, 10.0, spans) == [
        ("host", 1.0), ("a", 2.0), ("host", 2.0), ("b", 5.0)]
    assert trace._split(12.5, 13.0, spans) == [("host", 0.5)]


def test_self_time_subtracts_nested_ops():
    ops = [("while", 0.0, 10.0), ("body", 1.0, 4.0), ("inner", 2.0, 3.0),
           ("body", 5.0, 9.0), ("next", 10.0, 11.0)]
    assert trace._self_times(ops) == [("while", 3.0), ("body", 2.0),
                                      ("inner", 1.0), ("body", 4.0),
                                      ("next", 1.0)]
