"""``svc_hash_c25.replay`` at a small size on the CPU: a sound run is
correct, and the control and each fault the cell can have are not."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import faults, harness, small  # noqa: E402

CELL = "svc_hash_c25.replay"


def test_sound_run_is_correct(tmp_path):
    res = small.run_small(CELL, str(tmp_path))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"replay_events_per_s", "setup_s"}


def test_control_is_not_correct():
    from bench import control
    cell = small.shrink(harness.resolve(harness.load_benchmark(ROOT), CELL))
    checks = control.control_checks(cell, 7, 32)
    assert not all(v <= lim for _, v, lim in checks), checks


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(fault, tmp_path, monkeypatch):
    faults.plant(fault, monkeypatch)
    res = small.run_small(CELL, str(tmp_path))
    assert not res["correct"], res["checks"]
