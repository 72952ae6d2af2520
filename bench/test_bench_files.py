"""The benchmark's files: every cell resolves by name, a new cell is new
files alone, the hose is the recorded one, the peaks and the rank cycle's
bytes model."""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def _bench() -> dict:
    return harness.load_benchmark(ROOT)


def test_benchmark_keys_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for k in ("configs", "workloads"):
        for x in b[k]:
            assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] \
                and "\t" not in x["why"]
    assert len(json.dumps(b)) <= 64 * 1024
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["layer"].strip()
        for w in m.get("workloads", []):
            assert w in cells and _applies(e2e[m["moves"]], w)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.resolve(_bench(), cell)
    assert c.chips in (1, 4)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    mode = harness.load_module("modes", c.traffic["mode"])
    for fn in ("setup", "window", "check"):
        assert callable(getattr(mode, fn))
    for m in c.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    cfg = harness.engine_configs(c.config)
    assert cfg["rt"].cooc_capacity == c.config["engine"]["cooc_capacity"]
    assert set(harness.semantics(c.config)) == set(cfg)


def test_configs_files_and_reduced():
    b = _bench()
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        d = json.load(open(os.path.join(ROOT, c["file"])))
        assert d["name"] == c["name"] and d["reduced"] == c["reduced"]
        assert "assumed" in d and "guarantees" in d
        # every cut names a key of the file and says why it was made
        assert set(d.get("reduced_why", {})) == set(d["reduced"])
        for key in d["reduced"]:
            node = d
            for part in key.split("."):
                assert part in node, key
                node = node[part]


@pytest.mark.parametrize("config", [c["name"] for c in _bench()["configs"]])
def test_every_config_states_its_hose(config):
    from bench.hose import HoseParams
    d = json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))
    p = HoseParams.from_json(d["hose"])
    assert p.queries_per_tick > 0 and p.vocab_size > 0


def test_control_reads_ticks_fed_from_a_sound_run(tmp_path):
    from bench import control
    log = tmp_path / "sound.err"
    log.write_text("counter cycles = 1\ncounter ticks_fed = 177\n"
                   "check drops = 0 (limit 0)\n")
    assert control.ticks_fed(str(log)) == 177
    (tmp_path / "empty.err").write_text("counter cycles = 1\n")
    with pytest.raises(ValueError):
        control.ticks_fed(str(tmp_path / "empty.err"))


def test_new_cell_is_new_files_alone(tmp_path):
    """A copy of the benchmark with one more configuration, traffic mix,
    per-layer metric and cell, added as files and entries only, resolves
    and runs the new metric's reader."""
    shutil.copytree(BENCH, tmp_path / "bench")
    b = _bench()
    cfg = json.load(open(os.path.join(BENCH, "configs", "rt_hash_c25.json")))
    cfg["name"] = "rt_hash_c24"
    cfg["engine"]["cooc_capacity"] = 1 << 24
    cfg["hose"]["queries_per_tick"] = 16384
    (tmp_path / "bench/configs/rt_hash_c24.json").write_text(json.dumps(cfg))
    tr = json.load(open(os.path.join(BENCH, "traffic", "refresh.json")))
    tr["fill_ticks"] = 88
    (tmp_path / "bench/traffic/refresh_half.json").write_text(json.dumps(tr))
    (tmp_path / "bench/metrics/cycles_seen.py").write_text(
        "def read(run):\n    return run.counters.get('cycles')\n")
    b["configs"].append({"name": "rt_hash_c24", "source": "x",
                         "file": "bench/configs/rt_hash_c24.json",
                         "reduced": ["cooc_capacity"], "why": "x"})
    b["workloads"].append({"name": "rt_hash_c24.refresh_half",
                           "config": "rt_hash_c24",
                           "traffic": "refresh_half", "chips": 1,
                           "why": "x"})
    b["per_layer"].append({"name": "cycles_seen", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "rank cycle", "moves": "refresh_s",
                           "workloads": ["rt_hash_c24.refresh_half"]})
    b["end_to_end"][0]["workloads"].append("rt_hash_c24.refresh_half")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    spec = importlib.util.spec_from_file_location(
        "bench_copy_harness", tmp_path / "bench" / "harness.py")
    h = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = h
    try:
        spec.loader.exec_module(h)
    finally:
        del sys.modules[spec.name]
    cell = h.resolve(h.load_benchmark(str(tmp_path)),
                     "rt_hash_c24.refresh_half")
    assert cell.config["engine"]["cooc_capacity"] == 1 << 24
    assert cell.config["hose"]["queries_per_tick"] == 16384
    assert cell.traffic["fill_ticks"] == 88
    assert [m["name"] for m in cell.per_layer] == ["cycles_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"refresh_s", "setup_s"}

    class _Run:
        counters = {"cycles": 3}
    assert h.load_module("metrics", "cycles_seen").read(_Run()) == 3


def test_hose_matches_recorded_digest():
    from bench import feed
    from bench.hose import digest
    rec = json.load(open(os.path.join(BENCH, "testdata",
                                      "hose_digest.json")))
    cfg = harness.resolve(_bench(), "rt_hash_c25.refresh").config
    assert rec["config"] == "rt_hash_c25"
    hose = feed.make_hose(cfg, rec["seed"])
    assert digest(hose.tick(t) for t in range(rec["ticks"])) == rec["sha256"]


def test_peaks_keyed_by_device_kind():
    from bench import peaks
    assert peaks.device_peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.device_peaks("cpu")


def test_rank_bytes_model_matches_store_lanes():
    """The store bytes the roofline reads are the hash layout's lanes:
    two u32 key lanes and seven 4-byte lanes per cooccurrence slot, two
    key lanes and three 4-byte lanes per query slot."""
    from repro.core.engine import EngineConfig, init_state
    cfg = EngineConfig(query_capacity=1 << 10, cooc_capacity=1 << 12,
                       session_capacity=1 << 8)
    st = init_state(cfg)
    lanes = [st.cooc.key_hi, st.cooc.key_lo, st.qstore.key_hi,
             st.qstore.key_lo, *st.cooc.lanes.values(),
             *st.qstore.lanes.values()]
    got = sum(x.nbytes for x in lanes)
    assert got == 36 * cfg.cooc_capacity + 20 * cfg.query_capacity
    roof = harness.load_module("metrics", "rank_roofline")
    assert roof.least_bytes(got, 100, 8) == got + 100 * (8 + 8 * 12)
