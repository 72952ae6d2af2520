"""``sharded_hash_4chip.replay`` at a small size on 4 virtual CPU devices:
a sound run is correct, and a run with a fault planted in the routes is
not, nor is the control; set-up fails at once on a program without the
sharded replay; the roofline's bytes are the stores' lanes; the routing
metrics read the program's counters in the window."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CELL = "sharded_hash_4chip.replay"

_RUNS = textwrap.dedent("""
    import json, sys, tempfile
    import jax, jax.numpy as jnp
    from bench import small
    from repro.core import sharded_engine as se

    def run():
        res = small.run_small(%(cell)r, tempfile.mkdtemp())
        return {k: res[k] for k in ("correct", "checks", "metrics",
                                    "attempted", "failed")}

    route, grams = se._route, se.tweet_grams

    def bucket_zeroed(owner, lanes, valid, n, cap, axis):
        # shard 1 takes in nothing that the routes send it
        got, ok, dropped, received = route(owner, lanes, valid, n, cap,
                                           axis)
        return got, ok & (jax.lax.axis_index(axis) != 1), dropped, received

    def tweet_route_skipped(*a, **kw):
        qstore, pairs = grams(*a, **kw)
        return qstore, pairs._replace(valid=jnp.zeros_like(pairs.valid))

    out = {"sound": run()}
    se._route = bucket_zeroed
    out["bucket_zeroed"] = run()
    se._route, se.tweet_grams = route, tweet_route_skipped
    out["tweet_route_skipped"] = run()
    print("RUNS " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTEST_ALLOW_DEVICES="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", _RUNS % {"cell": CELL}],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    line = [x for x in r.stdout.splitlines() if x.startswith("RUNS ")]
    assert r.returncode == 0 and line, \
        f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-3000:]}"
    return json.loads(line[-1][len("RUNS "):])


def test_sound_run_is_correct(runs):
    res = runs["sound"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"replay_events_per_s", "setup_s"}
    assert res["checks"]["qstore_replica_diff"]["value"] == 0
    assert res["checks"]["drops"]["value"] == 0


@pytest.mark.parametrize("fault", ["bucket_zeroed", "tweet_route_skipped"])
def test_fault_is_not_correct(runs, fault):
    res = runs[fault]
    assert not res["correct"], res["checks"]
    assert res["checks"]["key_diff"]["value"] > 0


def test_control_is_not_correct():
    """The reference held in bfloat16, by the cell's own comparisons."""
    from bench import control, small
    cell = small.shrink(harness.resolve(harness.load_benchmark(ROOT), CELL))
    checks = control.control_checks(cell, 7, 32)
    assert not all(v <= lim for _, v, lim in checks), checks


def test_setup_fails_fast_without_sharded_replay(tmp_path, monkeypatch):
    """On a program without ``ShardedEngine`` set-up raises before it
    draws the hose or writes the log."""
    from repro.core import sharded_engine as se
    monkeypatch.delattr(se, "ShardedEngine")
    cell = harness.resolve(harness.load_benchmark(ROOT), CELL)
    run = harness.Run(cell=cell, seed=1, seconds=1.0, trace=False,
                      root=str(tmp_path), work=str(tmp_path),
                      spans=harness.Spans(False))
    mode = harness.load_module("modes", "sharded_replay")
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="ShardedEngine"):
        mode.setup(run)
    assert time.perf_counter() - t0 < 5.0 and not run.spans.spans
    assert not os.listdir(tmp_path)


def test_sharded_roofline_bytes_match_store_lanes():
    """The slot bytes the roofline reads are the sharded stores' lanes:
    two u32 key lanes and three 4-byte lanes a query slot, seven a
    cooccurrence slot; a session row holds two key lanes, a window of
    5 x (u32, u32, i32) and three i32 lanes; a routed pair is the
    program's four u32 key halves and its valid flag."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import sharded_engine as se
    from repro.core.engine import EngineConfig
    cfg = se.ShardedConfig(base=EngineConfig(
        query_capacity=1 << 10, cooc_capacity=1 << 12,
        session_capacity=1 << 8, session_window=5))
    st = se.init_sharded_state(cfg, Mesh(np.array(jax.devices()[:1]),
                                         ("shard",)))
    mode = harness.load_module("modes", "sharded_replay")
    q, c, s = (mode.row_bytes(t) for t in (st.qstore, st.cooc, st.sessions))
    assert (q, c, s) == (20, 36, 8 + 5 * 12 + 12)
    roof = harness.load_module("metrics", "sharded_ingest_roofline")
    assert roof.ROUTED_PAIR_BYTES == se._PAIR_BYTES == 17
    assert roof.least_bytes(100, 10, 40, 4, q, c, s) == pytest.approx(
        2 * 20 * 100 + 20 * 10 + 2 * 80 * 100 / 4 + 2 * (36 + 17) * 40 / 4)


# metric: (counts in the window, counters, reading)
ROUTE_CASES = {
    "route_pairs_per_tick": ([("shard.route_pairs", 600),
                              ("shard.route_pairs", 200)], {"ticks": 16},
                             50.0),
    "route_bytes_per_tick": ([("shard.route_bytes", 4096)] * 2,
                             {"ticks": 32}, 256.0),
    "route_skew": ([("shard.pairs_max", 110), ("shard.pairs_mean", 100),
                    ("shard.pairs_max", 130), ("shard.pairs_mean", 100)],
                   {}, 1.2),
}


def _route_run(w0, counters):
    return SimpleNamespace(spans=SimpleNamespace(
        spans=[("window", w0, w0 + 100.0)]), counters=dict(counters))


@pytest.mark.parametrize("metric", sorted(ROUTE_CASES))
def test_route_metric_reads_the_window(metric):
    from repro import obs
    counts, counters, want = ROUTE_CASES[metric]
    for name, n in counts:
        obs.count(name, n)                               # before the window
    w0 = time.perf_counter()
    for name, n in counts:
        obs.count(name, n)                               # inside
    read = harness.load_module("metrics", metric).read
    assert read(_route_run(w0, counters)) == pytest.approx(want)
    # a window long before this process counted anything reads nothing
    assert read(_route_run(-1e6, counters)) is None


def test_device_metrics_read_the_sharded_program():
    """Per tick, from the trace's device seconds of ``sharded_ingest_many``
    (per chip), which ``ingest_ms_per_tick`` finds by its name's
    ``ingest_many``; the roofline over the least bytes at the chip's peak.
    None without that program in the trace."""
    from repro import obs
    from bench import peaks
    counters = {"ticks": 16, "query_events": 1000, "tweet_grams": 60,
                "n_shards": 4, "qstore_slot_bytes": 20,
                "cooc_slot_bytes": 36, "session_row_bytes": 80}
    w0 = time.perf_counter()
    obs.count("shard.route_pairs", 4000)
    run = SimpleNamespace(
        spans=SimpleNamespace(spans=[("window", w0, w0 + 100.0)]),
        counters=counters, device_kind="TPU v5 lite",
        summary=SimpleNamespace(program_s=lambda name: (  # as trace_reduce
            0.8 if name in "sharded_ingest_many" else None)))
    ms = harness.load_module("metrics", "ingest_ms_per_tick").read
    roof = harness.load_module("metrics", "sharded_ingest_roofline")
    assert ms(run) == pytest.approx(50.0)
    least = roof.least_bytes(1000, 60, 4000, 4, 20, 36, 80) / \
        peaks.device_peaks("TPU v5 lite")["hbm_bytes_s"]
    assert roof.read(run) == pytest.approx(100.0 * least / 0.8)
    run.summary = SimpleNamespace(program_s=lambda name: None)
    assert ms(run) is None and roof.read(run) is None


def test_driver_metrics_read_the_sharded_engine():
    """``host_syncs_per_tick`` and ``setup_compile_s`` read the sharded
    host driver as the mode drives it: one ``engine.sync`` a
    ``step_many``, and the replay program's compile in set-up."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro import obs
    from repro.core import sharded_engine as se
    from repro.core.engine import EngineConfig
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.streaming import ticks_to_stack
    eng = se.ShardedEngine(se.ShardedConfig(base=EngineConfig(
        query_capacity=1 << 10, cooc_capacity=1 << 12,
        session_capacity=1 << 8, rank_every=0)),
        Mesh(np.array(jax.devices()[:1]), ("shard",)))
    stream = SyntheticStream(StreamConfig(vocab_size=64, n_users=32,
                                          queries_per_tick=64,
                                          tweets_per_tick=4), seed=5)
    stacks = [ticks_to_stack([stream.gen_tick(t) for t in range(i, i + 4)])
              for i in (0, 4)]
    eng.ingest_many.lower(eng.state, stacks[0]).compile()
    w0 = time.perf_counter()
    for stack in stacks:
        eng.step_many(stack)
    run = SimpleNamespace(spans=SimpleNamespace(
        spans=[("window", w0, time.perf_counter())]), counters={"ticks": 8})
    syncs, compile_s = (harness.load_module("metrics", m).read(run)
                        for m in ("host_syncs_per_tick", "setup_compile_s"))
    assert syncs == pytest.approx(2 / 8)
    assert compile_s > 0
    assert any(k.startswith("compile.") and "sharded_ingest_many" in k
               for k in obs.window(float("-inf"), w0))
