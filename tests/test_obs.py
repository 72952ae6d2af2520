"""The program's own spans and counters (``repro.obs``): records, windows,
the bounded buffer, the compile listener, the profiler's host plane, the
named scopes of the rank and ingest programs, and the host syncs of the
engine's host loop."""
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import ranking
from repro.core.decay import DecayConfig
from repro.core.engine import (EngineConfig, SearchAssistanceEngine,
                               TickStack, ingest_many, init_state)
from repro.core.hashing import split_fp
from repro.data.stream import StreamConfig, SyntheticStream

RANK_SCOPES = ("rank.lookup_src", "rank.lookup_dst", "rank.score_gate",
               "rank.compact", "rank.group_sort", "rank.grid", "rank.topk",
               "rank.gather_out")
INGEST_SCOPES = ("ingest.qstore", "ingest.sessions", "ingest.cooc_insert",
                 "ingest.tweets", "ingest.maintenance")


def _cfg():
    return EngineConfig(query_capacity=1 << 10, cooc_capacity=1 << 12,
                        session_capacity=1 << 9, session_window=3,
                        decay_every=4, prune_every=6, rank_every=5,
                        decay=DecayConfig(policy="lazy"))


def _stack(n_ticks, t0=0):
    stream = SyntheticStream(
        StreamConfig(vocab_size=256, n_users=120, queries_per_tick=96,
                     tweets_per_tick=8, tweet_words=3, tweet_grams=4),
        seed=5)
    batches = [stream.gen_tick(t) for t in range(t0, t0 + n_ticks)]
    s_hi, s_lo = split_fp(np.stack([b[0].sess_fp for b in batches]))
    q_hi, q_lo = split_fp(np.stack([b[0].q_fp for b in batches]))
    g_hi, g_lo = split_fp(np.stack([b[1].grams for b in batches]))
    return TickStack(
        jnp.asarray(s_hi), jnp.asarray(s_lo), jnp.asarray(q_hi),
        jnp.asarray(q_lo),
        jnp.asarray(np.stack([b[0].src for b in batches]), jnp.int32),
        jnp.asarray(np.stack([b[0].valid for b in batches])),
        jnp.asarray(g_hi), jnp.asarray(g_lo),
        jnp.asarray(np.stack([b[1].valid for b in batches])))


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

def test_span_and_count_records_and_totals():
    rec = obs.Recorder()
    t0 = time.perf_counter()
    with rec.span("a.b"):
        time.sleep(0.01)
    rec.count("a.n", 3)
    rec.count("a.n")
    t1 = time.perf_counter()
    (name, s0, s1), = rec.spans
    assert name == "a.b" and t0 <= s0 and s1 - s0 >= 0.01 and s1 <= t1
    assert [(n, k) for n, _, k in rec.counts] == [("a.n", 3), ("a.n", 1)]
    tot = rec.totals()
    assert tot["a.n"] == (4, 0.0)
    assert tot["a.b"][0] == 1 and tot["a.b"][1] == pytest.approx(s1 - s0)
    assert rec.window(t0, t1) == tot


def test_window_clips_spans_and_counts_to_its_bounds():
    rec = obs.Recorder()
    b = -1000.0                           # long before any real record
    rec.add_span("x", b, b + 10)          # half inside
    rec.add_span("x", b + 12, b + 13)     # inside
    rec.add_span("x", b + 30, b + 40)     # after
    rec.add_span("y", b - 5, b - 1)       # before
    rec.count("c", 2)                     # now: after
    w = rec.window(b + 5, b + 20)
    assert w["x"] == (2, pytest.approx(6.0))
    assert "y" not in w and "c" not in w
    assert rec.window(b - 10, b + 100)["x"] == (3, pytest.approx(21.0))
    assert rec.window(b, time.perf_counter())["c"] == (2, 0.0)


def test_records_are_bounded_totals_are_not():
    rec = obs.Recorder()
    for i in range(obs.RECORDS + 5):
        rec.add_span("s", float(i), float(i) + 0.5)
        rec.count("n")
    assert len(rec.spans) == len(rec.counts) == obs.RECORDS
    assert rec.spans[0] == ("s", 5.0, 5.5)       # the oldest went first
    assert rec.totals()["s"] == (obs.RECORDS + 5,
                                 pytest.approx(0.5 * (obs.RECORDS + 5)))
    assert rec.totals()["n"][0] == obs.RECORDS + 5


def test_compile_listener_records_fun_name():
    def obs_probe_fn(x):
        return x * 3 + 1
    t0 = time.perf_counter()
    jax.jit(obs_probe_fn)(jnp.ones(7)).block_until_ready()
    w = obs.window(t0, time.perf_counter())
    n, seconds = w["compile.jit(obs_probe_fn)"]
    assert n == 1 and seconds > 0


def test_profiler_trace_holds_repro_span_on_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("test.probe"):
            jnp.ones(4).block_until_ready()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "repro.test.probe" in names


# ---------------------------------------------------------------------------
# named scopes in the device programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def op_scopes():
    """The scope names in the op_name metadata of the compiled rank and
    ingest programs, at tiny capacities on the CPU."""
    cfg = _cfg()
    st = init_state(cfg)
    rank = ranking.ranking_cycle.lower(
        st.cooc, st.qstore, cfg.rank, decay_cfg=cfg.decay,
        now=st.tick).compile().as_text()
    ingest = ingest_many.lower(st, _stack(2), cfg=cfg).compile().as_text()
    scope = re.compile(r'op_name="[^"]*?((?:rank|ingest)\.[a-z_]+)/')
    return {"rank": set(scope.findall(rank)),
            "ingest": set(scope.findall(ingest))}


@pytest.mark.parametrize("program,name",
                         [("rank", s) for s in RANK_SCOPES]
                         + [("ingest", s) for s in INGEST_SCOPES])
def test_program_ops_carry_scope(op_scopes, program, name):
    assert name in op_scopes[program]


# ---------------------------------------------------------------------------
# host syncs and spans of the engine's host loop
# ---------------------------------------------------------------------------

def _during(fn):
    t0 = time.perf_counter()
    fn()
    return obs.window(t0, time.perf_counter())


@pytest.fixture(scope="module")
def engine():
    eng = SearchAssistanceEngine(_cfg())
    eng.step_many(_stack(8))
    return eng


# one per blocking read in the call's code: int(tick) before and after
# ingest_many; int(tick), then the table's n_rows, n_overflow and lookup
# stats in one read, after the rank program
@pytest.mark.parametrize("call,syncs", [("step_many", 2),
                                        ("run_rank_cycle", 2)])
def test_engine_syncs_per_call(engine, call, syncs):
    stack = _stack(4, t0=int(engine.state.tick))
    w = _during(lambda: engine.step_many(stack) if call == "step_many"
                else engine.run_rank_cycle())
    assert w["engine.syncs"][0] == syncs
    assert w["engine.sync"][0] == syncs


def test_rank_cycle_spans_and_rows_exported(engine):
    w = _during(engine.run_rank_cycle)
    assert engine.suggestions
    assert w["rank.wait"][0] == w["rank.to_host"][0] == 1
    assert w["rank.rows_exported"][0] == len(engine.suggestions)


def test_rank_cycle_counts_lookup_rounds_and_tail_rows():
    """At a cooc capacity of 2^16 the rank cycle's two marginal lookups
    (2^16 rows each, ~1,500 live) run one full-width round and finish in
    the tail arena; the cycle counts each lookup's full-width rounds and
    tail rows once, as the rank program returns them, within the cycle's
    two host syncs."""
    cfg = dataclasses.replace(_cfg(), cooc_capacity=1 << 16)
    eng = SearchAssistanceEngine(cfg)
    eng.step_many(_stack(16))
    want = np.asarray(ranking.ranking_cycle(
        eng.state.cooc, eng.state.qstore, cfg.rank, decay_cfg=cfg.decay,
        now=eng.state.tick).lookup_stats)
    t0 = time.perf_counter()
    eng.run_rank_cycle()
    t1 = time.perf_counter()
    counts = [(name, n) for name, t, n in obs.RECORDER.counts
              if t0 <= t <= t1]
    got = [[n for name, n in counts if name == f"rank.lookup_{k}"]
           for k in ("full_rounds", "tail_rows")]
    assert np.array_equal(np.asarray(got).T, want)
    assert want.shape == (2, 2)
    assert (want[:, 0] == 1).all() and (want[:, 1] > 0).all()
    assert sum(name == "engine.syncs" for name, _ in counts) == 2
