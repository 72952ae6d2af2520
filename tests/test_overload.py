"""Overload-control semantics (flash crowds, §1/§4).

Covers: the firehose workload generator (determinism, ~50x flash-crowd
volume scaling, bounded shape alphabet, spam/multilingual structure), the
degradation ladder's hysteresis, deterministic admission control
(hash-sampling + physical compaction), the shed-accounting property —
(events offered) == (events ingested) + (events counted shed) at EVERY
degradation level, for both hoses, with ranking governed the same way —
micro-batched service stepping vs per-tick stepping (bit-exact), crash ->
restore -> replay THROUGH an actively-shedding window (bit-exact vs the
uninterrupted degraded run), the slow-I/O chaos injector, and the
frontend's overload metrics surface.
"""
import numpy as np
import jax
import pytest

from repro.core.background import AssistanceService
from repro.core.decay import DecayConfig
from repro.core.engine import EngineConfig, rank_due
from repro.data.stream import QueryEvents
from repro.distributed.fault_tolerance import CheckpointManager
from repro.serving.serve import SuggestFrontend, pack_suggestions
from repro.streaming import (FirehoseLogReader, FirehoseLogWriter,
                             FirehoseWorkload, SLOConfig, SpamSpec,
                             SpikeSpec, WorkloadConfig, admit_events,
                             admit_tweets, bucket_size,
                             kill_writer_mid_segment, recover_service,
                             slow_io)
from repro.streaming.overload import DegradationLadder
from repro.streaming.replay import ReplayConfig
from proptest import property_test


def _cfg(policy="lazy", **kw):
    base = dict(query_capacity=1 << 11, cooc_capacity=1 << 13,
                session_capacity=1 << 10, session_window=3,
                decay_every=4, prune_every=6, rank_every=5,
                region_width=16, decay=DecayConfig(policy=policy))
    base.update(kw)
    return EngineConfig(**base)


def _wl(seed=3, spike_mult=50.0, spike_at=6, **kw):
    base = dict(vocab_per_lang=128, n_langs=3, n_users=500,
                base_queries_per_tick=64, base_tweets_per_tick=8,
                min_bucket=64, min_tweet_bucket=8,
                spikes=(SpikeSpec(t_start=spike_at, mult=spike_mult),),
                spam=SpamSpec(period=9, burst_ticks=2))
    base.update(kw)
    return FirehoseWorkload(WorkloadConfig(**base), seed=seed)


def _slo(**kw):
    """Thresholds pushed out of reach by default — tests that need ladder
    movement either force levels or pass explicit triggers."""
    base = dict(slo_ms=1e9, up_lag=1e9, compact_min=16)
    base.update(kw)
    return SLOConfig(**base)


def _assert_states_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"state leaf {i}")


# ---------------------------------------------------------------------------
# Workload generator
# ---------------------------------------------------------------------------

def test_workload_deterministic_and_spike_scales_volume():
    wl_a, wl_b = _wl(seed=9), _wl(seed=9)
    for t in (0, 5, 9, 14):
        ev_a, tw_a = wl_a.gen_tick(t)
        ev_b, tw_b = wl_b.gen_tick(t)   # pure in (seed, t): no call-order dep
        for x, y in zip(ev_a, ev_b):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(tw_a.grams, tw_b.grams)
    calm = int(wl_a.gen_tick(4)[0].valid.sum())
    peak_t = 6 + 8    # past ramp, inside plateau
    peak = int(wl_a.gen_tick(peak_t)[0].valid.sum())
    assert peak > 30 * calm, (calm, peak)   # a genuine ~50x flash crowd
    assert wl_a.volume_mult(4) < 4.0 < wl_a.volume_mult(peak_t)
    # volume scaling is physical (array sizes grow), but the shape alphabet
    # stays tiny (power-of-4 buckets): the jitted paths cannot compile-storm
    shapes = {wl_a.gen_tick(t)[0].q_fp.shape for t in range(0, 30)}
    assert len(shapes) <= 4, shapes


def test_workload_spike_focus_and_spam_sessions():
    wl = _wl(seed=1)
    ev, tw = wl.gen_tick(6 + 8)
    spike_fps = {int(wl.fps[i]) for i in wl.spike_terms[0]}
    frac = np.isin(ev.q_fp[ev.valid].astype(np.uint64),
                   np.array(sorted(spike_fps), np.uint64)).mean()
    assert frac > 0.4, frac   # the crowd asks about the event
    # spam burst: payload queries come from a tiny bot session pool
    ev_s, _ = wl.gen_tick(18)   # period=9, burst_ticks=2 -> 18 is a burst
    spam_fps = set(int(wl.fps[i]) for i in wl.spam_idx)
    m = np.isin(ev_s.q_fp[ev_s.valid].astype(np.uint64),
                np.array(sorted(spam_fps), np.uint64))
    assert m.any()
    assert len(np.unique(ev_s.sess_fp[ev_s.valid][m])) <= 8  # n_bots


def test_workload_sessions_are_language_local():
    wl = _wl(seed=4, spike_mult=0.0, spam=None)
    fp2lang = {}
    for lang in range(wl.cfg.n_langs):
        for i in range(*wl.lang_slice[lang].indices(len(wl.vocab))):
            fp2lang[int(wl.fps[i])] = lang
    for t in range(4):
        ev, _ = wl.gen_tick(t)
        sess2langs = {}
        for s, q in zip(ev.sess_fp[ev.valid], ev.q_fp[ev.valid]):
            sess2langs.setdefault(int(s), set()).add(fp2lang[int(q)])
        assert all(len(ls) == 1 for ls in sess2langs.values())


def test_bucket_size():
    assert bucket_size(0, 64, 4096) == 64
    assert bucket_size(64, 64, 4096) == 64
    assert bucket_size(65, 64, 4096) == 256
    assert bucket_size(10_000, 64, 4096) == 4096   # clamped


# ---------------------------------------------------------------------------
# Ladder + admission
# ---------------------------------------------------------------------------

def test_ladder_hysteresis_and_force():
    cfg = SLOConfig(up_lag=4.0, down_lag=1.0, up_ticks=3, down_ticks=2,
                    slo_ms=50.0)
    lad = DegradationLadder(cfg)
    # needs up_ticks CONSECUTIVE hot observations to move one rung
    assert lad.observe(lag=10) == 0
    assert lad.observe(lag=10) == 0
    assert lad.observe(lag=0.0) == 0          # neutral resets the streak
    for _ in range(2):
        assert lad.observe(lag=10) == 0
    assert lad.observe(lag=10) == 1           # third consecutive -> level 1
    # latency breach escalates too; one rung at a time
    for _ in range(2):
        lad.observe(lag=0.0, p95_ms=100.0)
    assert lad.observe(lag=0.0, p95_ms=100.0) == 2
    # cool-down needs down_ticks consecutive clear ticks
    assert lad.observe(lag=0.0, p95_ms=10.0) == 2
    assert lad.observe(lag=0.0, p95_ms=10.0) == 1
    assert lad.level_ticks[2] > 0 and lad.n_escalations == 2
    assert lad.n_deescalations == 1
    # freelist pressure is a hot signal
    lad2 = DegradationLadder(cfg)
    for _ in range(3):
        lad2.observe(lag=0.0, free_frac=0.01)
    assert lad2.level == 1
    # force pins (scripted chaos schedules), unpinning resumes hysteresis
    lad.force(3)
    assert lad.observe(lag=0.0, p95_ms=1.0) == 3
    lad.force(None)
    assert lad.observe(lag=0.0, p95_ms=1.0) == 3   # needs down_ticks again
    assert lad.observe(lag=0.0, p95_ms=1.0) == 2


def test_admit_events_deterministic_tail_sampling():
    rng = np.random.default_rng(0)
    B = 256
    # a spike-shaped tick: the tail source dominates, so sampling it is
    # what actually frees capacity
    src = np.where(np.arange(B) % 8 == 0,
                   rng.integers(0, 2, B), 2).astype(np.int32)
    ev = QueryEvents(sess_fp=rng.integers(1, 2**63, B).astype(np.uint64),
                     q_fp=rng.integers(1, 2**63, B).astype(np.uint64),
                     src=src,
                     valid=np.arange(B) < 200)
    cfg = _slo(tail_keep=0.1)
    for lvl in (0, 1, 2):
        out, shed = admit_events(ev, lvl, cfg)
        assert out is ev and shed == 0        # identity below level 3
        assert admit_tweets(None, lvl, cfg) == (None, 0)
    out, shed = admit_events(ev, 3, cfg)
    out2, shed2 = admit_events(ev, 3, cfg)    # pure hash: rerun == same
    assert shed == shed2 and shed > 0
    for x, y in zip(out, out2):
        np.testing.assert_array_equal(x, y)
    kept = int(out.valid.sum())
    assert kept + shed == 200
    # only tail-source events are shed; the rest survive, order preserved
    non_tail = ev.q_fp[ev.valid & (ev.src != cfg.tail_src)]
    np.testing.assert_array_equal(
        out.q_fp[out.valid][np.isin(out.q_fp[out.valid], non_tail)],
        non_tail)
    n_tail = int((ev.valid & (ev.src == cfg.tail_src)).sum())
    tail_kept = kept - len(non_tail)
    assert 0.05 < tail_kept / n_tail < 0.6    # ~tail_keep survives
    # physical compaction: a power-of-4 bucket, not the offered shape
    assert out.q_fp.shape[0] == bucket_size(kept, cfg.compact_min, B) < B


# ---------------------------------------------------------------------------
# Shed accounting — the never-silent property
# ---------------------------------------------------------------------------

@property_test(n_cases=4)
def test_shed_accounting_balances_at_every_level(rng):
    """(offered) == (ingested) + (counted shed) at every ladder level, for
    the query hose, the tweet firehose, AND ranking cycles."""
    level = int(rng.integers(0, 4))
    wl = _wl(seed=int(rng.integers(1 << 20)), spike_mult=6.0, spike_at=2)
    svc = AssistanceService(_cfg(), slo=_slo())
    svc.overload.ladder.force(level)
    n = 12
    for t in range(n):
        svc.step(*wl.gen_tick(t), lag_hint=float(rng.integers(0, 6)))
    svc.drain()
    c = svc.overload.counters
    assert int(svc.rt.state.tick) == n            # nothing lost in a buffer
    assert c["n_offered_events"] == c["n_ingested_events"] + c["n_shed_events"]
    assert c["n_offered_tweets"] == c["n_ingested_tweets"] + c["n_shed_tweets"]
    if level >= 3:
        assert c["n_shed_tweets"] == c["n_offered_tweets"] > 0
        assert c["n_shed_events"] > 0
    else:
        assert c["n_shed_events"] == 0 and c["n_shed_tweets"] == 0
    rt_dues = sum(rank_due(svc.rt.cfg, t) for t in range(n))
    bg_dues = sum(rank_due(svc.bg.cfg, t) for t in range(n))
    assert c["n_rank_run_rt"] + c["n_shed_rank_rt"] == rt_dues
    assert c["n_rank_run_bg"] + c["n_shed_rank_bg"] == bg_dues
    if level >= 1:
        assert c["n_rank_run_rt"] == 0
    snap = svc.overload.stats_snapshot()
    assert snap["n_shed_total"] == (c["n_shed_events"] + c["n_shed_tweets"]
                                    + c["n_shed_rank_rt"]
                                    + c["n_shed_rank_bg"])
    assert sum(snap["level_ticks"]) == n


# ---------------------------------------------------------------------------
# Bit-exactness: batching and shedding never change what state is built
# ---------------------------------------------------------------------------

def test_batched_service_matches_pertick_service():
    """Micro-batched fused dispatch == per-tick stepping, bit for bit (lag
    pressure forces K up to batch_max mid-run)."""
    wl = _wl(seed=7, spike_mult=4.0, spike_at=3)
    a = AssistanceService(_cfg())                       # legacy per-tick
    b = AssistanceService(_cfg(), slo=_slo(batch_max=8, lag_batch=0.5))
    n = 14
    for t in range(n):
        ev, tw = wl.gen_tick(t)
        a.step(ev, tw)
        b.step(ev, tw, lag_hint=4.0 if t >= 4 else 0.0)
    b.drain()
    assert b.overload.counters["n_flushes"] < n         # batching happened
    _assert_states_equal(a.rt.state, b.rt.state)
    _assert_states_equal(a.bg.state, b.bg.state)


def test_crash_recover_mid_shed_bitexact(tmp_path):
    """Crash INSIDE an actively-shedding window: restore + replay of the
    admitted log == the uninterrupted degraded run, bit for bit. This is
    the log-append-first + pure-hash-admission contract."""
    schedule = lambda t: 0 if t < 3 else (3 if t < 10 else 1)
    wl = _wl(seed=13, spike_mult=8.0, spike_at=3)
    n, crash_at, snap_at = 16, 10, 6

    def run(upto, svc=None, writer=None, log_dir=None, ckpts=None):
        if svc is None:
            svc = AssistanceService(_cfg(), slo=_slo())
        start = int(svc.rt.state.tick)
        for t in range(start, upto):
            svc.overload.ladder.force(schedule(t))
            la = (lambda tk, e, w: writer.append(tk, e, w)) if writer else None
            svc.step(*wl.gen_tick(t), log_append=la,
                     lag_hint=3.0 if 4 <= t < 9 else 0.0)
            if t == snap_at - 1 and ckpts is not None:
                svc.drain()          # snapshot needs the engines caught up
                svc.save_snapshot(*ckpts)
        svc.drain()
        return svc

    # A: uninterrupted degraded run (no durability involved)
    a = run(n)

    # B: same run against a log, crash at tick 10 (mid-shed, level 3),
    # recover from the tick-6 snapshot + admitted-log replay, continue
    log_dir = str(tmp_path / "log")
    ckpts = (CheckpointManager(str(tmp_path / "rt"), full_interval=3),
             CheckpointManager(str(tmp_path / "bg"), full_interval=3))
    w = FirehoseLogWriter(log_dir, ticks_per_segment=2)
    run(crash_at, writer=w, log_dir=log_dir, ckpts=ckpts)
    w.close()   # 10 appended ticks seal cleanly; the process "dies" here

    rec, rstats = recover_service(_cfg(), ckpts[0], ckpts[1], log_dir,
                                  ReplayConfig(chunk_ticks=4))
    assert rstats["rt"]["restored_step"] == snap_at
    assert rstats["rt"]["n_ticks"] == crash_at - snap_at   # replayed tail
    b = AssistanceService(rt=rec.rt, bg=rec.bg, slo=_slo())
    w2 = FirehoseLogWriter(log_dir, ticks_per_segment=2)
    b = run(n, svc=b, writer=w2)
    w2.close()

    _assert_states_equal(a.rt.state, b.rt.state)
    _assert_states_equal(a.bg.state, b.bg.state)
    # the log recorded the ADMITTED stream: level-3 ticks carry no tweets
    r = FirehoseLogReader(log_dir)
    logged = {t: (ev, tw) for t, ev, tw in r.read_ticks(0)}
    assert logged[5][1] is None and logged[12][1] is not None


# ---------------------------------------------------------------------------
# Chaos: slow I/O + torn writer under flash-crowd traffic
# ---------------------------------------------------------------------------

def test_slow_io_injector(tmp_path, monkeypatch):
    """Counts the injected sleeps instead of timing them: a wall-clock
    bound fails under a loaded test machine for reasons of its own."""
    import time
    sleeps = []
    monkeypatch.setattr(time, "sleep", sleeps.append)
    wl = _wl(seed=2, spike_mult=0.0, spam=None)
    w = FirehoseLogWriter(str(tmp_path), ticks_per_segment=2)
    slow_io(w, ("flush",), 0.05)
    for t in range(4):
        w.append(t, *wl.gen_tick(t))
    assert sleeps == [0.05, 0.05]             # two seals, two sleeps
    w._slow_io_undo()
    for t in range(4, 8):
        w.append(t, *wl.gen_tick(t))
    assert sleeps == [0.05, 0.05]             # undone: no further sleeps
    assert FirehoseLogReader(str(tmp_path)).last_tick() == 7


def test_chaos_slow_io_torn_writer_spike(tmp_path):
    """The full chaos sandwich: flash-crowd traffic + slow disk + a writer
    killed mid-segment; recovery truncates the torn tail and the service
    keeps its accounting invariant throughout."""
    wl = _wl(seed=5, spike_mult=10.0, spike_at=2)
    log_dir = str(tmp_path / "log")
    w = FirehoseLogWriter(log_dir, ticks_per_segment=4)
    slow_io(w, ("flush",), 0.01)
    svc = AssistanceService(_cfg(), slo=_slo(up_lag=2.0, up_ticks=2,
                                             down_ticks=3))
    la = lambda t, e, tw: w.append(t, e, tw)
    for t in range(7):
        svc.step(*wl.gen_tick(t), log_append=la, lag_hint=3.0)
    torn = kill_writer_mid_segment(w)         # dies with a partial buffer
    assert torn is not None
    svc.drain()
    c = svc.overload.counters
    assert c["n_offered_events"] == c["n_ingested_events"] + c["n_shed_events"]
    r = FirehoseLogReader(log_dir)
    # torn tail truncated (spike-driven shape rotations may have sealed
    # extra segments early, so the exact boundary varies — but the torn
    # ticks never become readable)
    assert r.last_tick() is not None and r.last_tick() < 6
    assert r.n_unmanifested_files == 1
    r.repair()
    assert FirehoseLogReader(log_dir).n_unmanifested_files == 0


# ---------------------------------------------------------------------------
# Frontend metrics surface
# ---------------------------------------------------------------------------

def test_frontend_overload_metrics(tmp_path):
    wl = _wl(seed=8, spike_mult=0.0, spam=None)
    svc = AssistanceService(_cfg(), slo=_slo())
    svc.overload.ladder.force(3)
    for t in range(6):
        svc.step(*wl.gen_tick(t))
    svc.drain()
    rt_dir = str(tmp_path / "rt")
    sugg_ckpt = CheckpointManager(rt_dir)
    svc.rt.run_rank_cycle()
    sugg_ckpt.save(5, pack_suggestions(svc.rt.suggestions),
                   meta={"tick": 5, "overload": svc.overload.stats_snapshot()})
    f = SuggestFrontend(rt_dir)
    f.poll()
    m = f.metrics()
    assert m["shed_level"] == 3 and m["shed_level_name"] == "sample_ingest"
    assert m["n_shed_events"] > 0 and m["n_shed_total"] > 0
    assert m["n_shed_rank"] == (svc.overload.counters["n_shed_rank_rt"]
                                + svc.overload.counters["n_shed_rank_bg"])
    assert m["step_p95_ms"] is not None and m["step_p95_ms"] > 0
    assert m["overload"]["n_offered_events"] > 0
    # a backend without overload control surfaces None, not a crash
    plain_dir = str(tmp_path / "plain")
    CheckpointManager(plain_dir).save(
        1, pack_suggestions(svc.rt.suggestions), meta={"tick": 1})
    f2 = SuggestFrontend(plain_dir)
    f2.poll()
    m2 = f2.metrics()
    assert m2["shed_level"] is None and m2["overload"] is None
    assert m2["step_p95_ms"] is None and m2["n_shed_rank"] is None


def test_legacy_service_path_unchanged(tmp_path):
    """Without ``slo`` the service still steps per tick; ``log_append``
    fires before ingestion and ``drain`` is a no-op."""
    wl = _wl(seed=6, spike_mult=0.0, spam=None)
    svc = AssistanceService(_cfg())
    assert svc.overload is None
    w = FirehoseLogWriter(str(tmp_path), ticks_per_segment=2)
    seen = []
    for t in range(4):
        ev, tw = wl.gen_tick(t)
        svc.step(ev, tw, log_append=lambda tk, e, x: (seen.append(tk),
                                                      w.append(tk, e, x)))
    assert seen == [0, 1, 2, 3]
    assert svc.drain() is None
    assert int(svc.rt.state.tick) == 4
