"""Sharded (beyond-paper) engine == unsharded engine == plain reference, on
8 virtual devices.

Runs in a subprocess because the 8-device XLA flag must be set before jax
initializes (the main pytest process keeps the default 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(script: str, ok: str) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTEST_ALLOW_DEVICES"] = "1"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert ok in r.stdout


_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro import obs
    from repro.core.engine import EngineConfig, SearchAssistanceEngine
    from repro.core import sharded_engine as se, stores
    from repro.core.reference import ReferenceEngine
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.streaming import ticks_to_stack

    assert len(jax.devices()) == 8
    n = 8
    mesh = Mesh(np.array(jax.devices()).reshape(n), ("shard",))
    # 320 query events a tick: two whole ingest quanta of 128 and a short
    # third; 20 tweets a tick: not a multiple of the shards
    ecfg = EngineConfig(query_capacity=1<<12, cooc_capacity=1<<15,
                        session_capacity=1<<12, session_window=4,
                        decay_every=4, rank_every=0, ingest_quantum=128)
    scfg = se.ShardedConfig(base=ecfg, n_salts=2, route_capacity=256,
                            tweet_route_capacity=1024)
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=200,
                                          queries_per_tick=320,
                                          tweets_per_tick=20), seed=5)
    ticks = [stream.gen_tick(t) for t in range(8)]
    stacks = [ticks_to_stack(ticks[i:i + 4]) for i in (0, 4)]
    eng = se.ShardedEngine(scfg, mesh)
    one = SearchAssistanceEngine(ecfg)
    ref = ReferenceEngine(ecfg)
    for s in stacks:
        syncs = obs.totals().get("engine.syncs", (0, 0.0))[0]
        eng.step_many(s)
        assert obs.totals()["engine.syncs"][0] == syncs + 1
        one.step_many(s)
    for ev, tw in ticks:
        ref.step(ev, tw)
    st = eng.state
    assert eng.tick == 8 == int(one.state.tick)
    assert np.asarray(st.n_route_drop).sum() == 0, "routing overflow"
    assert obs.totals()["shard.route_pairs"][0] \\
        == int(np.asarray(st.n_routed).sum())

    # the query store: every replica bit-identical, and equal to the
    # unsharded engine's
    for leaf, want in zip(jax.tree.leaves(st.qstore),
                          jax.tree.leaves(one.state.qstore)):
        reps = [np.asarray(s.data) for s in leaf.addressable_shards]
        for r in reps[1:]:
            np.testing.assert_array_equal(r, reps[0])
        np.testing.assert_array_equal(reps[0], np.asarray(want))

    # cooccurrences: each pair in the shard that owns it, and the union the
    # unsharded store in keys and counts; weights to float32 rounding, as
    # a pair's adds reach its shard in another order than on one device
    for i in range(n):
        e = stores.export_live(se._shard_view(st.cooc, i, n))
        own = np.asarray(se.pair_owner(*(jnp.asarray(e[k]) for k in
                         ("src_hi", "src_lo", "dst_hi", "dst_lo")), 2, n))
        assert (own == i).all(), (i, own)
    pairs = se.export_sharded_pairs(scfg, st)
    u = stores.export_live(one.state.cooc)
    order = np.lexsort((u["dst_lo"], u["dst_hi"], u["src_lo"], u["src_hi"]))
    u = {k: v[order] for k, v in u.items()}
    for k in ("src_hi", "src_lo", "dst_hi", "dst_lo", "count", "last_tick"):
        np.testing.assert_array_equal(pairs[k], u[k], err_msg=k)
    np.testing.assert_allclose(pairs["weight"], u["weight"], rtol=1e-6)

    # sessions: the union of the shards' rows is the unsharded store's
    ss = se.export_sharded_sessions(st)
    us = one.state.sessions
    live = np.asarray((us.key_hi != 0) | (us.key_lo != 0))
    ko = np.lexsort((np.asarray(us.key_lo)[live],
                     np.asarray(us.key_hi)[live]))
    for k in se._SESS_COLS:
        np.testing.assert_array_equal(
            ss[k], np.asarray(getattr(us, k))[live][ko], err_msg=k)

    # the merged top-k against the plain reference: each pair lives in one
    # shard, so the merged lists are the whole store's top-k
    merged = se.merge_sharded_suggestions(
        se.make_sharded_rank(scfg, mesh)(st), ecfg.rank.top_k)
    want = ref.rank_cycle()
    assert merged and set(merged) == set(want), (len(merged), len(want))
    for f in want:
        ms = [s for _, s in merged[f]]
        ws = [s for _, s in want[f]]
        assert len(ms) == len(ws), (f, merged[f], want[f])
        np.testing.assert_allclose(ms, ws, rtol=5e-3, atol=1e-4)
    print(f"SHARDED_OK {len(merged)} sources, {pairs['src_hi'].size} pairs")
""")


@pytest.mark.slow
def test_sharded_engine_matches_unsharded_subprocess():
    """Queries in several ingest quanta and tweets through
    ``ShardedEngine.step_many``: the stores equal the unsharded engine's
    on the same ``TickStack``s, the query-store replicas are bit-identical,
    and the merged top-k is the plain reference's."""
    _run(_SCRIPT, "SHARDED_OK")


_OVERFLOW_SCRIPT = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro import obs
    from repro.core.engine import EngineConfig
    from repro.core import sharded_engine as se
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.streaming import ticks_to_stack

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    ecfg = EngineConfig(query_capacity=1<<12, cooc_capacity=1<<14,
                        session_capacity=1<<10, session_window=4,
                        rank_every=0, ingest_quantum=128)
    stream = SyntheticStream(StreamConfig(vocab_size=64, n_users=32,
                                          queries_per_tick=256,
                                          tweets_per_tick=16), seed=3)
    stack = ticks_to_stack([stream.gen_tick(t) for t in range(6)])
    got = {}
    for cap in (8, 1024):
        eng = se.ShardedEngine(se.ShardedConfig(
            base=ecfg, route_capacity=cap, tweet_route_capacity=cap), mesh)
        before = obs.totals().get("shard.route_drops", (0, 0.0))[0]
        eng.step_many(stack)
        drops = int(np.asarray(eng.state.n_route_drop).sum())
        assert obs.totals()["shard.route_drops"][0] - before == drops
        got[cap] = (drops, int(np.asarray(eng.state.n_routed).sum()))
    (d_small, r_small), (d_big, r_big) = got[8], got[1024]
    # too small a bucket drops pairs and counts every one it drops
    assert d_small > 0 and d_big == 0, got
    assert r_small + d_small == r_big, got
    print("OVERFLOW_OK", got)
""")


@pytest.mark.slow
def test_sharded_route_overflow_is_counted_subprocess():
    """A route capacity set too small shows in ``n_route_drop`` and in the
    ``shard.route_drops`` counter, and routed + dropped pairs add up to
    what a roomy capacity routes."""
    _run(_OVERFLOW_SCRIPT, "OVERFLOW_OK")


_REPLAY_SCRIPT = textwrap.dedent("""
    import tempfile
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core.engine import EngineConfig
    from repro.core import sharded_engine as se
    from repro.core.decay import DecayConfig
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.distributed.fault_tolerance import CheckpointManager
    from repro.streaming import ticks_to_stack

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("shard",))
    ecfg = EngineConfig(query_capacity=1<<12, cooc_capacity=1<<15,
                        session_capacity=1<<12, session_window=4,
                        decay_every=3, prune_every=5, rank_every=0,
                        decay=DecayConfig(policy="lazy"))
    scfg = se.ShardedConfig(base=ecfg, n_salts=2, route_capacity=1024)
    tick_step = se.make_sharded_tick_step(scfg, mesh)
    many = se.make_sharded_ingest_many(scfg, mesh)
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=200,
                                          queries_per_tick=192,
                                          tweets_per_tick=16), seed=5)
    ticks = [stream.gen_tick(t) for t in range(8)]
    one = [jax.tree.map(lambda x: x[0], ticks_to_stack([tk])) for tk in ticks]

    # uninterrupted live run (one full tick step per tick)
    live = se.init_sharded_state(scfg, mesh)
    for tk in one:
        live = tick_step(live, tk)

    # crash after tick 4: snapshot + parallel catch-up replay of the tail.
    # The snapshots are DELTA-CHAINED (full_interval=4): tick 2 writes the
    # full, tick 4 writes only the changed leading rows of each shard-
    # stacked leaf; restore composes the chain transparently.
    half = se.init_sharded_state(scfg, mesh)
    ckpt = CheckpointManager(tempfile.mkdtemp(), full_interval=4)
    for i, tk in enumerate(one[:4]):
        half = tick_step(half, tk)
        if i in (1, 3):
            se.save_sharded_snapshot(half, ckpt)
    assert ckpt.last_save_kind == "delta", ckpt.last_save_kind
    restored, log_tick = se.restore_sharded_snapshot(scfg, mesh, ckpt)
    assert log_tick == 4
    caught_up = many(restored, ticks_to_stack(ticks[4:]))
    la, _ = jax.tree.flatten(live); lb, _ = jax.tree.flatten(caught_up)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"leaf {i}")
    print("SHARDED_REPLAY_OK tick", int(np.asarray(caught_up.tick)))
""")


@pytest.mark.slow
def test_sharded_replay_matches_live_subprocess():
    """Snapshot + fused parallel replay == uninterrupted sharded run
    (bit-for-bit), queries and tweets, on 8 virtual devices."""
    _run(_REPLAY_SCRIPT, "SHARDED_REPLAY_OK")


_RESHARD_SCRIPT = textwrap.dedent("""
    import tempfile
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core.engine import EngineConfig
    from repro.core import sharded_engine as se
    from repro.core.decay import DecayConfig
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.distributed.elastic import live_reshard, sharded_pressure
    from repro.streaming import ticks_to_stack
    from repro.streaming.log import FirehoseLogWriter

    LAYOUT = "%(layout)s"
    TWEETS = %(tweets)d
    devs = np.array(jax.devices())
    mesh2 = Mesh(devs[:2], ("shard",))
    mesh4 = Mesh(devs[:4], ("shard",))
    ecfg = EngineConfig(query_capacity=1<<12, cooc_capacity=1<<15,
                        session_capacity=1<<12, session_window=4,
                        decay_every=3, prune_every=5, rank_every=0,
                        cooc_layout=LAYOUT, region_width=16,
                        decay=DecayConfig(policy="lazy"))
    scfg = se.ShardedConfig(base=ecfg, n_salts=2, route_capacity=1024)
    step2 = se.make_sharded_tick_step(scfg, mesh2)
    step4 = se.make_sharded_tick_step(scfg, mesh4)
    rank2 = se.make_sharded_rank(scfg, mesh2)
    rank4 = se.make_sharded_rank(scfg, mesh4)
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=200,
                                          queries_per_tick=192,
                                          tweets_per_tick=TWEETS), seed=5)
    raw = [stream.gen_tick(t) for t in range(12)]
    ticks = [jax.tree.map(lambda x: x[0], ticks_to_stack([tk])) for tk in raw]
    logd = tempfile.mkdtemp()
    w = FirehoseLogWriter(logd, ticks_per_segment=2)
    for t, (ev, tw) in enumerate(raw[:10]):  # the log ends inside the split
        w.append(t, ev, tw if TWEETS else None)  # window: 10,11 post-swap
    w.close()

    def top1(m):
        return {f: max(s for _, s in v) for f, v in m.items() if v}

    def run_with_live_split():
        # 2-shard live run to tick 8; the split window covers ticks 8-9:
        # the OLD layout keeps serving them while the snapshot is
        # re-partitioned to 4 shards and caught up from the shared log.
        st = se.init_sharded_state(scfg, mesh2)
        for tk in ticks[:8]:
            st = step2(st, tk)
        old = st
        for tk in ticks[8:10]:
            old = step2(old, tk)           # zero downtime: old serves 8,9
        new, stats = live_reshard(scfg, st, 4, mesh4, log_dir=logd,
                                  chunk_ticks=4)
        assert stats["old_n"] == 2 and stats["new_n"] == 4
        assert stats["replayed_ticks"] == 2, stats
        assert stats["n_pair_drop"] == 0 and stats["n_sess_drop"] == 0
        assert int(np.asarray(new.tick)) == 10 == int(np.asarray(old.tick))
        m_old = se.merge_sharded_suggestions(rank2(old), ecfg.rank.top_k)
        m_new = se.merge_sharded_suggestions(rank4(new), ecfg.rank.top_k)
        assert m_old, "old layout must answer throughout the window"
        # the handoff loses no queries ...
        assert set(m_new) == set(m_old), (len(m_new), len(m_old))
        # ... or mass: every pair moves whole to its new owner, so each
        # query's top score carries across the handoff unchanged
        t_old, t_new = top1(m_old), top1(m_new)
        assert all(abs(t_new[f] - t_old[f]) <= 1e-5 for f in t_old)
        for tk in ticks[10:]:              # swap: serve live on 4 shards
            new = step4(new, tk)
        return st, new

    def same(x, y):
        la, ta = jax.tree.flatten(x); lb, tb = jax.tree.flatten(y)
        assert ta == tb
        for i, (p, q) in enumerate(zip(la, lb)):
            np.testing.assert_array_equal(np.asarray(p), np.asarray(q),
                                          err_msg=f"leaf {i}")

    st8, a = run_with_live_split()
    _, b = run_with_live_split()
    # schedule parity: an identical split schedule is bit-reproducible
    same(a, b)
    # and equals a clean run that resharded with the world stopped at tick
    # 8 and took ticks 8-11 live on 4 shards: the log tail (tweets too)
    # replays exactly what live ticks would have done
    clean, _ = se.reshard_sharded_state(scfg, st8, 4)
    for tk in ticks[8:]:
        clean = step4(clean, tk)
    same(a, clean)
    p = sharded_pressure(a, ecfg)
    assert p["route_drop"] == 0
    if LAYOUT == "region":
        assert 0.0 <= p["free_region_frac"] <= 1.0

    # scale back in: merge 4 -> 2 keeps every query answerable
    m4 = se.merge_sharded_suggestions(rank4(a), ecfg.rank.top_k)
    merged, mstats = live_reshard(scfg, a, 2, mesh2, log_dir=logd)
    assert mstats["new_n"] == 2 and mstats["replayed_ticks"] == 0
    m2 = se.merge_sharded_suggestions(rank2(merged), ecfg.rank.top_k)
    assert set(m2) == set(m4)
    print(f"RESHARD_OK {LAYOUT} {len(m2)} keys")
""")


@pytest.mark.slow
@pytest.mark.parametrize("layout,tweets", [("hash", 0), ("region", 0),
                                           ("hash", 16)],
                         ids=["hash", "region", "hash-tweets"])
def test_live_shard_split_merge_subprocess(layout, tweets):
    """Live 2->4 shard split under load (old layout answers the ticks that
    arrive during the window; the new layout catches up from the shared
    log), schedule-parity bit-exactness, equality with a clean run, no
    lost queries/mass across the handoff, and a 4->2 merge — on virtual
    devices; ``hash-tweets`` logs the firehose too."""
    _run(_RESHARD_SCRIPT % {"layout": layout, "tweets": tweets},
         "RESHARD_OK")


def test_shard_autoscaler_hysteresis():
    """Split/merge decisions need SUSTAINED pressure/idleness (hold_ticks),
    respect the min/max bounds, and trigger off any of the three signals
    (region freelist, replay lag, routing drops). Pure host logic."""
    from repro.distributed.elastic import AutoscaleConfig, ShardAutoscaler
    asc = ShardAutoscaler(AutoscaleConfig(hold_ticks=2, max_shards=8,
                                          min_shards=2))
    assert asc.observe(4, free_region_frac=0.05) == 4   # one spiky tick:
    assert asc.observe(4, free_region_frac=0.50) == 4   # no reshard (reset)
    assert asc.observe(4, free_region_frac=0.05) == 4
    assert asc.observe(4, free_region_frac=0.05) == 8   # held 2 -> split
    assert asc.observe(8, free_region_frac=0.90) == 8   # idleness holds too
    assert asc.observe(8, free_region_frac=0.90) == 4   # held 2 -> merge
    bounded = ShardAutoscaler(AutoscaleConfig(hold_ticks=1, max_shards=4,
                                              min_shards=4))
    assert bounded.observe(4, free_region_frac=0.01) == 4   # at max_shards
    assert bounded.observe(4, free_region_frac=0.90) == 4   # at min_shards
    multi = ShardAutoscaler(AutoscaleConfig(hold_ticks=1, max_shards=8))
    assert multi.observe(2, free_region_frac=None, lag_ticks=9.0) == 4
    assert multi.observe(2, free_region_frac=None, route_drop_rate=1.0) == 4
