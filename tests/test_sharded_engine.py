"""Sharded (beyond-paper) engine == unsharded engine, on 8 virtual devices.

Runs in a subprocess because the 8-device XLA flag must be set before jax
initializes (the main pytest process keeps the default 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.engine import EngineConfig, SearchAssistanceEngine
    from repro.core import sharded_engine as se
    from repro.core.hashing import split_fp
    from repro.data.stream import StreamConfig, SyntheticStream

    assert len(jax.devices()) == 8
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("shard",))
    ecfg = EngineConfig(query_capacity=1<<12, cooc_capacity=1<<15,
                        session_capacity=1<<12, session_window=4,
                        decay_every=4, rank_every=0)
    scfg = se.ShardedConfig(base=ecfg, n_salts=2, route_capacity=1024)
    step = se.make_sharded_step(scfg, mesh)
    decay = se.make_sharded_decay(scfg, mesh)
    rank = se.make_sharded_rank(scfg, mesh)
    state = se.init_sharded_state(scfg, mesh)
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=200,
                                          queries_per_tick=192,
                                          tweets_per_tick=0), seed=5)
    eng = SearchAssistanceEngine(ecfg)
    for t in range(6):
        ev, tw = stream.gen_tick(t)
        s_hi, s_lo = split_fp(ev.sess_fp); q_hi, q_lo = split_fp(ev.q_fp)
        state = step(state, jnp.asarray(s_hi), jnp.asarray(s_lo),
                     jnp.asarray(q_hi), jnp.asarray(q_lo),
                     jnp.asarray(ev.src, jnp.int32), jnp.asarray(ev.valid))
        eng.step(ev, None)
        if t > 0 and t % ecfg.decay_every == 0:
            state = decay(state, jnp.int32(ecfg.decay_every))
        state = state._replace(tick=state.tick + 1)
    assert np.asarray(state.n_route_drop).sum() == 0, "routing overflow"
    merged = se.merge_sharded_suggestions(rank(state), ecfg.rank.top_k)
    eng.run_rank_cycle()
    ref = eng.suggestions
    assert set(merged) == set(ref), (len(merged), len(ref))
    n_score_ok = 0
    for f in merged:
        # every rank of every source: each pair lives in one shard, so the
        # merged lists are the unsharded engine's top-k
        ms = [s for _, s in merged[f]]
        rs = [s for _, s in ref[f]]
        assert len(ms) == len(rs), (f, merged[f], ref[f])
        np.testing.assert_allclose(ms, rs, rtol=5e-3, atol=1e-4)
        n_score_ok += 1
    print(f"SHARDED_OK {len(merged)} keys, {n_score_ok} score-matched")
""")


@pytest.mark.slow
def test_sharded_engine_matches_unsharded_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTEST_ALLOW_DEVICES"] = "1"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SHARDED_OK" in r.stdout


_REPLAY_SCRIPT = textwrap.dedent("""
    import tempfile
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.engine import EngineConfig
    from repro.core import sharded_engine as se
    from repro.core.decay import DecayConfig
    from repro.core.hashing import split_fp
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.distributed.fault_tolerance import CheckpointManager

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
                                          tweets_per_tick=0), seed=5)
    batches = []
    for t in range(8):
        ev, _ = stream.gen_tick(t)
        s_hi, s_lo = split_fp(ev.sess_fp); q_hi, q_lo = split_fp(ev.q_fp)
        batches.append(tuple(jnp.asarray(x) for x in
                       (s_hi, s_lo, q_hi, q_lo,
                        ev.src.astype(np.int32), ev.valid)))

    # uninterrupted live run (one full tick step per batch)
    live = se.init_sharded_state(scfg, mesh)
    for b in batches:
        live = tick_step(live, *b)

    # crash after tick 4: snapshot + parallel catch-up replay of the tail.
    # The snapshots are DELTA-CHAINED (full_interval=4): tick 2 writes the
    # full, tick 4 writes only the changed leading rows of each shard-
    # stacked leaf; restore composes the chain transparently.
    half = se.init_sharded_state(scfg, mesh)
    ckpt = CheckpointManager(tempfile.mkdtemp(), full_interval=4)
    for i, b in enumerate(batches[:4]):
        half = tick_step(half, *b)
        if i in (1, 3):
            se.save_sharded_snapshot(half, ckpt)
    assert ckpt.last_save_kind == "delta", ckpt.last_save_kind
    restored, log_tick = se.restore_sharded_snapshot(scfg, mesh, ckpt)
    assert log_tick == 4
    stacked = tuple(jnp.stack([b[i] for b in batches[4:]]) for i in range(6))
    caught_up = many(restored, *stacked)
    la, _ = jax.tree.flatten(live); lb, _ = jax.tree.flatten(caught_up)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"leaf {i}")
    print("SHARDED_REPLAY_OK tick", int(np.asarray(caught_up.tick)))
""")


@pytest.mark.slow
def test_sharded_replay_matches_live_subprocess():
    """Snapshot + fused parallel replay == uninterrupted sharded run
    (bit-for-bit), on 8 virtual devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTEST_ALLOW_DEVICES"] = "1"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _REPLAY_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "SHARDED_REPLAY_OK" in r.stdout


_RESHARD_SCRIPT = textwrap.dedent("""
    import tempfile
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.engine import EngineConfig
    from repro.core import sharded_engine as se
    from repro.core.decay import DecayConfig
    from repro.core.hashing import split_fp
    from repro.data.stream import StreamConfig, SyntheticStream
    from repro.distributed.elastic import live_reshard, sharded_pressure
    from repro.streaming.log import FirehoseLogWriter

    LAYOUT = "%(layout)s"
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
                                          tweets_per_tick=0), seed=5)
    batches, raw = [], []
    for t in range(12):
        ev, _ = stream.gen_tick(t)
        raw.append(ev)
        s_hi, s_lo = split_fp(ev.sess_fp); q_hi, q_lo = split_fp(ev.q_fp)
        batches.append(tuple(jnp.asarray(x) for x in
                       (s_hi, s_lo, q_hi, q_lo,
                        ev.src.astype(np.int32), ev.valid)))
    logd = tempfile.mkdtemp()
    w = FirehoseLogWriter(logd, ticks_per_segment=2)
    for t, ev in enumerate(raw[:10]):   # the log ends inside the split
        w.append(t, ev, None)           # window: ticks 10,11 are post-swap
    w.close()

    def top1(m):
        return {f: max(s for _, s in v) for f, v in m.items() if v}

    def run_with_live_split():
        # 2-shard live run to tick 8; the split window covers ticks 8-9:
        # the OLD layout keeps serving them while the snapshot is
        # re-partitioned to 4 shards and caught up from the shared log.
        st = se.init_sharded_state(scfg, mesh2)
        for b in batches[:8]:
            st = step2(st, *b)
        old = st
        for b in batches[8:10]:
            old = step2(old, *b)           # zero downtime: old serves 8,9
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
        for b in batches[10:]:             # swap: serve live on 4 shards
            new = step4(new, *b)
        return new

    a = run_with_live_split()
    b = run_with_live_split()
    # schedule parity: an identical split schedule is bit-reproducible
    la, ta = jax.tree.flatten(a); lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"leaf {i}")
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
@pytest.mark.parametrize("layout", ["hash", "region"])
def test_live_shard_split_merge_subprocess(layout):
    """Live 2->4 shard split under load (old layout answers the ticks that
    arrive during the window; the new layout catches up from the shared
    log), schedule-parity bit-exactness, no lost queries/mass across the
    handoff, and a 4->2 merge — on virtual devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTEST_ALLOW_DEVICES"] = "1"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c",
                        _RESHARD_SCRIPT % {"layout": layout}], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "RESHARD_OK" in r.stdout


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
