"""Parity + property tests for the fused single-sweep store update path.

The fused find-or-claim probe (``stores._find_or_claim``) must preserve the
*semantics* of the pre-fusion two-pass reference (``insert_accumulate_twopass``,
kept verbatim): identical key -> accumulated-value maps, and exact n_dropped
accounting — a batch's unique keys are either fully applied or dropped and
counted, never partially applied or silently lost. Claim *winners* may differ
between the two conflict-resolution strategies, so near-full assertions are on
conservation, not on bit-identical placement.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import stores
from repro.core.decay import DecayConfig, sweep_decay_prune
from repro.core.hashing import split_fp, join_fp
from proptest import property_test

MODES = (("weight", "add"), ("count", "add"), ("last_tick", "set"))


def _mk(capacity):
    return stores.make_table(capacity, {
        "weight": jnp.float32, "count": jnp.float32, "last_tick": jnp.int32})


def _upd(n, w, tick=0):
    return {"weight": jnp.asarray(w, jnp.float32),
            "count": jnp.ones(n, jnp.float32),
            "last_tick": jnp.full(n, tick, jnp.int32)}


def _ins(fn, t, fps, w, valid=None, tick=0):
    fps = np.asarray(fps, np.uint64)
    hi, lo = split_fp(fps)
    n = len(fps)
    valid = np.ones(n, bool) if valid is None else valid
    return fn(t, jnp.asarray(hi), jnp.asarray(lo), _upd(n, w, tick),
              jnp.asarray(valid), modes=MODES)


def _table_dict(t):
    exp = stores.export_live(t)
    fps = join_fp(exp["key_hi"], exp["key_lo"])
    return {int(f): (float(w), float(c), int(lt)) for f, w, c, lt in
            zip(fps, exp["weight"], exp["count"], exp["last_tick"])}


@property_test(n_cases=6)
def test_fused_matches_twopass_collision_heavy(rng):
    """Small table + clustered keys: fused path == two-pass reference as a
    key->value map, with zero drops at <= 50% load on both paths."""
    cap = 1 << 9
    t_new, t_old = _mk(cap), _mk(cap)
    for batch in range(4):
        # ~200 distinct keys, heavily repeated within each batch
        keys = rng.integers(1, 200, size=256).astype(np.uint64) * 2654435761
        w = rng.random(256).astype(np.float32)
        valid = rng.random(256) < 0.9
        t_new = _ins(stores.insert_accumulate, t_new, keys, w,
                     valid=valid, tick=batch)
        t_old = _ins(stores.insert_accumulate_twopass, t_old, keys, w,
                     valid=valid, tick=batch)
    assert int(t_new.n_dropped) == 0
    assert int(t_old.n_dropped) == 0
    d_new, d_old = _table_dict(t_new), _table_dict(t_old)
    assert set(d_new) == set(d_old)
    for k in d_new:
        np.testing.assert_allclose(d_new[k][0], d_old[k][0], rtol=1e-5)
        assert d_new[k][1] == d_old[k][1]
        assert d_new[k][2] == d_old[k][2]


@property_test(n_cases=4)
def test_near_full_exact_drop_accounting(rng):
    """Near-full table: every attempted unique key is either fully applied
    (all its batch updates) or dropped and counted — exact conservation."""
    cap = 1 << 8
    for fn in (stores.insert_accumulate, stores.insert_accumulate_twopass):
        t = _mk(cap)
        oracle = {}
        attempted_total = 0
        for batch in range(3):
            # ~1.5x capacity distinct keys across the run -> forced overflow
            keys = (rng.integers(1, 400, size=300).astype(np.uint64)
                    * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
            w = rng.random(300).astype(np.float32)
            t = _ins(fn, t, keys, w, tick=batch)
            for k, ww in zip(keys, w):
                e = oracle.setdefault(int(k), [0.0, 0])
                e[0] += float(ww)
                e[1] += 1
            attempted_total = len(oracle)
        dropped = int(t.n_dropped)
        live = int(t.live_count())
        assert dropped > 0, "test must actually exercise overflow"
        d = _table_dict(t)
        assert len(d) == live
        # surviving keys carry their COMPLETE accumulated history: a key
        # placed in batch b accumulates every later batch too, so any
        # mismatch would prove partial application.
        for k, (w_got, c_got, _) in d.items():
            # key must exist in the oracle and be fully accumulated from the
            # first batch that placed it; count is an integer number of hits
            assert k in oracle
            assert c_got == int(c_got)
            assert c_got <= oracle[k][1]
        # conservation: every unique key was either placed once or counted
        # dropped each batch it failed; placed+never-again-dropped keys
        # cannot exceed the attempted universe.
        assert live <= attempted_total
        assert live <= cap


def test_fused_drops_zero_at_half_load_exact_values():
    """<= 50% load: n_dropped stays 0 and values match a dict oracle."""
    cap = 1 << 10
    t = _mk(cap)
    rng = np.random.default_rng(3)
    oracle = {}
    for batch in range(4):
        keys = (rng.integers(1, cap // 2, size=512).astype(np.uint64)
                * np.uint64(0x2545F4914F6CDD1D)) | np.uint64(1)
        w = rng.random(512).astype(np.float32)
        t = _ins(stores.insert_accumulate, t, keys, w, tick=batch)
        for k, ww in zip(keys, w):
            e = oracle.setdefault(int(k), [0.0, 0])
            e[0] += float(ww)
            e[1] += 1
    assert int(t.n_dropped) == 0
    d = _table_dict(t)
    assert set(d) == set(oracle)
    for k, (w_got, c_got, _) in d.items():
        np.testing.assert_allclose(w_got, oracle[k][0], rtol=1e-5)
        assert c_got == oracle[k][1]


@property_test(n_cases=4)
def test_sessions_crowded_table_matches_deque_model(rng):
    """Session-store probe under crowding (~50% load incl. collisions) still
    emits exactly the sliding-window pairs of a python deque model."""
    from collections import deque
    W = int(rng.integers(2, 5))
    cap = 1 << 7
    st = stores.make_session_table(cap, W)
    model = {}
    expected, got = [], []
    for batch in range(3):
        B = 96
        sess = rng.integers(1, cap // 2, size=B).astype(np.uint64) * 7919
        q = rng.integers(1, 64, size=B).astype(np.uint64) * 104729
        src = rng.integers(0, 3, size=B).astype(np.int32)
        valid = rng.random(B) < 0.95
        for s, qq, sc, v in zip(sess, q, src, valid):
            if not v:
                continue
            d = model.setdefault(int(s), deque(maxlen=W))
            for (p, psc) in d:
                if p != int(qq):
                    expected.append((p, int(qq)))
            d.append((int(qq), int(sc)))
        s_hi, s_lo = split_fp(sess)
        q_hi, q_lo = split_fp(q)
        st, pairs = stores.update_sessions(
            st, jnp.asarray(s_hi), jnp.asarray(s_lo), jnp.asarray(q_hi),
            jnp.asarray(q_lo), jnp.asarray(src), jnp.int32(batch),
            jnp.asarray(valid))
        pv = np.asarray(pairs.valid)
        sfp = join_fp(np.asarray(pairs.src_hi), np.asarray(pairs.src_lo))[pv]
        dfp = join_fp(np.asarray(pairs.dst_hi), np.asarray(pairs.dst_lo))[pv]
        got.extend(zip(sfp.tolist(), dfp.tolist()))
    assert int(st.n_dropped) == 0
    assert sorted(got) == sorted(expected)


def test_fused_reuses_pruned_slots():
    """Prune-safety: the fused sweep must find keys past pruned (empty)
    slots on their probe sequence, and reuse those slots without dupes."""
    cap = 1 << 9
    t = _mk(cap)
    keys = (np.arange(1, 220, dtype=np.uint64) * 0x9E3779B97F4A7C15) | 1
    t = _ins(stores.insert_accumulate, t, keys, np.ones(len(keys)))
    # decay half the weight range below the prune threshold
    rng = np.random.default_rng(0)
    w = rng.random(len(keys)).astype(np.float32)
    t = _ins(stores.insert_accumulate, t, keys, w)
    cfg = DecayConfig(half_life_ticks=4.0, prune_threshold=1.2)
    t, live, _ = sweep_decay_prune(t, jnp.int32(2), cfg=cfg)
    assert 0 < int(live) < len(keys)
    # reinsert everything twice; no duplicates, exact counts
    t = _ins(stores.insert_accumulate, t, keys, np.ones(len(keys)))
    t = _ins(stores.insert_accumulate, t, keys, np.ones(len(keys)))
    assert int(t.live_count()) == len(keys)
    hi, lo = split_fp(keys)
    vals, found, _ = stores.lookup(t, jnp.asarray(hi), jnp.asarray(lo))
    assert np.asarray(found).all()


# ---------------------------------------------------------------------------
# Multi-lane decay sweep: fused kernel == jnp reference in decay.py
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C", [1024, 8192])
def test_multilane_decay_sweep_matches_jnp_reference(C):
    """kernel path (all lanes in one pass) == decay.py jnp reference."""
    rng = np.random.default_rng(C)
    lanes = {"weight": jnp.float32, "count": jnp.float32,
             "last_tick": jnp.int32, "src_hi": jnp.uint32,
             "src_lo": jnp.uint32}
    t = stores.make_table(C, lanes)
    n = C // 2
    keys = (rng.integers(1, 1 << 30, size=n).astype(np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
    hi, lo = split_fp(keys)
    upds = {"weight": jnp.asarray(rng.random(n) * 2, jnp.float32),
            "count": jnp.ones(n, jnp.float32),
            "last_tick": jnp.full(n, 3, jnp.int32),
            "src_hi": jnp.asarray(rng.integers(1, 2**32, n), jnp.uint32),
            "src_lo": jnp.asarray(rng.integers(1, 2**32, n), jnp.uint32)}
    modes = (("weight", "add"), ("count", "add"), ("last_tick", "set"),
             ("src_hi", "set"), ("src_lo", "set"))
    t = stores.insert_accumulate(t, jnp.asarray(hi), jnp.asarray(lo), upds,
                                 jnp.ones(n, bool), modes=modes)
    cfg = DecayConfig(half_life_ticks=6.0, prune_threshold=0.4)
    t_ref, live_ref, tot_ref = sweep_decay_prune(
        t, jnp.int32(6), cfg=cfg, use_kernel=False)
    t_ker, live_ker, tot_ker = sweep_decay_prune(
        t, jnp.int32(6), cfg=cfg, use_kernel=True)
    assert int(live_ref) == int(live_ker)
    np.testing.assert_allclose(float(tot_ref), float(tot_ker), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(t_ref.key_hi),
                                  np.asarray(t_ker.key_hi))
    np.testing.assert_array_equal(np.asarray(t_ref.key_lo),
                                  np.asarray(t_ker.key_lo))
    for name in lanes:
        a, b = np.asarray(t_ref.lanes[name]), np.asarray(t_ker.lanes[name])
        if a.dtype == np.float32:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(a, b)


def test_decay_prune_multi_kernel_vs_ref_oracle():
    """Direct kernel-vs-oracle check incl. a second decayed weight lane."""
    from repro.kernels.decay_prune import decay_prune_multi
    from repro.kernels.ref import decay_prune_multi_ref
    C = 2048
    rng = np.random.default_rng(1)
    kh = rng.integers(0, 2**32, C, dtype=np.uint32)
    kl = rng.integers(0, 2**32, C, dtype=np.uint32)
    dead = rng.random(C) < 0.3
    kh[dead] = 0
    kl[dead] = 0
    w0 = jnp.asarray((rng.random(C) * 3).astype(np.float32))
    w1 = jnp.asarray((rng.random(C) * 5).astype(np.float32))
    cnt = jnp.asarray(np.floor(rng.random(C) * 9).astype(np.float32))
    tick = jnp.asarray(rng.integers(0, 100, C).astype(np.int32))
    args = (jnp.asarray(kh), jnp.asarray(kl), (w0, w1), (cnt, tick),
            jnp.float32(0.5), jnp.float32(0.3))
    got = decay_prune_multi(*args, interpret=True)
    exp = decay_prune_multi_ref(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(exp[1]))
    for g, e in zip(got[2], exp[2]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6)
    for g, e in zip(got[3], exp[3]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
    assert int(got[4]) == int(exp[4])
    np.testing.assert_allclose(float(got[5]), float(exp[5]), rtol=1e-4)


# ---------------------------------------------------------------------------
# Ranking compaction parity
# ---------------------------------------------------------------------------

def test_ranking_compaction_parity_and_overflow_counting():
    """Lexsort-path compaction machinery (the pre-segmented reference)."""
    import dataclasses
    from repro.core import ranking
    from repro.core.engine import EngineConfig, SearchAssistanceEngine
    from repro.data.stream import StreamConfig, SyntheticStream

    cfg = EngineConfig(query_capacity=1 << 12, cooc_capacity=1 << 14,
                       session_capacity=1 << 11, session_window=4,
                       rank_every=0, decay_every=0)
    eng = SearchAssistanceEngine(cfg)
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=150,
                                          queries_per_tick=256,
                                          tweets_per_tick=0), seed=2)
    for t in range(6):
        ev, _ = stream.gen_tick(t)
        eng.step(ev, None)

    full = ranking.ranking_cycle_lexsort(
        eng.state.cooc, eng.state.qstore,
        dataclasses.replace(cfg.rank, compact_frac=1.0))
    comp = ranking.ranking_cycle_lexsort(
        eng.state.cooc, eng.state.qstore,
        dataclasses.replace(cfg.rank, compact_frac=0.5))
    assert int(full.n_overflow) == 0
    assert int(comp.n_overflow) == 0
    s_full = ranking.suggestions_to_host(full)
    s_comp = ranking.suggestions_to_host(comp)
    assert set(s_full) == set(s_comp)
    assert int(full.n_rows) == int(comp.n_rows)
    for f in s_full:
        a = sorted(s_full[f], key=lambda t: (-t[1], t[0]))
        b = sorted(s_comp[f], key=lambda t: (-t[1], t[0]))
        # rtol 1e-4, not 1e-6: compaction reorders the surviving pairs, so
        # the per-source f32 normalization sums accumulate in a different
        # order than the uncompacted pass — occasionally past 1e-6, which
        # made this flaky. A real parity break (missing pair, wrong
        # normalizer) shifts scores by >1e-2 here.
        np.testing.assert_allclose([s for _, s in a], [s for _, s in b],
                                   rtol=1e-4)
        assert {d for d, _ in a} == {d for d, _ in b}

    # a pathologically small compaction buffer must COUNT what it cuts, and
    # the cut must remove the globally LOWEST-scoring pairs — the best
    # suggestion always survives compaction.
    tiny = ranking.ranking_cycle_lexsort(
        eng.state.cooc, eng.state.qstore,
        dataclasses.replace(cfg.rank, compact_frac=1e-4))
    assert int(tiny.n_overflow) > 0
    s_tiny = ranking.suggestions_to_host(tiny)
    best_full = max(s for row in s_full.values() for _, s in row)
    best_tiny = max(s for row in s_tiny.values() for _, s in row)
    np.testing.assert_allclose(best_tiny, best_full, rtol=1e-4)


# ---------------------------------------------------------------------------
# Claim-sort key packing: winners deterministic-by-arrival
# ---------------------------------------------------------------------------

@property_test(n_cases=4)
def test_claim_winners_invariant_under_batch_permutation(rng):
    """Permuting a batch must leave the resulting table bit-identical: the
    packed (slot, batch idx) claim key makes winners a function of the
    deduped (sorted) key set, not of the input order or sort stability."""
    cap = 1 << 8
    n = 180
    # clustered keys -> heavy probe collisions -> many contended claims
    keys = (rng.integers(1, 90, size=n).astype(np.uint64)
            * np.uint64(0x9E3779B97F4A7C15)) | np.uint64(1)
    w = rng.random(n).astype(np.float32)
    # permutation-invariant updates: ADD lanes + a constant SET tick
    tables = []
    for perm in (np.arange(n), rng.permutation(n), rng.permutation(n)):
        t = _mk(cap)
        t = _ins(stores.insert_accumulate, t, keys[perm], w[perm], tick=7)
        tables.append(t)
    for t in tables[1:]:
        np.testing.assert_array_equal(np.asarray(tables[0].key_hi),
                                      np.asarray(t.key_hi))
        np.testing.assert_array_equal(np.asarray(tables[0].key_lo),
                                      np.asarray(t.key_lo))
        for name in tables[0].lanes:
            np.testing.assert_allclose(np.asarray(tables[0].lanes[name]),
                                       np.asarray(t.lanes[name]), rtol=1e-6)


def test_claim_winners_lexsort_fallback_matches_packed():
    """When log2(C) + log2(B) > 31 the packed key cannot fit u32; the
    lexsort fallback must pick the same winners (lowest batch index)."""
    from repro.core.stores import _claim_winners
    rng = np.random.default_rng(0)
    B = 1 << 12
    slots = jnp.asarray(rng.integers(0, 1 << 10, size=B), jnp.uint32)
    contend = jnp.asarray(rng.random(B) < 0.7)
    # C small enough to pack vs C huge enough to force the fallback
    won_packed = _claim_winners(slots, contend, B, 1 << 10)
    won_fallback = _claim_winners(slots, contend, B, 1 << 24)
    np.testing.assert_array_equal(np.asarray(won_packed),
                                  np.asarray(won_fallback))
    # exactly one winner per contended slot, and it is the first arrival
    sl = np.asarray(slots)
    cn = np.asarray(contend)
    wn = np.asarray(won_packed)
    for s in np.unique(sl[cn]):
        contenders = np.nonzero(cn & (sl == s))[0]
        winners = np.nonzero(wn & (sl == s))[0]
        assert len(winners) == 1 and winners[0] == contenders.min()


# ---------------------------------------------------------------------------
# Two-phase lookup: full-width rounds, then a tail arena
# ---------------------------------------------------------------------------

def _probe_depth(t, hi, lo, slot):
    """The probe round at which each found row's key sat."""
    h0 = np.asarray(stores.probe_hash(jnp.asarray(hi), jnp.asarray(lo)),
                    np.int64)
    r = np.arange(16)
    cand = (h0[:, None] + r * (r + 1) // 2) & (t.capacity - 1)
    return np.argmax(cand == np.asarray(slot)[:, None], axis=1)


def _lookup_case(case, rng):
    """(table, key fps, lookup kwargs, (full rounds, tail engaged))."""
    B = 1 << 17
    near_full = case == "near_full"
    cap, n = (1 << 11, 1950) if near_full else (1 << 17, 3000)
    fps = (rng.choice(1 << 62, size=n, replace=False) + 1).astype(np.uint64)
    absent = (rng.choice(1 << 62, size=4096 if case == "many_absent" else 64,
                         replace=False) + (1 << 62)).astype(np.uint64)
    t = _ins(stores.insert_accumulate, _mk(cap), fps, rng.random(n))
    t = t._replace(lanes=dict(t.lanes, last_tick=jnp.asarray(
        rng.integers(0, 50, cap), jnp.int32)))
    if near_full:
        # every key once, then rows found in round 0 up to a full phase 1
        hi, lo = split_fp(fps)
        _, found, slot = stores.lookup(t, jnp.asarray(hi), jnp.asarray(lo))
        depth = _probe_depth(t, hi, lo, slot)
        shallow = fps[np.asarray(found) & (depth == 0)]
        present = np.concatenate([fps, rng.choice(shallow, size=8192)])
    else:
        present = rng.choice(fps, size=30000)
    keys = np.zeros(B, np.uint64)
    live = np.concatenate([present, absent])
    keys[rng.choice(B, size=len(live), replace=False)] = live
    kw = (dict(decay_cfg=DecayConfig(policy="lazy"), now=jnp.int32(60))
          if case == "low_load_decayed" else {})
    return t, keys, kw, ((16, False) if case == "many_absent" else (1, True))


@pytest.mark.parametrize("case", ["low_load", "many_absent", "near_full",
                                  "low_load_decayed"])
def test_two_phase_lookup_matches_full_width_loop(case):
    """A batch of 2^17 rows (tail arena 2^11) returns what the same keys
    return in chunks of 8,192 rows, which run the full-width loop alone:
    bit-identical lanes, found mask and slot. Low load hands the rest to
    the tail after one round; more absent keys than the arena holds keep
    all 16 rounds at full width; a near-full table leaves keys 10 and more
    rounds deep for the tail."""
    t, keys, kw, (want_rounds, want_tail) = _lookup_case(
        case, np.random.default_rng(14))
    hi, lo = split_fp(keys)
    vals, found, slot, stats = stores.lookup(
        t, jnp.asarray(hi), jnp.asarray(lo), with_stats=True, **kw)
    parts = [stores.lookup(t, jnp.asarray(hi[i:i + 8192]),
                           jnp.asarray(lo[i:i + 8192]), **kw)
             for i in range(0, len(keys), 8192)]
    full_rounds, tail_rows = np.asarray(stats)
    assert full_rounds == want_rounds and (tail_rows > 0) == want_tail
    np.testing.assert_array_equal(
        np.asarray(slot), np.concatenate([np.asarray(p[2]) for p in parts]))
    np.testing.assert_array_equal(
        np.asarray(found), np.concatenate([np.asarray(p[1]) for p in parts]))
    for name, v in vals.items():
        np.testing.assert_array_equal(
            np.asarray(v), np.concatenate([np.asarray(p[0][name])
                                           for p in parts]))
    f = np.asarray(found)
    assert 0 < f.sum() < np.count_nonzero(keys)
    if case == "near_full":
        assert _probe_depth(t, hi[f], lo[f], np.asarray(slot)[f]).max() >= 10
