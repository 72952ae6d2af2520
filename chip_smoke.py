#!/usr/bin/env python3
"""Bring-up check: the serving stack on a TPU at deployment-scale state.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the sharded engine only

One chip drives the main path once, through the classes a deployment runs:
``AssistanceService`` (real-time + background engines + interpolation),
the durable ``FirehoseLogWriter``, ``CheckpointManager`` state snapshots,
and ``SuggestFrontend`` replicas behind a ``ServerSet``. Both engines use
the hash cooc layout at one v5e chip's share of HBM (sizes in ``Sizes``).
Phases, one result line each:

    [device]  the first device is a TPU, else exit non-zero, print nothing
    [tune]    autotune.tune on the chip: every candidate's time; a kernel
              candidate that raises fails the run
    [correct] SyntheticStream ticks through the engine at full capacities
              vs core/reference.ReferenceEngine (top-k sets, scores, drops)
    [load]    SyntheticStream traffic (Zipf queries, topical sessions,
              tweets) replayed through ingest_many until the rt cooc store
              is >= 1/4 full
    [serve]   live ticks with an rt and a bg rank cycle, a log, a snapshot,
              ServerSet requests; rank-cycle / export host wall times
    [parity]  at that fill, the rank cycle and the decay sweep under the
              tuned plan vs the plan with every kernel/jnp choice swapped
    [serve]   peak device bytes

``--chips 4`` runs only the sharded engine over a 4-chip mesh (cooc 2^25
per shard) against ReferenceEngine. The last stdout line is the JSON
result; any failed phase exits non-zero. Everything runs in this one
process: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")


class PhaseError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseError(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Deployment sizes. Assumed, not published: the stores are sized to
    one v5e chip's HBM holding rt + bg engines beside the rank cycle's
    temporaries (cooc 2^26 needs 12.4 GiB of rank temporaries alone)."""
    query_capacity: int = 1 << 22
    cooc_capacity: int = 1 << 25
    session_capacity: int = 1 << 21
    vocab: int = 1 << 19            # distinct query strings in the hose
    users: int = 1 << 16            # users, one session each per epoch
    batch: int = 1 << 15            # query events per 10 s tick
    tweets: int = 512               # firehose tweets per tick
    grams: int = 16                 # n-gram slots per tweet
    # replayed through ingest_many (~29 min of hose); the live ticks then
    # reach tick 180, where both rt (every 30) and bg (every 90) rank
    fill_ticks: int = 176
    chunk_ticks: int = 8            # ticks per ingest_many dispatch
    correct_ticks: int = 20
    correct_batch: int = 4096
    n_requests: int = 10


def engine_config(sz: Sizes, plan=None):
    from repro.core.engine import EngineConfig
    # cadences: EngineConfig defaults (decay every minute, rank every 5
    # minutes of 10 s ticks, paper §2.3); hash layout
    return EngineConfig(query_capacity=sz.query_capacity,
                        cooc_capacity=sz.cooc_capacity,
                        session_capacity=sz.session_capacity, plan=plan)


def state_bytes(state) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(state))


def hose(sz: Sizes, seed: int):
    """The deployment hose: ``SyntheticStream`` at a 2^19-query vocabulary
    (Zipf 1.07 popularity, topic-sticky sessions, topical tweets)."""
    from repro.data.stream import StreamConfig, SyntheticStream
    return SyntheticStream(StreamConfig(
        vocab_size=sz.vocab, n_users=sz.users, queries_per_tick=sz.batch,
        tweets_per_tick=sz.tweets, tweet_grams=sz.grams), seed=seed)


def backlog(stream, t0: int, n_ticks: int):
    """Ticks ``[t0, t0 + n_ticks)`` of ``stream`` as one device TickStack,
    stacked as a replayed log chunk."""
    import numpy as np
    from repro.streaming import LogChunk, chunk_to_stack
    ticks = [stream.gen_tick(t) for t in range(t0, t0 + n_ticks)]
    col = lambda f: np.stack([f(ev, tw) for ev, tw in ticks])
    return chunk_to_stack(LogChunk(
        ticks=np.arange(t0, t0 + n_ticks), sess_fp=col(lambda e, w: e.sess_fp),
        q_fp=col(lambda e, w: e.q_fp), src=col(lambda e, w: e.src),
        q_valid=col(lambda e, w: e.valid), grams=col(lambda e, w: w.grams),
        t_valid=col(lambda e, w: w.valid)))


# ---------------------------------------------------------------------------
# comparison with the reference engine
# ---------------------------------------------------------------------------

# the tests' score tolerance (tests/test_engine.py), applied here to every
# rank of every source, and to what counts as a tie at the k-th rank
RTOL, ATOL = 5e-3, 1e-4


def topk_agrees(got, want) -> bool:
    """Two top-k lists of one source agree: same length, scores within the
    tests' tolerance rank by rank, and the same destination set except for
    members tied (within that tolerance) with the k-th reference score."""
    import numpy as np
    if len(got) != len(want):
        return False
    gs = np.array([s for _, s in got])
    ws = np.array([s for _, s in want])
    if not np.allclose(gs, ws, rtol=RTOL, atol=ATOL):
        return False
    gd, wd = {d for d, _ in got}, {d for d, _ in want}
    cut = want[-1][1]
    scores = {**dict(want), **dict(got)}
    return all(abs(scores[d] - cut) <= ATOL + RTOL * abs(cut)
               for d in gd ^ wd)


def compare_tables(got, want) -> tuple:
    """(n_sources, n_disagreeing, max score error at any rank) for two
    suggestion dicts with the same source set (checked by the caller)."""
    bad = sum(not topk_agrees(got[f], want[f]) for f in want)
    err = max((abs(a[1] - b[1]) for f in want
               for a, b in zip(got[f], want[f])), default=0.0)
    return len(want), bad, err


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no accelerator: first device is {d0.platform!r}")
    check(len(devs) >= chips, f"need {chips} chips, found {len(devs)}")
    print(f"[device] platform={d0.platform} kind={d0.device_kind!r} "
          f"count={len(devs)}", flush=True)
    return d0, len(devs)


def phase_tune(sz: Sizes, cache_dir: str):
    from repro.launch import autotune
    cfg = engine_config(sz)
    t0 = time.perf_counter()
    plan = autotune.tune(cfg, cache=os.path.join(cache_dir, "autotune"))
    dt = time.perf_counter() - t0
    rec = json.loads(autotune.cache_path(
        cfg, os.path.join(cache_dir, "autotune")).read_text())
    timings = rec["timings_us"]
    for name in sorted(timings):
        t = timings[name]
        print(f"[tune] {name}: "
              f"{'RAISED' if t is None else f'{t:.1f} us'}", flush=True)
    raised = [k for k, t in timings.items() if t is None]
    check(not raised, f"kernel candidates raised: {raised}")
    print(f"[tune] plan={plan.variants()} shape_class={plan.shape_class} "
          f"({dt:.1f}s incl. compiles)", flush=True)
    return plan


def phase_correct(sz: Sizes, plan, seed: int):
    from repro.core.engine import SearchAssistanceEngine
    from repro.core.reference import ReferenceEngine
    from repro.data.stream import StreamConfig, SyntheticStream
    cfg = engine_config(sz, plan)
    stream = SyntheticStream(StreamConfig(queries_per_tick=sz.correct_batch),
                             seed=seed)
    eng = SearchAssistanceEngine(cfg)
    ref = ReferenceEngine(cfg)
    t0 = time.perf_counter()
    for t in range(sz.correct_ticks):
        ev, tw = stream.gen_tick(t)
        eng.step(ev, tw)
        ref.step(ev, tw)
    eng.run_rank_cycle()
    ref.rank_cycle()
    st = eng.state
    drops = {k: int(getattr(st, k).n_dropped)
             for k in ("qstore", "cooc", "sessions")}
    got, want = eng.suggestions, ref.suggestions
    check(set(got) == set(want),
          f"source sets differ: engine {len(got)} vs reference {len(want)}")
    n, bad, err = compare_tables(got, want)
    print(f"[correct] {sz.correct_ticks} ticks x {sz.correct_batch} events "
          f"+ tweets at cooc={cfg.cooc_capacity}: {n} sources, "
          f"{bad} top-k disagreements, max score error {err:.2e}, "
          f"drops={drops} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    check(n > 0 and bad == 0, f"{bad} of {n} top-k lists disagree")
    check(not any(drops.values()), f"dropped entries: {drops}")


def phase_load_serve(sz: Sizes, plan, seed: int, work: str, d0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ranking
    from repro.core.background import AssistanceService, background_config
    from repro.distributed.fault_tolerance import CheckpointManager
    from repro.serving.serve import SuggestFrontend, ServerSet, \
        pack_suggestions
    from repro.streaming import FirehoseLogWriter

    cfg = engine_config(sz, plan)
    svc = AssistanceService(cfg, bg_cfg=background_config(
        cfg, rank_every_mult=3))
    resident = state_bytes(svc.rt.state) + state_bytes(svc.bg.state)
    print(f"[load] resident state rt+bg={resident} B "
          f"(rt={state_bytes(svc.rt.state)} B)", flush=True)
    s0 = time.perf_counter()
    stream = hose(sz, seed)
    print(f"[load] SyntheticStream vocab={len(stream.vocab)} "
          f"users={sz.users} ({time.perf_counter() - s0:.1f}s to build)",
          flush=True)

    # ---- load: replay a backlog through ingest_many (the catch-up path) ----
    t0 = time.perf_counter()
    for c in range(0, sz.fill_ticks, sz.chunk_ticks):
        stack = backlog(stream, c, sz.chunk_ticks)
        svc.rt.step_many(stack)
        svc.bg.step_many(stack)        # same backlog, bg cadences
    jax.block_until_ready((svc.rt.state, svc.bg.state))
    dt = time.perf_counter() - t0
    live = int(jnp.sum(svc.rt.state.cooc.live_mask))
    frac = live / sz.cooc_capacity
    n_ev = sz.fill_ticks * sz.batch
    print(f"[load] ingest_many {sz.fill_ticks} ticks ({n_ev} events + "
          f"{sz.fill_ticks * sz.tweets} tweets): cooc live={live} "
          f"({frac:.3f} of slots), drops={int(svc.rt.state.cooc.n_dropped)}"
          f"; bg cooc live={int(jnp.sum(svc.bg.state.cooc.live_mask))} "
          f"({dt:.1f}s for rt+bg incl. compiles and host generation)",
          flush=True)
    check(frac >= 0.25, f"cooc store only {frac:.3f} full")

    # ---- serve: live ticks through the whole stack ----
    log_dir = os.path.join(work, "log")
    rt_dir, bg_dir = os.path.join(work, "rt"), os.path.join(work, "bg")
    writer = FirehoseLogWriter(log_dir, ticks_per_segment=8)
    rt_tables, bg_tables = CheckpointManager(rt_dir), CheckpointManager(bg_dir)
    tick0 = int(svc.rt.state.tick)
    ranked = {"rt": 0, "bg": 0}
    t_live = []
    t = tick0
    while not (ranked["rt"] and ranked["bg"]):
        check(t < tick0 + 4 * cfg.rank_every * 3, "no rank cycle came due")
        ev, tw = stream.gen_tick(t)
        s0 = time.perf_counter()
        res = svc.step(ev, tw, log_append=writer.append)
        jax.block_until_ready(svc.rt.state)
        t_live.append(time.perf_counter() - s0)
        if res is not None:
            for name, eng, tables in (("rt", svc.rt, rt_tables),
                                      ("bg", svc.bg, bg_tables)):
                if res.get(name) is not None:
                    ranked[name] += 1
                    tables.save(t, pack_suggestions(eng.suggestions),
                                meta={"tick": t})
                    print(f"[serve] t={t} {name} rank cycle: "
                          f"{res[name]}", flush=True)
        t += 1
    writer.close()
    print(f"[serve] {len(t_live)} live ticks of {sz.batch} events + "
          f"{sz.tweets} tweets: step host wall s min/median/max = "
          f"{min(t_live):.3f}/{float(np.median(t_live)):.3f}/"
          f"{max(t_live):.3f} (incl. first-call compiles and rank ticks)",
          flush=True)

    # rank cycle and table export, timed apart (programs already compiled)
    st = svc.rt.state
    s0 = time.perf_counter()
    table = ranking.ranking_cycle(st.cooc, st.qstore, cfg.rank)
    jax.block_until_ready(table)
    s1 = time.perf_counter()
    sugg = ranking.suggestions_to_host(table)
    s2 = time.perf_counter()
    packed = pack_suggestions(sugg)
    s3 = time.perf_counter()
    print(f"[serve] rt rank cycle {s1 - s0:.3f}s host wall "
          f"({int(table.n_rows)} rows, overflow {int(table.n_overflow)}); "
          f"suggestions_to_host {s2 - s1:.3f}s ({len(sugg)} sources); "
          f"pack_suggestions {s3 - s2:.3f}s", flush=True)
    del packed
    phase_parity(cfg, plan, st, table)
    del table

    # snapshot both engines (checkpoint + log offset)
    s0 = time.perf_counter()
    ck_rt = CheckpointManager(os.path.join(work, "state", "rt"))
    ck_bg = CheckpointManager(os.path.join(work, "state", "bg"))
    svc.save_snapshot(ck_rt, ck_bg)
    print(f"[serve] snapshot rt={ck_rt.last_save_bytes} B "
          f"bg={ck_bg.last_save_bytes} B ({time.perf_counter() - s0:.1f}s)",
          flush=True)

    # the read path: frontends poll the persisted tables, ServerSet routes
    s0 = time.perf_counter()
    frontends = [SuggestFrontend(rt_dir, bg_dir, stream.tok, log_dir=log_dir)
                 for _ in range(2)]
    for f in frontends:
        f.poll()
    s1 = time.perf_counter()
    # the most popular queries with suggestions (vocab is in Zipf order)
    queries = [q for q, f in zip(stream.vocab, stream.fps)
               if int(f) in svc.suggestions][:sz.n_requests]
    check(len(queries) == sz.n_requests,
          f"only {len(queries)} queries have suggestions")
    servers = ServerSet(frontends)
    answers = {q: servers.request(q, k=5) for q in queries}
    empty = [q for q, a in answers.items() if not a]
    q0 = queries[0]
    print(f"[serve] {len(answers)} ServerSet requests, {len(empty)} empty; "
          f"frontend poll {s1 - s0:.1f}s for 2 replicas; "
          f"related({q0!r}) = {answers[q0][:3]}", flush=True)
    check(not empty, f"empty answers for {empty}")
    stats = d0.memory_stats() or {}
    print(f"[serve] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)


def phase_parity(cfg, plan, st, table):
    """At deployment fill, where the reference engine is too slow to run:
    the rank cycle under the plan with every kernel/jnp choice swapped
    must give ``table`` (the tuned plan's) within the tests' tolerance,
    and the decay sweep's kernel and jnp paths the same store, bit for
    bit (both multiply by one scalar factor and compare to one
    threshold)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ranking
    from repro.core.decay import sweep_decay_prune
    from repro.core.hashing import join_fp
    from repro.core.plan import JNP, KERNEL

    ops = ("score_gate", "bucket_topk", "decay_prune")
    swap = {KERNEL: JNP, JNP: KERNEL}
    other = dataclasses.replace(
        plan, **{op: swap[getattr(plan, op)] for op in ops})
    s0 = time.perf_counter()
    t2 = ranking.ranking_cycle(st.cooc, st.qstore,
                               dataclasses.replace(cfg.rank, plan=other))
    jax.block_until_ready(t2)
    dt = time.perf_counter() - s0
    a = {k: np.asarray(v) for k, v in table._asdict().items()}
    b = {k: np.asarray(v) for k, v in t2._asdict().items()}
    del t2
    for k in ("n_rows", "n_overflow", "src_hi", "src_lo"):
        check(np.array_equal(a[k], b[k]), f"swapped plan: {k} differs")
    same = ((a["dst_hi"] == b["dst_hi"]) & (a["dst_lo"] == b["dst_lo"])
            & (a["score"] == b["score"])).all(1)
    rows = np.nonzero(~same)[0]

    def topk(t):
        fp = join_fp(t["dst_hi"][rows], t["dst_lo"][rows]).tolist()
        sc = t["score"][rows].tolist()
        return [[(f, x) for f, x in zip(fr, sr) if x > 0.0]
                for fr, sr in zip(fp, sc)]

    bad = sum(not topk_agrees(g, w) for g, w in zip(topk(b), topk(a)))
    err = float(np.max(np.abs(a["score"] - b["score"])))
    swapped = ", ".join(f"{op}={getattr(other, op)}" for op in ops[:2])
    print(f"[parity] rank cycle, swapped plan ({swapped}) vs tuned: "
          f"{int(a['n_rows'])} sources, {len(rows)} not "
          f"bit-identical, {bad} top-k disagreements, max score error "
          f"{err:.2e} ({dt:.1f}s incl. compile)", flush=True)
    check(bad == 0, f"{bad} top-k lists differ between plans")

    d_ticks = jnp.int32(cfg.decay_every)
    kern, _, _ = sweep_decay_prune(st.cooc, d_ticks, cfg=cfg.decay,
                                   use_kernel=True)
    ref, _, _ = sweep_decay_prune(st.cooc, d_ticks, cfg=cfg.decay,
                                  use_kernel=False)
    diff = [str(p) for p, x, y in zip(
        jax.tree_util.tree_leaves_with_path(kern),
        jax.tree.leaves(kern), jax.tree.leaves(ref))
        if not bool(jnp.array_equal(x, y))]
    print(f"[parity] decay sweep, kernel vs jnp on the filled cooc store "
          f"(live {int(jnp.sum(st.cooc.live_mask))} -> "
          f"{int(jnp.sum(ref.live_mask))}): "
          f"{'bit-identical' if not diff else f'differs in {diff}'}",
          flush=True)
    check(not diff, f"decay sweep kernel and jnp differ in {diff}")


def phase_sharded(sz: Sizes, n_chips: int, seed: int):
    """The sharded engine over a ``n_chips`` mesh, total cooc ``n_chips *
    sz.cooc_capacity`` (one engine's store per shard), vs ReferenceEngine."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import sharded_engine as se
    from repro.core.hashing import split_fp
    from repro.core.reference import ReferenceEngine
    from repro.data.stream import StreamConfig, SyntheticStream

    mesh = Mesh(np.array(jax.devices()[:n_chips]), ("shard",))
    ecfg = dataclasses.replace(engine_config(sz),
                               cooc_capacity=n_chips * sz.cooc_capacity,
                               session_capacity=n_chips * sz.session_capacity)
    scfg = se.ShardedConfig(base=ecfg)
    step = se.make_sharded_step(scfg, mesh)
    decay = se.make_sharded_decay(scfg, mesh)
    rank = se.make_sharded_rank(scfg, mesh)
    state = se.init_sharded_state(scfg, mesh)
    per_dev = state_bytes(state) // n_chips
    stream = SyntheticStream(StreamConfig(queries_per_tick=sz.correct_batch,
                                          tweets_per_tick=0), seed=seed)
    ref = ReferenceEngine(ecfg)
    t0 = time.perf_counter()
    for t in range(sz.correct_ticks):
        ev, _ = stream.gen_tick(t)
        s_hi, s_lo = split_fp(ev.sess_fp)
        q_hi, q_lo = split_fp(ev.q_fp)
        state = step(state, jnp.asarray(s_hi), jnp.asarray(s_lo),
                     jnp.asarray(q_hi), jnp.asarray(q_lo),
                     jnp.asarray(ev.src, jnp.int32), jnp.asarray(ev.valid))
        if t > 0 and t % ecfg.decay_every == 0:
            state = decay(state, jnp.int32(ecfg.decay_every))
        state = state._replace(tick=state.tick + 1)
        ref.step(ev, None)
    merged = se.merge_sharded_suggestions(rank(state), ecfg.rank.top_k)
    ref.rank_cycle()
    drops = int(np.asarray(state.n_route_drop).sum())
    want = ref.suggestions
    check(set(merged) == set(want),
          f"source sets differ: sharded {len(merged)} vs ref {len(want)}")
    n, bad, err = compare_tables(merged, want)
    per_shard = np.asarray(state.cooc.live_mask).reshape(n_chips, -1).sum(1)
    print(f"[sharded] {n_chips} shards x cooc {sz.cooc_capacity} "
          f"({per_dev} B state per device): {sz.correct_ticks} ticks x "
          f"{sz.correct_batch} events, {n} sources: {bad} top-k "
          f"disagreements, max score error {err:.2e}; route drops={drops}, "
          f"per-shard pairs={per_shard.tolist()} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    check(n > 0 and bad == 0, f"{bad} of {n} top-k lists disagree")
    check(drops == 0, f"{drops} routed pairs dropped")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the sharded engine on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: run from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    work = os.path.join(ROOT, ".chip_smoke")
    phase = "device"
    try:
        d0, count = phase_device(args.chips)
        if args.chips == 4:
            phase = "sharded"
            phase_sharded(Sizes(), 4, args.seed)
        else:
            sz = Sizes()
            print(f"[config] hash layout, query={sz.query_capacity} "
                  f"cooc={sz.cooc_capacity} sessions={sz.session_capacity} "
                  f"per engine (assumed: the chip's share of HBM), 1 rt "
                  f"replica; compile cache {cache_dir}", flush=True)
            phase = "tune"
            plan = phase_tune(sz, cache_dir)
            phase = "correct"
            phase_correct(sz, plan, args.seed)
            gc.collect()
            phase = "load+serve"
            shutil.rmtree(work, ignore_errors=True)
            phase_load_serve(sz, plan, args.seed, work, d0)
    except PhaseError as e:
        print(f"chip_smoke: {phase} failed: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
