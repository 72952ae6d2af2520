"""End-to-end search-assistance service launcher (paper Figure 4).

Runs the full deployed architecture on a synthetic stream: backend
engine(s) consuming the query hose + firehose, leader-elected persistence
every rank cycle, frontend replicas polling for fresh results, background
model + interpolation, and a periodic spelling job.

The stack is **restartable end to end**: the elected leader appends every
tick to a durable firehose log and snapshots BOTH engine states (real-time
and background) into delta-chained checkpoint dirs (changed slots only
between fulls — ``--full-every``). Kill the process and relaunch with
``--recover`` and it restores both engines from their snapshot chains,
replays the shared log tail faster than real time (ranking suppressed per
engine until its lag clears), rebuilds the interpolation cache, and keeps
serving from where it left off.

With ``--slo-ms`` set the live path runs under the overload controller
(``streaming/overload.py``): lag-adaptive micro-batching through the fused
``ingest_many`` scan plus the degradation ladder (shed rt ranking ->
stretch bg ranking -> admission-control ingest), every shed counted and
surfaced in the status line. ``--workload firehose`` swaps the synthetic
stream for the flash-crowd workload generator (``--spike-mult`` x volume
at ``--spike-at``), ``--tick-ms`` paces simulated arrivals so falling
behind real time shows up as lag, and ``--slow-io-ms`` injects disk
latency into the log writer (chaos knob).

With ``--compact-every N`` the leader periodically folds the sealed log
into a base snapshot (``streaming/compaction.py``): on-disk log bytes stay
bounded while replay-from-zero survives via the newest base — the fleet
path takes the same flag through ``FleetConfig.compact_every``.

With ``--fleet N`` the run switches to the self-healing replicated fleet
(``distributed.fleet.ServingFleet``): N full serving stacks replaying one
leader-written, epoch-fenced durable log, heartbeat failure detection,
lag-gated readmission, and hedged staleness-aware routing. The chaos
knobs ``--kill-leader-at`` (mid-segment) and ``--kill-follower-at``
demonstrate failover + self-healing live; requests keep being answered
throughout.

  python -m repro.launch.serve_assist --ticks 120 --out /tmp/assist
  python -m repro.launch.serve_assist --ticks 120 --out /tmp/assist --recover
  python -m repro.launch.serve_assist --ticks 120 --out /tmp/assist \\
      --slo-ms 80 --workload firehose --spike-mult 50 --tick-ms 40
  python -m repro.launch.serve_assist --ticks 48 --out /tmp/assist \\
      --fleet 3 --workload firehose --spike-at 6 \\
      --kill-leader-at 7 --kill-follower-at 12
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from ..core.background import AssistanceService, background_config
from ..core.engine import EngineConfig, SearchAssistanceEngine
from ..core.spelling import SpellConfig, spelling_cycle
from ..core import stores
from ..core.hashing import join_fp
from ..data.stream import StreamConfig, SyntheticStream, steve_jobs_scenario
from ..distributed.fault_tolerance import CheckpointManager, ReplicaGroup
from ..serving.serve import SuggestFrontend, ServerSet, pack_suggestions
from ..streaming import (FirehoseLogReader, FirehoseLogWriter, ReplayConfig,
                         FirehoseWorkload, SLOConfig, SpamSpec, SpikeSpec,
                         WorkloadConfig, recover_service, slow_io)
from .compile_cache import use_compile_cache


def _fmt(v, nd: int = 1):
    """Status-line formatting: a missing signal prints as '?', not None
    (lag is None before the first log segment seals; latency percentiles
    are None before the first overload-meta persist)."""
    if v is None:
        return "?"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def _run_fleet(args, ecfg, gen_tick, head, head_t0) -> None:
    """--fleet N: the self-healing replicated fleet, chaos knobs wired."""
    from ..distributed.fleet import FleetConfig, ServingFleet
    fleet = ServingFleet(args.out, ecfg,
                         FleetConfig(n_replicas=args.fleet,
                                     compact_every=args.compact_every,
                                     keep_bases=args.keep_bases))
    ss = fleet.serverset(timeout_s=0.25, max_retries=1)
    for t in range(args.ticks):
        ev, tw = gen_tick(t)
        if t == args.kill_leader_at:
            lead = fleet.leader()
            fleet.kill(lead, mid_segment=True)
            print(f"[t={t}] leader {lead} KILLED mid-segment (torn tail)")
        if t == args.kill_follower_at:
            victim = next((r.rid for r in fleet._replicas
                           if r.status == "live"
                           and r.rid != fleet.leader()), None)
            if victim is not None:
                fleet.kill(victim)
                print(f"[t={t}] follower {victim} killed")
        fleet.offer_tick(t, ev, tw)
        if t % 6 == 0 and t >= head_t0:
            res = ss.request_info(head, k=5)
            m = fleet.metrics()
            print(f"[t={t}] related('{head}') via replica {res.replica} "
                  f"(tick={_fmt(res.tick)} staleness={_fmt(res.staleness)}"
                  f"{' HEDGED' if res.hedged else ''}) "
                  f"{len(res.suggestions)} rows | leader={m['leader']} "
                  f"epoch={m['epoch']} "
                  f"status={[r['status'] for r in m['replicas'].values()]}")
    m = fleet.metrics()
    print(f"[done] fleet: {ss.n_requests} requests ({ss.n_hedged} hedged), "
          f"{m['n_failovers']} failovers, {m['n_recoveries']} recoveries, "
          f"log healed {m['n_healed_ticks']} ticks "
          f"({m['n_lost_ticks']} lost), epoch {m['epoch']}, "
          f"{m['n_compactions']} compactions "
          f"(floor={_fmt(m['log_floor_tick'])})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--out", default="/tmp/assist")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--fleet", type=int, default=0,
                    help="run N self-healing fleet replicas instead of the "
                         "single-stack path (distributed.fleet)")
    ap.add_argument("--kill-leader-at", type=int, default=-1,
                    help="fleet chaos: kill the log-writer leader "
                         "mid-segment at this tick")
    ap.add_argument("--kill-follower-at", type=int, default=-1,
                    help="fleet chaos: kill a live follower at this tick")
    ap.add_argument("--fail-replica-at", type=int, default=-1,
                    help="tick at which backend replica 0 dies (failover demo)")
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="tick at which the WHOLE stack exits mid-run "
                         "(relaunch with --recover to pick it back up)")
    ap.add_argument("--recover", action="store_true",
                    help="restore rt+bg engine state from the snapshot "
                         "chains and replay the log tail before serving")
    ap.add_argument("--full-every", type=int, default=4,
                    help="state-snapshot chain: one full every N snapshots, "
                         "deltas (changed slots only) in between")
    ap.add_argument("--use-kernel", action="store_true",
                    help="legacy: force ALL hot paths through Pallas "
                         "(overrides --autotune's measured plan)")
    ap.add_argument("--autotune", action="store_true",
                    help="measure kernel-vs-jnp per hot path at startup "
                         "(cached per backend/shape class) and run the "
                         "winning plan")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="enable overload control with this per-tick step "
                         "latency SLO (0 = legacy per-tick path)")
    ap.add_argument("--workload", choices=("synthetic", "firehose"),
                    default="synthetic",
                    help="'firehose' = flash-crowd workload generator "
                         "(streaming/workload.py)")
    ap.add_argument("--spike-mult", type=float, default=50.0,
                    help="flash-crowd peak volume multiplier (firehose)")
    ap.add_argument("--spike-at", type=int, default=30,
                    help="flash-crowd onset tick (firehose)")
    ap.add_argument("--tick-ms", type=float, default=0.0,
                    help="simulated real-time budget per tick; processing "
                         "slower than this accrues lag (0 = no pacing)")
    ap.add_argument("--slow-io-ms", type=float, default=0.0,
                    help="inject this much latency into every log-segment "
                         "seal (chaos: degraded disk)")
    ap.add_argument("--compact-every", type=int, default=0,
                    help="fold the sealed log into a base snapshot every N "
                         "ticks: bounded on-disk bytes, replay-from-zero "
                         "kept alive via the base (0 = no compaction)")
    ap.add_argument("--keep-bases", type=int, default=2,
                    help="compaction fallback depth: old bases (and their "
                         "log tail) retained after each floor swap")
    args = ap.parse_args()
    use_compile_cache()

    if args.workload == "firehose":
        wl = FirehoseWorkload(WorkloadConfig(
            base_queries_per_tick=1024, base_tweets_per_tick=64,
            spikes=(SpikeSpec(t_start=args.spike_at, mult=args.spike_mult),),
            spam=SpamSpec()), seed=0)
        gen_tick, tok = wl.gen_tick, wl.tok
        head, head_t0 = "breaking0 term0", args.spike_at
    else:
        scfg, event = steve_jobs_scenario(
            base_cfg=StreamConfig(vocab_size=2048, queries_per_tick=1024,
                                  tweets_per_tick=128))
        stream = SyntheticStream(scfg, seed=0)
        gen_tick, tok = stream.gen_tick, stream.tok
        head, head_t0 = "steve jobs", event.t_start
    # use_kernel stays None unless the legacy flag is given — a bool here
    # force-overrides the tuned plan at every dispatch site.
    ecfg = EngineConfig(query_capacity=1 << 14, cooc_capacity=1 << 17,
                        session_capacity=1 << 14, decay_every=6,
                        rank_every=12,
                        use_kernel=True if args.use_kernel else None)
    if args.autotune:
        from .autotune import tune_engine_config
        ecfg = tune_engine_config(ecfg)
        print("[assist] tuned plan:", ecfg.plan.variants())
    if args.fleet > 0:
        _run_fleet(args, ecfg, gen_tick, head, head_t0)
        return
    bgcfg = background_config(ecfg, rank_every_mult=3)

    rt_dir = os.path.join(args.out, "rt")
    bg_dir = os.path.join(args.out, "bg")
    spell_dir = os.path.join(args.out, "spell")
    log_dir = os.path.join(args.out, "log")
    state_rt = os.path.join(args.out, "state", "rt")
    state_bg = os.path.join(args.out, "state", "bg")
    rt_group = ReplicaGroup(args.replicas, CheckpointManager(rt_dir))
    # engine-STATE snapshots (the recovery path): delta-chained so the
    # cadence can match every rank cycle without a write-volume blowup
    state_rt_ckpt = CheckpointManager(state_rt, keep_n=4,
                                      full_interval=args.full_every)
    state_bg_ckpt = CheckpointManager(state_bg, keep_n=4,
                                      full_interval=args.full_every)

    start_tick = 0
    if args.recover:
        # recover_service handles engines with no snapshot yet (a crash
        # before the first persist): they cold-start and replay the whole
        # retained log, so resume always lands past the logged ticks.
        # allow_gap: a snapshot can be newer than the log's surviving tail
        # (unflushed ticks died with the crash) — resuming appends past the
        # hole is the paper's stance (§4.2: losing a little state is
        # tolerable), and later recoveries skip it instead of failing.
        FirehoseLogReader(log_dir).repair()   # drop torn-tail debris
        t0 = time.perf_counter()
        svc, rstats = recover_service(ecfg, state_rt_ckpt, state_bg_ckpt,
                                      log_dir,
                                      ReplayConfig(chunk_ticks=8,
                                                   allow_gap=True),
                                      bg_cfg=bgcfg)
        dt = time.perf_counter() - t0
        print(f"[recover] rt: replayed {rstats['rt']['n_ticks']} ticks from "
              f"snapshot {rstats['rt']['restored_step']}, bg: "
              f"{rstats['bg']['n_ticks']} ticks from "
              f"{rstats['bg']['restored_step']} "
              f"(fell_back={rstats['bg']['restore'].get('fell_back')}); "
              f"{dt:.1f}s to fresh tables")
        backends = [svc.rt]
        for i in range(1, args.replicas):
            eng = SearchAssistanceEngine(ecfg, name=f"rt{i}")
            eng.state = svc.rt.state       # replicated, not sharded
            eng.suggestions = dict(svc.rt.suggestions)
            backends.append(eng)
        bg_engine = svc.bg
        start_tick = int(svc.rt.state.tick)
    else:
        backends = [SearchAssistanceEngine(ecfg, name=f"rt{i}")
                    for i in range(args.replicas)]
        bg_engine = SearchAssistanceEngine(bgcfg, name="bg")

    writer = FirehoseLogWriter(log_dir, ticks_per_segment=8,
                               keep_segments=16)
    if args.slow_io_ms > 0:
        slow_io(writer, ("flush",), args.slow_io_ms / 1e3)
    compactor = None
    if args.compact_every > 0:
        from ..streaming.compaction import CompactionConfig, LogCompactor
        # folds under the names recover_service restores ("rt"/"bg")
        compactor = LogCompactor(
            log_dir, {"rt": ecfg, "bg": bgcfg},
            cfg=CompactionConfig(keep_bases=args.keep_bases))
    bg_ckpt = CheckpointManager(bg_dir)
    spell_ckpt = CheckpointManager(spell_dir)

    frontends = [SuggestFrontend(rt_dir, bg_dir, tok,
                                 spell_dir=spell_dir, log_dir=log_dir)
                 for _ in range(2)]
    serverset = ServerSet(frontends)

    # overload control (--slo-ms): one controller drives the whole stack —
    # leader rt engine + bg engine, with the follower replicas as mirrors
    # fed the same fused flushed stacks
    svc = None
    if args.slo_ms > 0:
        svc = AssistanceService(rt=backends[0], bg=bg_engine,
                                slo=SLOConfig(slo_ms=args.slo_ms),
                                mirrors=backends[1:])

    def log_all(tick, ev_a, tw_a):
        # the elected leader appends (the admitted batch) to the durable log
        for rid in rt_group.live():
            rt_group.log_append(rid, writer, tick, ev_a, tw_a)

    wall0 = time.perf_counter()
    for t in range(start_tick, args.ticks):
        ev, tw = gen_tick(t)
        if args.fail_replica_at == t:
            rt_group.fail(0)
            print(f"[t={t}] replica 0 FAILED; leader is now {rt_group.leader()}")

        if svc is not None:
            # simulated arrival pacing: ticks arrive every --tick-ms of
            # wall time; processing slower than that accrues lag the
            # controller must batch/shed away
            lag_hint = 0.0
            if args.tick_ms > 0:
                arrived = (time.perf_counter() - wall0) * 1e3 / args.tick_ms
                lag_hint = max(0.0, start_tick + arrived - t)
            res = svc.step(ev, tw, log_append=log_all, lag_hint=lag_hint)
            leader = rt_group.leader()
            ranked = res is not None and res.get("rt") is not None
            # persist on a rank cycle — and heartbeat at the same cadence
            # while ranking is shed, so frontends keep seeing fresh shed /
            # latency telemetry (and the leader keeps snapshotting state
            # for crash recovery) through a sustained overload. The
            # heartbeat re-persists the STALE table under its honest
            # ``tick`` (the last ranked tick), never claiming freshness.
            heartbeat = (not ranked and t > 0
                         and t % svc.rt.cfg.rank_every == 0)
            if (ranked or heartbeat) and leader is not None:
                done = int(svc.rt.state.tick) - 1   # stats watermark
                meta = {"layout": svc.rt.cfg.cooc_layout,
                        "overload": svc.overload.stats_snapshot()}
                if svc.rt.cfg.plan is not None:   # tuned variants -> metrics
                    meta["plan"] = svc.rt.cfg.plan.to_json()
                if ranked:
                    meta["tick"] = done             # last reflected tick
                elif svc.rt.last_rank_tick >= 0:
                    meta["tick"] = int(svc.rt.last_rank_tick) - 1
                if svc.rt.last_maintenance:
                    meta["maintenance"] = svc.rt.last_maintenance
                wrote = rt_group.persist(
                    leader, done, pack_suggestions(svc.rt.suggestions), meta)
                if wrote:
                    svc.save_snapshot(state_rt_ckpt, state_bg_ckpt)
                    print(f"[t={t}] leader persisted "
                          f"{len(svc.rt.suggestions)} rows"
                          f"{' (heartbeat)' if heartbeat else ''} at level "
                          f"{svc.overload.ladder.name} (snapshots: rt="
                          f"{state_rt_ckpt.last_save_kind}, bg="
                          f"{state_bg_ckpt.last_save_kind})")
            if res is not None and res.get("bg") is not None:
                bg_ckpt.save(t, pack_suggestions(svc.bg.suggestions),
                             meta={"tick": int(svc.bg.state.tick) - 1})
        else:
            log_all(t, ev, tw)
            results = []
            for rid, eng in enumerate(backends):
                if not rt_group.alive[rid]:
                    continue
                results.append((rid, eng.step(ev, tw)))
            bg_res = bg_engine.step(ev, tw)

            for rid, res in results:
                if res is not None:   # a rank cycle ran -> leader persists
                    eng = backends[rid]
                    meta = {"tick": t, "layout": eng.cfg.cooc_layout}
                    if eng.last_maintenance:  # freelist pressure -> frontends
                        meta["maintenance"] = eng.last_maintenance
                    if eng.cfg.plan is not None:  # tuned variants -> metrics
                        meta["plan"] = eng.cfg.plan.to_json()
                    wrote = rt_group.persist(
                        rid, t, pack_suggestions(eng.suggestions), meta)
                    if wrote:
                        # leader also snapshots BOTH engine states (delta-
                        # chained) so a crashed stack restores rt AND bg
                        eng.save_snapshot(state_rt_ckpt)
                        bg_engine.save_snapshot(state_bg_ckpt)
                        print(f"[t={t}] leader replica {rid} persisted "
                              f"{len(backends[rid].suggestions)} suggestion "
                              f"rows (state snapshots: rt="
                              f"{state_rt_ckpt.last_save_kind}/"
                              f"{state_rt_ckpt.last_save_bytes}B, bg="
                              f"{state_bg_ckpt.last_save_kind}/"
                              f"{state_bg_ckpt.last_save_bytes}B)")
            if bg_res is not None:
                bg_ckpt.save(t, pack_suggestions(bg_engine.suggestions),
                             meta={"tick": t})

        # leader folds the sealed log into a base on cadence (bounded
        # on-disk bytes; replay-from-zero survives via the base)
        if compactor is not None and t > 0 \
                and t % args.compact_every == 0 \
                and rt_group.leader() is not None:
            writer.flush()          # seal the tail so the floor reaches t
            compactor.assume_epoch(rt_group.epoch)
            cst = compactor.compact()
            if not cst.get("noop"):
                print(f"[t={t}] compacted: floor={cst['floor']} "
                      f"dropped {cst['n_segments_dropped']} segments "
                      f"({cst['wall_s']:.2f}s)")

        # periodic spelling job (paper: a Pig job over a long span)
        if t > 0 and t % 60 == 0:
            leader = rt_group.leader()
            if leader is not None:
                exp = stores.export_live(backends[leader].state.qstore)
                fps = join_fp(exp["key_hi"], exp["key_lo"])
                texts = [tok.text(int(f)) for f in fps]
                corr = spelling_cycle(fps, texts, exp["weight"],
                                      SpellConfig(use_kernel=args.use_kernel))
                if corr:
                    a = np.array(list(corr.keys()), np.uint64)
                    b = np.array([v[0] for v in corr.values()], np.uint64)
                    d = np.array([v[1] for v in corr.values()], np.float64)
                    spell_ckpt.save(t, [a, b, d])
                    print(f"[t={t}] spelling job: {len(corr)} corrections")

        # frontends poll every tick (paper: every minute)
        for f in frontends:
            f.poll()

        if t % 12 == 0 and t >= head_t0:
            sugg = serverset.request(head, k=5)
            m = frontends[0].metrics()
            line = (f"[t={t}] related('{head}') = "
                    f"{[(s, round(sc, 3)) for s, sc in sugg]} "
                    f"(rt_lag={_fmt(m['rt_lag_ticks'])} "
                    f"bg_lag={_fmt(m['bg_lag_ticks'])}")
            if svc is not None:
                line += (f" | p50/p95/p99="
                         f"{_fmt(m['step_p50_ms'])}/"
                         f"{_fmt(m['step_p95_ms'])}/"
                         f"{_fmt(m['step_p99_ms'])}ms"
                         f" level={_fmt(m['shed_level_name'])}"
                         f" shed={_fmt(m['n_shed_total'])}"
                         f" [live: level={svc.overload.ladder.name}"
                         f" shed={svc.overload.stats_snapshot()['n_shed_total']}]")
            print(line + ")")

        if args.crash_at == t:
            # no drain: buffered-but-unflushed ticks are already in the
            # durable log, so --recover replays them (bit-exact mid-shed)
            print(f"[t={t}] CRASH (simulated): relaunch with --recover "
                  f"--out {args.out}")
            return

    if svc is not None:
        svc.drain()
        print(f"[done] overload stats: {svc.overload.stats_snapshot()}")
    writer.close()
    print("final suggestions for head query:",
          serverset.request(head, k=8))


if __name__ == "__main__":
    main()
