"""Catch-up replay of the whole service from a durable backlog.

A cold start: a replica with no snapshot replays the retained log from
tick 0 into empty engines, as ``recover_service`` does for an engine that
has none. Set-up writes the traffic's backlog with the program's
``FirehoseLogWriter``, opens it with ``FirehoseLogReader`` (which checks
every segment), starts both engines empty, and loads each engine's
replay program (one ``ReplayConfig.chunk_ticks`` stack) from the compile
cache without running it.

The window replays the log as ``recover_service`` does, one increment of
``chunk_ticks`` ticks per engine at a time, rt then bg: the reader's
chunk, ``chunk_to_stack``, ``step_many``. Ranking stays suppressed, as
the catch-up rule does while replay lags the log head. It ends at the
first whole increment after ``--seconds``, or when the backlog runs out.
``replay_events_per_s`` counts each hose event once (query events plus
tweets), although both engines ingest it.

The check compares both engines' query, cooccurrence and session stores,
and their drop counters, with the reference fed the same ticks.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import numpy as np

from bench import compare, feed, harness


@dataclasses.dataclass
class State:
    hose: object
    ticks: list
    engines: dict
    reader: object
    chunk: int
    ticks_done: int = 0


def setup(run) -> State:
    from repro.core import engine as engine_mod
    from repro.launch import autotune
    from repro.streaming import (FirehoseLogReader, FirehoseLogWriter,
                                 ReplayConfig, chunk_to_stack)

    tr = run.cell.traffic
    with run.spans("hose"):
        hose = feed.make_hose(run.cell.config, run.seed)
        ticks = [hose.tick(t) for t in range(tr["backlog_ticks"])]
    log_dir = os.path.join(run.work, "log")
    with run.spans("log_write"):
        w = FirehoseLogWriter(log_dir, ticks_per_segment=tr["ticks_per_segment"])
        for t, tk in enumerate(ticks):
            w.append(t, *feed.program_tick(tk))
        w.close()
    cfgs = harness.engine_configs(run.cell.config)
    with run.spans("tune"):
        plan = autotune.tune(cfgs["rt"], cache=run.autotune_cache())
    cfgs = {n: dataclasses.replace(c, plan=plan) for n, c in cfgs.items()}
    engines = {n: engine_mod.SearchAssistanceEngine(c, n)
               for n, c in cfgs.items()}
    with run.spans("log_open"):
        reader = FirehoseLogReader(log_dir)
    chunk = ReplayConfig().chunk_ticks
    with run.spans("compile_ingest"):
        first = chunk_to_stack(next(reader.read_chunks(0, chunk,
                                                       upto_tick=chunk)))
        for n, eng in engines.items():
            engine_mod.ingest_many.lower(eng.state, first,
                                         cfg=eng.cfg).compile()
        del first
    return State(hose, ticks, engines, reader, chunk)


def window(run, s: State) -> dict:
    from repro.streaming import chunk_to_stack

    sp = run.spans
    total = len(s.ticks)
    t0 = time.perf_counter()
    increments = []
    while s.ticks_done < total:
        end = min(s.ticks_done + s.chunk, total)
        for name, eng in s.engines.items():
            start = int(eng.state.tick)
            chunks = s.reader.read_chunks(start, s.chunk, upto_tick=end)
            while True:
                with sp(f"read.{name}"):
                    chunk = next(chunks, None)
                    stack = chunk and chunk_to_stack(chunk)
                if chunk is None:
                    break
                with sp(f"ingest.{name}"):
                    eng.step_many(stack)
        s.ticks_done = end
        increments.append(time.perf_counter() - t0 - sum(increments))
        if time.perf_counter() - t0 >= run.seconds:
            break
    else:
        print(f"replay: the window replayed the whole backlog of {total} "
              f"ticks; the rate is the backlog over the time it took, and "
              f"the traffic's backlog_ticks must grow", flush=True)
    dt = time.perf_counter() - t0
    events = sum(feed.n_events(tk) for tk in s.ticks[:s.ticks_done])
    run.counters.update(ticks=s.ticks_done, ticks_fed=s.ticks_done,
                        events=events, increments_s=increments)
    return {"metrics": {"replay_events_per_s": events / dt},
            "attempted": s.ticks_done, "failed": 0}


def _export(eng) -> dict:
    """One engine's stores on the host: live entries, weights decayed to
    the engine's tick the way its reads decay them."""
    from repro.core.hashing import join_fp

    st, cfg = eng.state, eng.cfg
    now = int(st.tick)
    h = cfg.decay.half_life_ticks
    out = {"drops": sum(int(x.n_dropped)
                        for x in (st.qstore, st.cooc, st.sessions))}

    def host(table, names):
        k = join_fp(np.asarray(table.key_hi), np.asarray(table.key_lo))
        live = k != 0
        lanes = {n: np.asarray(table.lanes[n])[live] for n in names}
        return k[live], lanes

    qk, ql = host(st.qstore, ("weight", "count", "last_tick"))
    dec = lambda ln: ln["weight"].astype(np.float64) * np.exp2(
        -(now - ln["last_tick"].astype(np.float64)) / h)
    out.update(q_fp=qk, q_w=dec(ql), q_c=ql["count"].astype(np.float64))
    _, cl = host(st.cooc, ("weight", "count", "last_tick", "src_hi",
                           "src_lo", "dst_hi", "dst_lo"))
    out.update(c_src=join_fp(cl["src_hi"], cl["src_lo"]),
               c_dst=join_fp(cl["dst_hi"], cl["dst_lo"]), c_w=dec(cl),
               c_c=cl["count"].astype(np.float64))
    ss = st.sessions
    sk = join_fp(np.asarray(ss.key_hi), np.asarray(ss.key_lo))
    live = sk != 0
    ring = join_fp(np.asarray(ss.ring_hi)[live], np.asarray(ss.ring_lo)[live])
    cursor = np.asarray(ss.cursor)[live].astype(np.int64)
    filled = np.asarray(ss.filled)[live].astype(np.int64)
    W = ring.shape[1]
    # oldest first, right-aligned, 0 before the oldest: the reference's
    # layout of a session's window
    win = np.zeros_like(ring)
    for a in range(W):
        age = W - 1 - a
        col = np.mod(cursor - 1 - age, W)
        ok = age < filled
        win[ok, a] = ring[np.nonzero(ok)[0], col[ok]]
    out.update(s_fp=sk[live], s_filled=filled, s_window=win)
    return out


def check(run, s: State) -> None:
    for n, e in s.engines.items():
        harness.count_fill(run, n, e.state)
    exported = {n: _export(e) for n, e in s.engines.items()}
    s.engines = None                          # free the device state
    gc.collect()
    run.checks += store_checks(run.cell.config, s.hose,
                               s.ticks[:s.ticks_done], exported)


def store_checks(config, hose, ticks, exported):
    """The replay cell's numbers, each with its limit: every engine's
    stores against the reference fed ``ticks``."""
    sem = harness.semantics(config)
    got = {"key_diff": 0, "weight_gap": 0.0, "count_gap": 0.0,
           "session_diff": 0}
    drops = 0
    for name, prog in exported.items():
        ref = harness.make_reference(config, name, hose).run(ticks)
        r = compare.stores(prog, ref, sem[name].prune_threshold)
        for k, v in r.items():
            got[k] = got[k] + v if k.endswith("diff") else max(got[k], v)
        drops += prog["drops"]
    lim = compare.LIMITS["replay"]
    return [(k, v, lim[k]) for k, v in got.items()] + [
        ("drops", drops, lim["drops"])]
