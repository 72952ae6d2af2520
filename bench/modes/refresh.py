"""Refresh cycles of one real-time engine, from a store at steady fill.

Set-up fills the engine through ``step_many`` with the traffic's fill
ticks (the last ``warm_ticks`` one tick at a time, which compiles the
window's ingest program), makes the window's ticks, and loads the rank
program from the compile cache without running it.

The window runs refresh cycles back to back until ``--seconds`` have
passed, and ends at the first cycle boundary after that. One cycle:

  ingest    the next tick (``step_many``), so no two cycles rank one store
  rank      ``run_rank_cycle``: the rank program and ``suggestions_to_host``
  persist   ``pack_suggestions`` and ``CheckpointManager.save``
  poll      ``SuggestFrontend.poll`` on the one frontend
  requests  ``ServerSet.request`` for the most popular queries

``refresh_s`` is the window over the cycles completed in it.

The check compares, with the reference ranking of the same ticks: the
table persisted by the last cycle, every source of it; the frontend's
answers to the last cycle's requests and to a sample of sources drawn
from the seed; and the stores' drop counters.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time

import numpy as np

from bench import compare, feed, harness

N_SAMPLE = 256          # frontend answers checked besides the requests


@dataclasses.dataclass
class State:
    hose: object
    ticks: list
    cfg: object
    eng: object
    stacks: list
    ckpt: object
    frontend: object
    servers: object
    queries: list
    cycles: int = 0
    answers: dict = None


def setup(run) -> State:
    from repro.core import ranking
    from repro.core.engine import SearchAssistanceEngine
    from repro.distributed.fault_tolerance import CheckpointManager
    from repro.launch import autotune
    from repro.serving.serve import ServerSet, SuggestFrontend

    tr = run.cell.traffic
    fill, warm, chunk = tr["fill_ticks"], tr["warm_ticks"], \
        tr["fill_chunk_ticks"]
    with run.spans("hose"):
        hose = feed.make_hose(run.cell.config, run.seed)
        ticks = [hose.tick(t) for t in range(fill + tr["max_cycles"])]
    cfg = harness.engine_configs(run.cell.config)["rt"]
    with run.spans("tune"):
        cfg = dataclasses.replace(
            cfg, plan=autotune.tune(cfg, cache=run.autotune_cache()))
    eng = SearchAssistanceEngine(cfg, "rt")
    with run.spans("fill"):
        t = 0
        while t < fill - warm:
            n = min(chunk, fill - warm - t)
            eng.step_many(feed.stack(ticks[t:t + n], t))
            t += n
        while t < fill:
            eng.step_many(feed.stack(ticks[t:t + 1], t))
            t += 1
        harness.count_fill(run, "rt", eng.state)
    stacks = [feed.stack(ticks[t:t + 1], t)
              for t in range(fill, fill + tr["max_cycles"])]
    with run.spans("compile_rank"):
        st = eng.state
        dkw = (dict(decay_cfg=cfg.decay, now=st.tick) if cfg.lazy_decay
               else {})
        ranking.ranking_cycle.lower(st.cooc, st.qstore, cfg.rank,
                                    **dkw).compile()
    tables = os.path.join(run.work, "rt_tables")
    ckpt = CheckpointManager(tables)
    frontend = SuggestFrontend(tables, None, hose.tok)
    servers = ServerSet([frontend])
    # Zipf rank order: the most popular queries first
    queries = hose.vocab[:tr["requests_per_cycle"]]
    lanes = [st.cooc.key_hi, st.cooc.key_lo, st.qstore.key_hi,
             st.qstore.key_lo, *st.cooc.lanes.values(),
             *st.qstore.lanes.values()]
    run.counters["rank_store_bytes"] = float(sum(x.nbytes for x in lanes))
    run.counters["top_k"] = cfg.rank.top_k
    return State(hose, ticks, cfg, eng, stacks, ckpt, frontend, servers,
                 queries)


def window(run, s: State) -> dict:
    from repro.serving.serve import pack_suggestions

    sp = run.spans
    t0 = time.perf_counter()
    attempted = failed = 0
    rows = []
    while s.cycles < len(s.stacks):
        with sp("ingest"):
            s.eng.step_many(s.stacks[s.cycles])
        with sp("rank"):
            res = s.eng.run_rank_cycle()
        last = int(s.eng.state.tick) - 1      # the newest tick ingested
        with sp("persist"):
            s.ckpt.save(last, pack_suggestions(s.eng.suggestions),
                        meta={"tick": last})
        with sp("poll"):
            s.frontend.poll()
        with sp("requests"):
            answers = {}
            for q in s.queries:
                attempted += 1
                try:
                    answers[q] = s.servers.request(q, k=s.cfg.rank.top_k)
                except RuntimeError:
                    answers[q] = None
                failed += not answers[q]
        s.answers = answers
        rows.append(res["n_rows"])
        s.cycles += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    else:
        print(f"refresh: the window used all {len(s.stacks)} pregenerated "
              f"ticks; the traffic's max_cycles must grow", flush=True)
    dt = time.perf_counter() - t0
    run.counters.update(cycles=s.cycles, sources=float(np.mean(rows)),
                        ticks_fed=run.cell.traffic["fill_ticks"] + s.cycles)
    return {"metrics": {"refresh_s": dt / s.cycles},
            "attempted": attempted, "failed": failed}


def check(run, s: State) -> None:
    st = s.eng.state
    drops = sum(int(x.n_dropped) for x in (st.qstore, st.cooc, st.sessions))
    table = s.ckpt.restore_host(s.ckpt.latest_step())
    # pack_suggestions' keys, in the tree order the checkpoint flattens
    persisted = compare.unpack(dict(zip(
        ("dst", "offsets", "score", "src"),
        (table[f"leaf_{i}"] for i in range(4)))))
    s.eng = s.stacks = None                   # free the device state
    gc.collect()
    ref = harness.make_reference(run.cell.config, "rt", s.hose)
    cands = ref.run(s.ticks[:run.counters["ticks_fed"]]).candidates()
    run.checks += served_checks(
        run, s.hose, cands, persisted,
        lambda q: s.frontend.related(q, s.cfg.rank.top_k), s.answers,
        s.cfg.rank.top_k, s.frontend.alpha, drops)


def served_checks(run, hose, cands, persisted, answer, requested, k, alpha,
                  drops):
    """The refresh cell's numbers, each with its limit: the persisted
    table, the answers to the requests and to a sample of sources drawn
    from the seed, and the drop counters."""
    text_fp = {q: int(f) for q, f in zip(hose.vocab, hose.fps)}
    fp_text = {f: q for q, f in text_fp.items()}
    tab = compare.table(persisted, cands, alpha=1.0, k=k)
    srcs = cands.sources()
    pick = np.random.default_rng(run.seed).choice(
        len(srcs), size=min(N_SAMPLE, len(srcs)), replace=False)
    served = {fp_text[srcs[i]]: answer(fp_text[srcs[i]])
              for i in sorted(pick)}
    served.update({q: a or [] for q, a in requested.items()})
    ans = compare.answers(
        {text_fp[q]: [(text_fp.get(d, -1), x) for d, x in a]
         for q, a in served.items()}, cands, alpha=alpha, k=k)
    run.counters.update(sources_compared=tab["compared"],
                        sources_uncertain=tab["skipped"])
    lim = compare.LIMITS["refresh"]
    return [("table_bad_sources", tab["bad"], lim["table_bad_sources"]),
            ("table_score_gap", tab["gap"], lim["table_score_gap"]),
            ("answer_bad", ans["bad"], lim["answer_bad"]),
            ("answer_score_gap", ans["gap"], lim["answer_score_gap"]),
            ("drops", drops, lim["drops"])]
