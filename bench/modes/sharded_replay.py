"""Catch-up replay of one sharded real-time engine over the chips of a host.

A cold start, as in ``modes/replay.py``: a sharded replica with no
snapshot replays the retained firehose log from tick 0 into empty stores.
Set-up first checks that the program has the sharded ``TickStack`` replay
(``sharded_engine.ShardedEngine``), and fails at once where it has not.
It then writes the traffic's backlog with the program's
``FirehoseLogWriter``, opens it with ``FirehoseLogReader`` (which checks
every segment), builds a mesh over the configuration's ``n_shards`` chips,
starts the sharded state empty and loads the replay program (one
``ReplayConfig.chunk_ticks`` stack) from the compile cache without running
it. It tunes nothing: the tuned plan picks no path of this program (the
hash layout and the lazy decay run no kernel in ingest).

The window replays the log one chunk of ``chunk_ticks`` ticks at a time:
the reader's chunk, ``chunk_to_stack``, ``ShardedEngine.step_many``.
Ranking stays suppressed, as the catch-up rule does while replay lags the
log head. It ends at the first whole chunk after ``--seconds``, or when
the backlog runs out. ``replay_events_per_s`` counts each query event and
each tweet once.

The check exports the union of the shards and one query-store replica in
the layout of ``modes/replay.py`` and compares it with the reference fed
the same ticks, by that mode's ``store_checks`` (``drops`` counts the
route drops with the stores' own), and counts ``qstore_replica_diff``:
the query-store slots in which a replica differs from the first.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time
import types

import numpy as np

from bench import feed, harness
from bench.modes.replay import _export, store_checks


@dataclasses.dataclass
class State:
    hose: object
    ticks: list
    engine: object
    devices: list
    reader: object
    chunk: int
    ticks_done: int = 0


def sharded_config(config: dict):
    """The program's ``ShardedConfig`` as the configuration file states it."""
    from repro.core.sharded_engine import ShardedConfig
    sh = config["sharding"]
    return ShardedConfig(base=harness.engine_configs(config)["rt"],
                         n_salts=sh["n_salts"],
                         route_capacity=sh["route_capacity"],
                         tweet_route_capacity=sh["tweet_route_capacity"])


def setup(run) -> State:
    from repro.core import sharded_engine as se
    if not hasattr(se, "ShardedEngine"):
        raise harness.BenchError("the program has no sharded TickStack "
                                 "replay (sharded_engine.ShardedEngine)")
    import jax
    from jax.sharding import Mesh
    from repro.streaming import (FirehoseLogReader, FirehoseLogWriter,
                                 ReplayConfig, chunk_to_stack)

    config, tr = run.cell.config, run.cell.traffic
    n = config["sharding"]["n_shards"]
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise harness.BenchError(f"{n} shards need {n} devices; JAX found "
                                 f"{len(jax.devices())}")
    with run.spans("hose"):
        hose = feed.make_hose(config, run.seed)
        ticks = [hose.tick(t) for t in range(tr["backlog_ticks"])]
    log_dir = os.path.join(run.work, "log")
    with run.spans("log_write"):
        w = FirehoseLogWriter(log_dir, ticks_per_segment=tr["ticks_per_segment"])
        for t, tk in enumerate(ticks):
            w.append(t, *feed.program_tick(tk))
        w.close()
    with run.spans("init"):
        eng = se.ShardedEngine(sharded_config(config),
                               Mesh(np.array(devices), ("shard",)))
    with run.spans("log_open"):
        reader = FirehoseLogReader(log_dir)
    chunk = ReplayConfig().chunk_ticks
    with run.spans("compile_ingest"):
        first = chunk_to_stack(next(reader.read_chunks(0, chunk,
                                                       upto_tick=chunk)))
        eng.ingest_many.lower(eng.state, first).compile()
        del first
    st = eng.state
    run.counters.update(n_shards=n, qstore_slot_bytes=row_bytes(st.qstore),
                        cooc_slot_bytes=row_bytes(st.cooc),
                        session_row_bytes=row_bytes(st.sessions))
    return State(hose, ticks, eng, devices, reader, chunk)


def window(run, s: State) -> dict:
    from repro.streaming import chunk_to_stack

    sp = run.spans
    total = len(s.ticks)
    chunks = s.reader.read_chunks(0, s.chunk, upto_tick=total)
    t0 = time.perf_counter()
    increments = []
    while True:
        with sp("read"):
            chunk = next(chunks, None)
            stack = chunk and chunk_to_stack(chunk)
        if chunk is None:
            print(f"sharded_replay: the window replayed the whole backlog "
                  f"of {total} ticks; the rate is the backlog over the time "
                  f"it took, and the traffic's backlog_ticks must grow",
                  flush=True)
            break
        with sp("ingest"):
            s.engine.step_many(stack)
        s.ticks_done = s.engine.tick
        increments.append(time.perf_counter() - t0 - sum(increments))
        if time.perf_counter() - t0 >= run.seconds:
            break
    dt = time.perf_counter() - t0
    done = s.ticks[:s.ticks_done]
    events = sum(feed.n_events(tk) for tk in done)
    run.counters.update(
        ticks=s.ticks_done, ticks_fed=s.ticks_done, events=events,
        increments_s=increments,
        query_events=sum(int(tk.valid.sum()) for tk in done),
        tweet_grams=sum(int(np.count_nonzero(tk.grams[tk.t_valid]))
                        for tk in done))
    return {"metrics": {"replay_events_per_s": events / dt},
            "attempted": s.ticks_done, "failed": 0}


def replica_diff(table) -> int:
    """Slots of a replicated store in which some replica differs from the
    first; a differing scalar (a counter) counts one."""
    import jax
    rows, scalars = None, 0
    for leaf in jax.tree.leaves(table):
        reps = [np.asarray(x.data) for x in leaf.addressable_shards]
        ne = np.zeros(reps[0].shape, bool)
        for r in reps[1:]:
            ne |= r != reps[0]
        if ne.ndim == 0:
            scalars += int(ne)
        else:
            ne = ne.reshape(ne.shape[0], -1).any(1)
            rows = ne if rows is None else rows | ne
    return (0 if rows is None else int(rows.sum())) + scalars


def row_bytes(table) -> int:
    """Bytes of one slot of a store, every lane at the width held."""
    import jax
    C = table.key_hi.shape[0]
    return sum(x.nbytes for x in jax.tree.leaves(table)
               if x.ndim and x.shape[0] == C) // C


def check(run, s: State) -> None:
    for i, d in enumerate(s.devices):
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        if peak is not None:
            run.counters[f"memory_peak_bytes.chip{i}"] = peak
    st = s.engine.state
    harness.count_fill(run, "rt", st)
    qdiff = replica_diff(st.qstore)
    # the shards' tables side by side are the union of the shards
    union = st._replace(
        cooc=st.cooc._replace(n_dropped=int(np.asarray(st.cooc.n_dropped).sum())),
        sessions=st.sessions._replace(
            n_dropped=int(np.asarray(st.sessions.n_dropped).sum())))
    exported = _export(types.SimpleNamespace(state=union,
                                             cfg=s.engine.cfg.base))
    exported["drops"] += int(np.asarray(st.n_route_drop).sum())
    s.engine = union = st = None              # free the device state
    gc.collect()
    run.checks += store_checks(run.cell.config, s.hose,
                               s.ticks[:s.ticks_done], {"rt": exported})
    run.checks.append(("qstore_replica_diff", qdiff, 0))
