"""Catch-up replay: restore a snapshot, rewind into the log, outrun time.

Paper §4.2: "since the stores are memory-resident, their contents do not
survive restarts ... a (re)started instance can rewind to an earlier point
in the hose and consume messages at a faster rate than real time to catch
up to the present; in the meantime, the frontends serve the most recently
persisted results". This module is that loop:

  1. **restore** the newest ``EngineState`` snapshot — a
     ``CheckpointManager`` checkpoint whose manifest records the log offset
     (``log_tick``) replay must resume from;
  2. **replay** the firehose-log tail *faster than real time*: chunks of
     stacked micro-batches go through the fused ``engine.ingest_many``
     ``lax.scan`` step — one device dispatch per chunk, no per-tick host
     sync. Replay-mode overrides: ranking cycles are suppressed while the
     lag to the log head is >= ``rank_lag_ticks`` (the frontend is serving
     stale tables anyway), while the decay/prune maintenance keeps its
     exact live cadence inside the scan (state equality depends on it);
  3. **hand off** to live ingestion once caught up (and run the rank cycle
     the live engine would have been due for).

Replayed state is bit-for-bit identical to an uninterrupted run (tested at
every segment boundary), exact under the lazy/exponential decay policy.

**Whole-stack recovery** (:func:`recover_service`): the serving stack is
rt engine + background engine + interpolation cache (``core.background``);
both engines consume the same hose, so one durable log serves both. Each
engine restores from its *own* snapshot chain (its own log offset) and
replays the shared tail under its *own* cadence authority — the fused
``ingest_many`` scan takes the engine's config, so the bg engine's slow
decay/prune cadences replay exactly as they would have run live. Ranking
stays suppressed per engine until that engine's lag clears.

**Snapshot chains + fallback** (``distributed.fault_tolerance``): a
snapshot step may be a *delta* (changed slots only) chained to the last
full snapshot via its manifest (``kind``/``base_step``/``sha256``). The
restore chain-walk verifies every member; a torn or corrupt delta falls
back to the newest intact full — recovery then simply resumes replay from
that older snapshot's ``log_tick``, i.e. a broken chain costs a longer
replay tail, never a failed recovery (as long as one full verifies and the
log retains the tail). ``stats["restore"]`` records the fallback.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.engine import (EngineConfig, SearchAssistanceEngine, TickStack)
from ..core.hashing import split_fp
from ..distributed.fault_tolerance import CheckpointManager
from .log import FirehoseLogReader, LogChunk


@dataclasses.dataclass(frozen=True)
class ReplayConfig:
    chunk_ticks: int = 16       # ticks fused into one ingest_many dispatch
    rank_lag_ticks: int = 4     # resume ranking once lag drops below this
    allow_gap: bool = False     # snapshot older than log retention: skip
                                # to the log start (documented state loss)
                                # instead of raising


def chunk_to_stack(chunk: LogChunk) -> TickStack:
    """Host log chunk -> device TickStack (u64 fps split into u32 lanes)."""
    with obs.span("replay.stack"):
        s_hi, s_lo = split_fp(chunk.sess_fp)
        q_hi, q_lo = split_fp(chunk.q_fp)
        g_hi, g_lo = split_fp(chunk.grams)
        return TickStack(
            sess_hi=jnp.asarray(s_hi), sess_lo=jnp.asarray(s_lo),
            q_hi=jnp.asarray(q_hi), q_lo=jnp.asarray(q_lo),
            src=jnp.asarray(chunk.src, jnp.int32),
            q_valid=jnp.asarray(chunk.q_valid),
            g_hi=jnp.asarray(g_hi), g_lo=jnp.asarray(g_lo),
            t_valid=jnp.asarray(chunk.t_valid))


class CatchUpController:
    """Drives one engine from its restored offset to the log head."""

    def __init__(self, engine: SearchAssistanceEngine,
                 reader: FirehoseLogReader,
                 rcfg: ReplayConfig = ReplayConfig()):
        self.engine = engine
        self.reader = reader
        self.rcfg = rcfg

    def catch_up(self, target_tick: Optional[int] = None,
                 refresh: bool = True) -> Dict:
        """Replay [engine.tick, target) from the log; default target is one
        past the log head. Returns replay stats (ticks, chunks, wall time,
        suppressed/run rank cycles, events replayed). ``refresh=False``
        skips re-validating the log (pass it when the reader was freshly
        constructed — its ``__init__`` already checksummed every segment,
        and doing it twice doubles the restart-critical disk pass)."""
        eng, rcfg = self.engine, self.rcfg
        if refresh:
            self.reader.refresh()
        start = int(eng.state.tick)
        head = self.reader.last_tick()
        end = target_tick if target_tick is not None else (
            head + 1 if head is not None else start)
        stats = {"start_tick": start, "end_tick": end, "n_ticks": 0,
                 "n_chunks": 0, "n_events": 0, "n_rank_suppressed": 0,
                 "n_rank_run": 0, "n_skipped_gap_ticks": 0, "wall_s": 0.0}
        t0 = time.perf_counter()
        rank_every = eng.cfg.rank_every
        if end > start:
            first = self.reader.first_tick()
            if first is not None and first > start:
                if not rcfg.allow_gap:
                    raise ValueError(
                        f"snapshot at tick {start} predates log retention "
                        f"(log starts at {first}); pass allow_gap to skip "
                        f"ahead")
                stats["n_skipped_gap_ticks"] = first - start
                eng.state = eng.state._replace(tick=jnp.int32(first))
                start = first
            for chunk in self.reader.read_chunks(start, rcfg.chunk_ticks,
                                                 upto_tick=end):
                # a chunk is normally one consecutive run; tick holes (a
                # crash tore ticks a newer snapshot had covered, or the
                # writer skipped ticks) split it into runs, each replayed
                # after an allow_gap fast-forward — skipping is safe-but-
                # lossy (§4.2: losing a little state is tolerable)
                tks = chunk.ticks
                breaks = np.nonzero(tks[1:] - tks[:-1] != 1)[0] + 1
                n_due = 0
                for run in np.split(np.arange(tks.shape[0]), breaks):
                    sub = (chunk if len(run) == tks.shape[0]
                           else LogChunk(*(a[run] for a in chunk)))
                    expect = int(eng.state.tick)
                    gap = int(sub.ticks[0]) - expect
                    if gap < 0 or (gap > 0 and not rcfg.allow_gap):
                        raise ValueError(
                            f"log gap: replay expected tick {expect}, run "
                            f"covers [{int(sub.ticks[0])}, "
                            f"{int(sub.ticks[-1])}]"
                            + ("" if gap < 0 else "; pass allow_gap to "
                               "skip the missing ticks"))
                    if gap > 0:
                        stats["n_skipped_gap_ticks"] += gap
                        eng.state = eng.state._replace(
                            tick=jnp.int32(int(sub.ticks[0])))
                    eng.step_many(chunk_to_stack(sub))
                    stats["n_ticks"] += sub.n_ticks
                    stats["n_events"] += int(sub.q_valid.sum()) \
                        + int(sub.t_valid.sum())
                    # rank boundaries crossed (tick t ranks after ingesting
                    # t, i.e. t in [run.first, run.last])
                    n_due += sum(
                        1 for t in range(int(sub.ticks[0]),
                                         int(sub.ticks[-1]) + 1)
                        if rank_every > 0 and t > 0
                        and t % rank_every == 0)
                stats["n_chunks"] += 1
                if rank_every > 0:
                    lag = end - int(eng.state.tick)
                    if lag >= rcfg.rank_lag_ticks:
                        stats["n_rank_suppressed"] += n_due
                    elif n_due:
                        # caught up enough: serve fresh tables from here on
                        eng.run_rank_cycle()
                        stats["n_rank_run"] += 1
                        stats["n_rank_suppressed"] += n_due - 1
        # handoff: if no cycle ran at the head, run one now so the frontend
        # gets fresh tables immediately (rank cycles read state, never
        # mutate it — running extra ones cannot break replay exactness).
        # This must also cover the 0-tick replay case: a snapshot can be
        # newer than the log's surviving tail (the torn segment held the
        # ticks between them) and the restored stores still deserve tables;
        # repeated catch-up calls on an already-fresh engine stay no-ops.
        if rank_every > 0 and stats["n_rank_run"] == 0 \
                and (stats["n_ticks"] > 0 or not eng.suggestions):
            eng.run_rank_cycle()
            stats["n_rank_run"] += 1
        stats["wall_s"] = time.perf_counter() - t0
        return stats


def _check_snapshot_layout(cfg: EngineConfig, ckpt: CheckpointManager,
                           step: Optional[int]) -> None:
    try:
        meta = ckpt.manifest(step).get("meta", {})
    except FileNotFoundError:
        raise                      # no checkpoints at all: fail loudly
    except (OSError, json.JSONDecodeError):
        # torn/garbled manifest: leave it to the restore chain walk, which
        # falls back to the newest intact full instead of failing here
        return
    snap_layout = meta.get("layout")
    if snap_layout is not None and snap_layout != cfg.cooc_layout:
        raise ValueError(
            f"snapshot was written under cooc_layout={snap_layout!r} but "
            f"the restoring config uses {cfg.cooc_layout!r}; region "
            f"metadata (chain directory, fills, freelist) is part of the "
            f"checkpoint and cannot be reinterpreted")


def _maybe_restore_base(engine: SearchAssistanceEngine,
                        reader: FirehoseLogReader,
                        target_tick: Optional[int]) -> Optional[Dict]:
    """Tiered restore: when the log manifest advertises a compaction base
    NEWER than the engine's current offset (and ≤ the replay target), jump
    the engine onto it before replaying. This is what keeps replay-from-
    zero alive under compaction — the log below the floor may no longer
    exist on disk — and even when it does, the base is the cheaper
    legitimate start. A torn newest base transparently falls back to an
    older retained one (``info['fell_back']``); no usable base at all
    leaves the engine untouched (the pre-compaction gap rules apply)."""
    if not reader.bases:
        return None
    from .compaction import restore_from_base   # lazy: avoids import cycle
    head = reader.last_tick()
    end = target_tick if target_tick is not None else (
        head + 1 if head is not None else None)
    res = restore_from_base(reader.dir, engine.name, engine.state,
                            max_tick=end, log_name=reader.name)
    if res is None:
        return None
    state, tick, info = res
    if tick <= int(engine.state.tick):
        return None         # own snapshot is fresher than any base
    engine.state = state
    return dict(info, base_tick=tick)


def _restore_and_catch_up(cfg: EngineConfig, ckpt: CheckpointManager,
                          reader: FirehoseLogReader,
                          rcfg: ReplayConfig, name: str,
                          target_tick: Optional[int],
                          step: Optional[int]) -> tuple:
    """Restore one engine (fresh when no snapshot exists — cold engines
    replay the whole retained log, hopping onto the newest compaction base
    first when one is advertised) and replay its tail from the shared,
    already-validated reader."""
    if step is None and ckpt.latest_step() is None:
        engine, log_tick = SearchAssistanceEngine(cfg, name), None
    else:
        _check_snapshot_layout(cfg, ckpt, step)
        engine, log_tick = SearchAssistanceEngine.restore_from_snapshot(
            cfg, ckpt, step=step, name=name)
        assert int(engine.state.tick) == log_tick, "snapshot offset mismatch"
    restore_info = dict(ckpt.last_restore)
    base_info = _maybe_restore_base(engine, reader, target_tick)
    stats = CatchUpController(engine, reader, rcfg).catch_up(target_tick,
                                                             refresh=False)
    stats["restored_step"] = log_tick
    stats["restore"] = restore_info
    stats["base"] = base_info
    return engine, stats


def recover_engine(cfg: EngineConfig, ckpt: CheckpointManager, log_dir: str,
                   rcfg: ReplayConfig = ReplayConfig(), name: str = "rt",
                   log_name: str = "firehose",
                   target_tick: Optional[int] = None,
                   step: Optional[int] = None
                   ) -> tuple:
    """The full crash-recovery path: snapshot restore + catch-up replay.

    Returns ``(engine, stats)``; the engine is caught up to the log head
    (or ``target_tick``) and ready for live ingestion. ``step`` picks a
    specific snapshot (default: the newest). The restore walks the
    snapshot's delta chain; a torn/corrupt chain member silently falls
    back to the newest intact full snapshot (``stats["restore"]``) and the
    replay tail grows to cover the difference. Under log compaction, a
    base newer than the restored snapshot is hopped onto before replay
    (``stats["base"]``) — mandatory when the log tail below the floor was
    trimmed, cheaper even when it was not.
    """
    _check_snapshot_layout(cfg, ckpt, step)
    engine, log_tick = SearchAssistanceEngine.restore_from_snapshot(
        cfg, ckpt, step=step, name=name)
    assert int(engine.state.tick) == log_tick, "snapshot offset mismatch"
    reader = FirehoseLogReader(log_dir, name=log_name)
    restore_info = dict(ckpt.last_restore)
    base_info = _maybe_restore_base(engine, reader, target_tick)
    stats = CatchUpController(engine, reader, rcfg).catch_up(target_tick,
                                                             refresh=False)
    stats["restored_step"] = log_tick
    stats["restore"] = restore_info
    stats["base"] = base_info
    return engine, stats


def recover_service(rt_cfg: EngineConfig, rt_ckpt: CheckpointManager,
                    bg_ckpt: CheckpointManager, log_dir: str,
                    rcfg: ReplayConfig = ReplayConfig(), *,
                    bg_cfg: Optional[EngineConfig] = None,
                    alpha: float = 0.7, log_name: str = "firehose",
                    target_tick: Optional[int] = None,
                    rt_step: Optional[int] = None,
                    bg_step: Optional[int] = None) -> tuple:
    """Crash-recover the WHOLE serving stack (rt + bg + interpolation).

    Restores the real-time and background engines from their respective
    snapshot directories (each records its own ``log_tick`` offset) and
    replays the shared firehose-log tail for each — the bg engine reuses
    the same fused ``ingest_many`` scan under *its* cadence authority
    (slow decay/prune cadences replay exactly as live), with ranking
    suppressed per-engine until that engine's lag clears; each engine
    ranks at its own handoff. An engine with no snapshot yet (crash before
    its first persist) cold-starts and replays the whole retained log.
    Finally the interpolation cache is rebuilt from both fresh tables.

    Returns ``(service, stats)`` with per-engine stats under ``stats["rt"]``
    and ``stats["bg"]``. The result is bit-exact vs. an uninterrupted
    service run (property-tested at every log-segment boundary).
    """
    from ..core.background import AssistanceService, background_config
    bg_cfg = bg_cfg if bg_cfg is not None else background_config(rt_cfg)
    # ONE reader validates the log once; both engines replay from it.
    reader = FirehoseLogReader(log_dir, name=log_name)
    rt_eng, rt_stats = _restore_and_catch_up(
        rt_cfg, rt_ckpt, reader, rcfg, "rt", target_tick, rt_step)
    bg_eng, bg_stats = _restore_and_catch_up(
        bg_cfg, bg_ckpt, reader, rcfg, "bg", target_tick, bg_step)
    service = AssistanceService(rt_cfg, alpha=alpha, bg_cfg=bg_cfg,
                                rt=rt_eng, bg=bg_eng)
    service.refresh_cache()
    return service, {"rt": rt_stats, "bg": bg_stats}
