"""Durable firehose log + faster-than-real-time catch-up replay (§4.2).

The paper's backend is deliberately volatile: durability comes from
persisting results periodically and from the ability of a (re)started
instance to *rewind into the firehose* and consume messages faster than
real time until it catches up, while frontends serve the last persisted
tables in the meantime. This package is that recovery subsystem:

  * :mod:`.log` — an append-only, segmented micro-batch log (npz segments +
    json manifest, atomic rename, rotation by tick count, keep-N retention,
    seek-by-tick reader, torn-tail detection), standing in for the
    replayable message queue the paper rewinds into;
  * :mod:`.replay` — the catch-up controller: restore the newest snapshot
    (checkpoint + log offset), replay the log tail through the fused
    ``engine.ingest_many`` scan step, hand off to live ingestion.
    ``recover_service`` extends this to the whole serving stack: rt engine
    + background engine + interpolation cache, each engine replaying the
    shared log from its own offset under its own cadence authority.

Snapshots themselves may be *incremental*: ``CheckpointManager`` (see
``distributed.fault_tolerance`` for the manifest format) writes delta
snapshots — changed store slots only — chained to the last full snapshot
(``kind``/``base_step``/``sha256`` in the manifest). Restore chain-walks
the deltas onto the base full; a torn or corrupt chain member falls back
to the newest intact full, and replay covers the difference from the log
(a broken chain costs tail length, never recoverability). Retention never
unlinks a full while a retained delta still references it. The shrunken
write volume is what lets the snapshot cadence drop ~4x — and the replay
tail (time-to-fresh after a crash) with it (bench_recovery rows
``recovery_snapshot_*``).

**Overload control** (:mod:`.overload`) — the live-path twin of the
recovery story (§1: the flash crowd is the paper's motivating workload).
An :class:`~repro.streaming.overload.OverloadController` in front of the
serving stack micro-batches live ticks through the same fused
``ingest_many`` scan that replay uses (batch size K adapts to lag,
quantized powers of two up to ``SLOConfig.batch_max``) and walks an
explicit **degradation ladder** when batching alone cannot hold the SLO:

  =====  =============  ==================================================
  level  name           what is shed at this rung (cumulative)
  =====  =============  ==================================================
  0      normal         nothing — full service
  1      shed_rank      rt ranking cycles (frontends serve the last
                        persisted tables, the §4.2 staleness stance)
  2      stretch_bg     bg ranking cadence stretched ``bg_stretch``x
  3      sample_ingest  tweet-firehose ingest dropped; tail-source query
                        events (``src >= tail_src``) sampled to ``tail_keep``
  =====  =============  ==================================================

*Triggers* (any): effective lag >= ``up_lag`` ticks, step-latency p95 over
``slo_ms``, region-freelist fraction under ``freelist_min``. *Hysteresis*:
one rung per ``up_ticks`` consecutive hot / ``down_ticks`` consecutive
cool observations — the ladder cannot flap. *SLO knobs* live on
:class:`~repro.streaming.overload.SLOConfig` (``slo_ms``, ``batch_max``,
``lag_batch``, hysteresis, ``bg_stretch``, ``tail_src``/``tail_keep``).
Every shed decision is counted in ``stats_snapshot()`` — never silent —
and admission runs *before* the durable log append with a pure-hash
sampler, so crash -> restore -> replay stays bit-exact mid-shed.
:mod:`.workload` generates the firehose traffic (Zipf + topic drift,
breaking-news flash crowds, spam bursts, multilingual sessions) that the
benches and the chaos harness (``kill_writer_mid_segment`` /
``corrupt_segment`` / ``corrupt_snapshot`` / :func:`~repro.streaming.log.slow_io`
/ :func:`~repro.streaming.log.flaky_io`) drive this machinery with.

**Failure model & fleet operations** — the fleet control plane
(``distributed.fleet.ServingFleet``) composes this package into a
replicated serving story. Who writes the log: exactly one replica — the
``ReplicaGroup``-elected leader — appends; every leadership change bumps
an **epoch** that the new leader stamps into the log manifest
(``FirehoseLogWriter.assume_epoch``) *before* its first append. Fencing
semantics: a writer whose epoch is older than the manifest's raises
:class:`~repro.streaming.log.WriterFencedError` at its next segment seal
and is permanently dead — a paused/partitioned ex-leader can never land a
stray segment, so split-brain on the durable log is structurally
impossible (``log_epoch`` reads the current fence token). Transient I/O:
the reader retries each segment read up to ``io_retries`` times with
exponential backoff before surfacing the error, so an NFS blip during
catch-up replay does not become a failed recovery (``flaky_io`` injects
exactly this fault class). What each state means for answer staleness:
a *live* replica answers at the current tick; the degradation ladder's
``shed_rank``/``stretch_bg`` rungs serve last-persisted rankings (§4.2:
stale-but-fast beats fresh-but-late); a *dead* replica is skipped by the
router and its requests hedge to the next-freshest survivor; a
*recovering* replica (snapshot restore + log-tail replay) is not routed
to until its lag clears, so clients never observe a rewound tick. Every
answer is tagged with its serving tick and staleness vs the freshest live
replica (``serving.serve.RouteResult``) — degraded answers are honest.

**Compaction contract** (:mod:`.compaction` + :mod:`.codec`) — the
storage tier under all of the above. Sealed segments and checkpoint
payloads are compressed (fingerprint lanes XOR-delta encoded first; codec
id + uncompressed sha256 in the manifest, on-disk sha256 unchanged), and
a :class:`~repro.streaming.compaction.LogCompactor` periodically folds
the sealed log prefix into per-engine **base snapshots** advertised in
the log manifest's ``bases`` list. *Who may compact*: only the current
leader — the compactor adopts the leadership epoch
(``LogCompactor.assume_epoch``) and re-validates it against the manifest
immediately before its atomic manifest swap, so a deposed (zombie)
compactor raises ``WriterFencedError`` without touching the manifest;
its orphaned fold output is never advertised and gets GC'd. *What the
replay floor means*: a base at tick T holds engine state covering every
tick < T — recovery (``recover_engine``/``recover_service``), fleet
restarts and ``elastic.live_reshard``'s log-tail replay all start from
the newest base ≤ their target instead of zero, so trimming segments
below the floor is safe and "replay from zero" stays possible forever
with bounded disk. Fleet **log-healing is floor-oblivious**: healing
re-appends missing ticks at the head, compaction trims the tail — the
two never touch the same segments. ``keep_bases`` old bases (plus the
log tail from the oldest retained base) remain on disk, so a torn or
corrupt newest base (``corrupt_base`` injection) degrades to the
previous base + a longer replay — counted, never a dead log. The
writer's blunt ``keep_segments`` retention warns-and-clamps rather than
trim a segment at/after the newest base.
"""
from .codec import (CodecError, decode_payload, encode_payload,
                    xor_delta_decode, xor_delta_encode)
from .compaction import (CompactionConfig, LogCompactor, corrupt_base,
                         restore_from_base)
from .log import (FirehoseLogReader, FirehoseLogWriter, LogChunk,
                  WriterFencedError, corrupt_segment, flaky_io,
                  kill_writer_mid_segment, log_bases, log_epoch, slow_io)
from .overload import (DegradationLadder, LatencyTracker, OverloadController,
                       SLOConfig, admit_events, admit_tweets)
from .replay import (CatchUpController, ReplayConfig, chunk_to_stack,
                     recover_engine, recover_service, ticks_to_stack)
from .workload import (FirehoseWorkload, SpamSpec, SpikeSpec, WorkloadConfig,
                       bucket_size)

__all__ = [
    "FirehoseLogReader", "FirehoseLogWriter", "LogChunk",
    "WriterFencedError", "corrupt_segment", "flaky_io",
    "kill_writer_mid_segment", "log_bases", "log_epoch", "slow_io",
    "CodecError", "decode_payload", "encode_payload",
    "xor_delta_decode", "xor_delta_encode",
    "CompactionConfig", "LogCompactor", "corrupt_base",
    "restore_from_base",
    "CatchUpController", "ReplayConfig", "chunk_to_stack", "recover_engine",
    "recover_service", "ticks_to_stack",
    "OverloadController", "SLOConfig", "DegradationLadder", "LatencyTracker",
    "admit_events", "admit_tweets",
    "FirehoseWorkload", "WorkloadConfig", "SpikeSpec", "SpamSpec",
    "bucket_size",
]
