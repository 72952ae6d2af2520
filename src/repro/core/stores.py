"""In-memory statistics stores as fixed-capacity, dense-array hash tables.

The paper's backend holds three in-memory stores (§4.2): the *query
statistics store*, the *query cooccurrence statistics store*, and the
*sessions store*. The deployed Twitter engine used JVM hash-maps mutated
event-at-a-time; the TPU-native adaptation here uses **open-addressing hash
tables laid out as dense JAX arrays** updated by *micro-batches* of events:

  * keys are 64-bit fingerprints stored as two uint32 lanes (no jax x64),
  * a batch of updates is deduplicated with a stable lexsort + segment-sum,
  * existing keys are found with a K-round triangular probe (all rounds are
    scanned when a key may be absent, which makes lookups correct in the
    presence of pruned slots without tombstones; the sweep early-exits the
    moment every key is resolved, and a large lookup batch runs its late
    rounds over its unresolved rows alone, in a small tail arena),
  * finds and claims share ONE fused sweep (``_find_or_claim``): the find
    rounds also record each row's empty-slot candidates as a bitmask, then
    claim rounds resolve conflicts *batch-locally* — contenders for a slot
    are ordered by a single packed (slot, batch idx) key and the first of
    each slot-run wins, O(B log B) per round instead of a capacity-sized
    scatter-max race (unique keys after dedup => at most one winner per
    key, losers fall to their next bit); packing the batch index into the
    sort key makes the winner *deterministic-by-arrival* rather than a
    property of the sort's stability,
  * keys that fail to place after K rounds are *dropped and counted* — the
    paper's engine likewise rate-limits/prunes to bound memory (§4.4).

**Source-major region layout** (:class:`RegionTable`): the cooccurrence
store can alternatively be partitioned into fixed-width per-source
*regions*, organized for the query it serves — per-source top-k (§4).
Invariants (the region-layout contract, relied on by ``ranking.py``,
``decay.py`` and the checkpoint/replay path):

  * **region id = source qstore slot**: the chain *directory* is indexed by
    the source query's qstore slot (``chain_region[slot]`` lists the pool
    regions owned by the source whose fingerprint is ``chain_hi/lo[slot]``;
    the fingerprint detects slot reuse after qstore pruning). A ranking
    bucket is therefore known at *insert* time — no per-cycle grouping
    sort, the ``[n_regions, width]`` bucket grid is a pure reshape.
  * **spill chain order**: a source's regions are ordered by directory
    position (depth 0 = primary, then spill regions in allocation order);
    within a region, pairs sit at positions ``[0, fill)`` in insertion
    order (dedup-sorted order within a batch). Inserts append into the
    first region with a free tail slot, in chain order.
  * **freelist lifecycle**: regions with ``region_owner < 0`` are free;
    allocation claims them in ascending region-id order (deterministic).
    The prune/decay sweeps compact every region live-first, recount
    ``region_fill``, unlink emptied regions from their chain (closing the
    hole so the chain stays a prefix), clear *orphaned* regions (owner
    slot re-claimed by another source, or source gone from the qstore) and
    return all of them to the freelist.
  * pairs whose source is absent from the qstore at insert time, spill
    chains past ``max_chain`` regions, and allocation failures are all
    *dropped and counted* in ``n_dropped`` — never silent (§4.4 again).

All operations are functional (table in, table out) and jit-compatible.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import probe_hash, combine_fp_device

# Lane reduction modes.
ADD = "add"    # accumulate (weights, counts)
SET = "set"    # last-writer-wins (timestamps, language, src/dst fps)
MAX = "max"    # running max


class HashTable(NamedTuple):
    """Open-addressing hash table over (hi, lo) uint32 fingerprint pairs."""
    key_hi: jax.Array          # u32[C]; (0,0) == empty slot
    key_lo: jax.Array          # u32[C]
    lanes: Dict[str, jax.Array]   # each [C] or [C, ...]
    n_dropped: jax.Array       # i32[] — updates dropped due to probe failure

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]

    @property
    def live_mask(self) -> jax.Array:
        return (self.key_hi != 0) | (self.key_lo != 0)

    def live_count(self) -> jax.Array:
        return jnp.sum(self.live_mask.astype(jnp.int32))


def make_table(capacity: int, lane_specs: Dict[str, Any]) -> HashTable:
    """lane_specs: name -> dtype or (dtype, trailing_shape)."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    lanes = {}
    for name, spec in lane_specs.items():
        if isinstance(spec, tuple):
            dtype, trailing = spec
            lanes[name] = jnp.zeros((capacity, *trailing), dtype=dtype)
        else:
            lanes[name] = jnp.zeros((capacity,), dtype=spec)
    return HashTable(
        key_hi=jnp.zeros((capacity,), jnp.uint32),
        key_lo=jnp.zeros((capacity,), jnp.uint32),
        lanes=lanes,
        n_dropped=jnp.zeros((), jnp.int32),
    )


def _probe_slot(h0: jax.Array, r: int, capacity: int) -> jax.Array:
    """Triangular probing: h0 + r(r+1)/2 mod C covers all slots for C=2^k."""
    return (h0 + jnp.uint32(r * (r + 1) // 2)) & jnp.uint32(capacity - 1)


def _probe_slot_dyn(h0: jax.Array, r: jax.Array, capacity: int) -> jax.Array:
    """`_probe_slot` with a *traced* round index (uint32 scalar or [B])."""
    r = r.astype(jnp.uint32)
    return (h0 + ((r * (r + 1)) >> 1)) & jnp.uint32(capacity - 1)


def _claim_winners(slot: jax.Array, contend: jax.Array, B: int, C: int
                   ) -> jax.Array:
    """First-of-each-slot-run claim resolution, deterministic-by-arrival.

    Packs ``(slot, batch idx)`` into ONE uint32 sort key whenever
    ``log2(C) + ceil_log2(B) <= 31`` (the common case), so the winner of
    every contended slot is the lowest batch index *by key value* — no
    reliance on sort stability. When the packed key would overflow 31 bits,
    falls back to a two-key lexsort over (idx, slot); the (slot, idx) pairs
    are unique, so any correct sort yields the same winners.

    Returns a [B] bool mask of winning rows (at most one per slot).
    """
    idx = jnp.arange(B, dtype=jnp.uint32)
    bits_b = max((B - 1).bit_length(), 1)
    if (C - 1).bit_length() + bits_b <= 31:
        sent = jnp.uint32(0xFFFFFFFF)
        packed = jnp.where(
            contend, (slot << jnp.uint32(bits_b)) | idx, sent)
        order = jnp.argsort(packed)
        po = packed[order]
        pslot = po >> jnp.uint32(bits_b)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), pslot[1:] != pslot[:-1]])
        return jnp.zeros((B,), bool).at[order].set(first & (po != sent))
    skey = jnp.where(contend, slot.astype(jnp.int32), C)
    order = jnp.lexsort((idx.astype(jnp.int32), skey))
    so = skey[order]
    first = jnp.concatenate([jnp.ones((1,), bool), so[1:] != so[:-1]])
    return jnp.zeros((B,), bool).at[order].set(first & (so < C))


def _find_or_claim(
    key_hi_tab: jax.Array,
    key_lo_tab: jax.Array,
    s_hi: jax.Array,
    s_lo: jax.Array,
    alive: jax.Array,
    probe_rounds: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-sweep find-or-claim over unique keys (the store hot path).

    One probe sweep records, per row, the slot already holding its key (the
    full ``probe_rounds`` sequence is scanned so lookups stay correct in the
    presence of pruned slots) **and** a bitmask of empty slots along the
    sequence. A second `while_loop` then resolves insertions *batch-locally*:
    each round, every unplaced row proposes its next empty-at-snapshot slot,
    contenders for the same slot are resolved by sorting a single packed
    (slot, batch idx) key (first of each slot-run wins — O(B log B), never
    O(capacity), and deterministic-by-arrival; see ``_claim_winners``),
    and losers fall through to their next candidate bit. Both loops early-exit
    the moment every row is served, so the accumulate-heavy steady state costs
    a couple of probe rounds instead of 2 x ``probe_rounds`` full passes.

    Requires ``alive`` rows to carry *unique* keys (callers dedup first).
    Returns (key_hi_tab, key_lo_tab, slot, placed, n_dropped); ``slot`` is -1
    for rows that were not placed.
    """
    assert probe_rounds <= 32, "empty-slot bitmask is uint32"
    C = key_hi_tab.shape[0]
    B = s_hi.shape[0]
    h0 = probe_hash(s_hi, s_lo)

    # -- Sweep 1: find existing slots, record empty candidates as bits. --
    def find_cond(st):
        r, found, _ = st
        return (r < probe_rounds) & jnp.any(alive & (found < 0))

    def find_body(st):
        r, found, emp = st
        slot = _probe_slot_dyn(h0, r, C)
        t_hi = key_hi_tab[slot]
        t_lo = key_lo_tab[slot]
        hit = alive & (found < 0) & (t_hi == s_hi) & (t_lo == s_lo)
        found = jnp.where(hit, slot.astype(jnp.int32), found)
        empty = (t_hi == 0) & (t_lo == 0)
        bit = jnp.left_shift(jnp.uint32(1), r.astype(jnp.uint32))
        emp = emp | jnp.where(empty, bit, jnp.uint32(0))
        return r + 1, found, emp

    _, found_slot, emp_bits = jax.lax.while_loop(
        find_cond, find_body,
        (jnp.uint32(0), jnp.full((B,), -1, jnp.int32),
         jnp.zeros((B,), jnp.uint32)))

    placed = found_slot >= 0
    write_slot = found_slot

    # -- Sweep 2: claim rounds. Slots empty at snapshot time can only be
    # consumed (the table never loses keys mid-insert), so re-checking the
    # proposal against the *current* table keeps claims race-free. --
    def claim_cond(st):
        kh, kl, placed, _, emp = st
        return jnp.any(alive & ~placed & (emp != 0))

    def claim_body(st):
        kh, kl, placed, wslot, emp = st
        want = alive & ~placed & (emp != 0)
        low = emp & (~emp + jnp.uint32(1))                    # lowest candidate bit
        r = jax.lax.population_count(low - jnp.uint32(1))     # its round index
        slot = _probe_slot_dyn(h0, jnp.where(want, r, 0), C)
        still_empty = (kh[slot] == 0) & (kl[slot] == 0)
        contend = want & still_empty
        # batch-local conflict resolution: one packed (slot, idx) sort key,
        # first row of each slot-run wins (deterministic-by-arrival).
        won = _claim_winners(slot, contend, B, C)
        drop_slot = jnp.where(won, slot.astype(jnp.int32), C)
        kh = kh.at[drop_slot].set(s_hi, mode="drop")
        kl = kl.at[drop_slot].set(s_lo, mode="drop")
        wslot = jnp.where(won, slot.astype(jnp.int32), wslot)
        placed = placed | won
        # every examined candidate is consumed (won, lost, or stale)
        emp = jnp.where(want, emp & ~low, emp)
        return kh, kl, placed, wslot, emp

    key_hi_tab, key_lo_tab, placed, write_slot, _ = jax.lax.while_loop(
        claim_cond, claim_body,
        (key_hi_tab, key_lo_tab, placed, write_slot, emp_bits))

    dropped = jnp.sum((alive & ~placed).astype(jnp.int32))
    return key_hi_tab, key_lo_tab, write_slot, placed, dropped


def _dedup_sorted(key_hi, key_lo, valid):
    """Stable lexsort by (hi, lo); returns (perm, seg_id, rep_mask, run_start).

    rep_mask marks the LAST row of each equal-key run in sorted order, so
    SET lanes naturally take the final (batch-order latest) value. Invalid
    rows have key (0,0) and sort first; they form segment(s) that callers
    mask out via the key-!=0 check.
    """
    perm = jnp.lexsort((key_lo, key_hi))  # lexsort is stable
    s_hi, s_lo = key_hi[perm], key_lo[perm]
    prev_hi = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), s_hi[:-1]])
    prev_lo = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), s_lo[:-1]])
    is_new = (s_hi != prev_hi) | (s_lo != prev_lo)
    seg_id = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    nxt_new = jnp.concatenate([is_new[1:], jnp.ones((1,), bool)])
    rep_mask = nxt_new & ((s_hi != 0) | (s_lo != 0)) & valid[perm]
    return perm, seg_id, rep_mask


def _dedup_and_aggregate(key_hi, key_lo, updates, valid, mode_map):
    """Shared insert prologue: mask invalid rows to the empty key, dedup with
    a stable lexsort, land per-segment lane reductions on every row of the
    run. Returns (s_hi, s_lo, agg, alive) in dedup-sorted batch order; alive
    marks each unique key's representative row."""
    key_hi = jnp.where(valid, key_hi, 0).astype(jnp.uint32)
    key_lo = jnp.where(valid, key_lo, 0).astype(jnp.uint32)
    B = key_hi.shape[0]
    perm, seg_id, rep_mask = _dedup_sorted(key_hi, key_lo, valid)
    s_hi, s_lo = key_hi[perm], key_lo[perm]
    agg: Dict[str, jax.Array] = {}
    for name, upd in updates.items():
        upd_s = upd[perm]
        mode = mode_map[name]
        if mode == ADD:
            seg = jax.ops.segment_sum(upd_s, seg_id, num_segments=B)
            agg[name] = seg[seg_id]
        elif mode == MAX:
            seg = jax.ops.segment_max(upd_s, seg_id, num_segments=B)
            agg[name] = seg[seg_id]
        else:  # SET — representative row is the last of the run already.
            agg[name] = upd_s
    return s_hi, s_lo, agg, rep_mask


def _apply_lane_updates(lanes, agg, mode_map, ok, write_slot, C, rebase=None):
    """Shared insert epilogue: apply aggregated updates at write_slot
    (unique keys => unique slots; OOB sentinel C drops masked rows).

    ``rebase`` (lazy decay policy): name -> decayed-current-value [B] for
    ADD lanes that must be *rebased* on write — the slot's stored value is
    replaced by ``decayed_current + update`` instead of accumulated raw, so
    read-time decay from the refreshed ``last_tick`` stays exact.
    """
    safe = jnp.where(ok, write_slot, 0)
    drop = jnp.where(ok, write_slot, C)
    new_lanes = dict(lanes)
    for name, upd in agg.items():
        lane = new_lanes[name]
        mode = mode_map[name]
        if rebase is not None and name in rebase:
            new_lanes[name] = lane.at[drop].set(rebase[name] + upd, mode="drop")
        elif mode == ADD:
            zeros = jnp.zeros_like(upd)
            add = jnp.where(_bmask(ok, upd), upd, zeros)
            new_lanes[name] = lane.at[safe].add(add)
        elif mode == MAX:
            cur = lane[safe]
            new_lanes[name] = lane.at[drop].set(jnp.maximum(cur, upd), mode="drop")
        else:  # SET
            new_lanes[name] = lane.at[drop].set(upd, mode="drop")
    return new_lanes


@partial(jax.jit, static_argnames=("modes", "probe_rounds", "decay_cfg",
                                   "decay_lanes", "tick_lane"))
def insert_accumulate(
    table: HashTable,
    key_hi: jax.Array,
    key_lo: jax.Array,
    updates: Dict[str, jax.Array],
    valid: jax.Array,
    *,
    modes: Tuple[Tuple[str, str], ...],
    probe_rounds: int = 16,
    decay_cfg=None,
    decay_lanes: Tuple[str, ...] = ("weight",),
    tick_lane: str = "last_tick",
    now=None,
) -> HashTable:
    """Batched insert-or-accumulate of (key -> lane updates).

    modes: tuple of (lane_name, ADD|SET|MAX) — a hashable static spec.

    Lazy decay policy (``decay_cfg`` + ``now``): ``decay_lanes`` are rebased
    on write — the stored value is decayed from the slot's ``tick_lane`` to
    ``now`` *before* the update is added, and the caller's SET of the tick
    lane to ``now`` re-anchors subsequent read-time decay. Without the
    rebase, refreshing ``last_tick`` would silently un-decay the elapsed
    gap. Exact for exponential decay (the factor is memoryless).
    """
    C = table.capacity
    mode_map = dict(modes)
    s_hi, s_lo, agg, alive = _dedup_and_aggregate(
        key_hi, key_lo, updates, valid, mode_map)

    key_hi_tab, key_lo_tab, write_slot, placed, dropped = _find_or_claim(
        table.key_hi, table.key_lo, s_hi, s_lo, alive, probe_rounds)

    ok = placed & alive
    rebase = None
    if decay_cfg is not None:
        safe = jnp.where(ok, write_slot, 0)
        f = decay_cfg.factor(jnp.maximum(now - table.lanes[tick_lane][safe], 0))
        rebase = {name: table.lanes[name][safe] * f for name in decay_lanes
                  if mode_map.get(name) == ADD}

    new_lanes = _apply_lane_updates(table.lanes, agg, mode_map,
                                    ok, write_slot, C, rebase=rebase)
    return HashTable(key_hi_tab, key_lo_tab, new_lanes, table.n_dropped + dropped)


@partial(jax.jit, static_argnames=("modes", "probe_rounds"))
def insert_accumulate_twopass(
    table: HashTable,
    key_hi: jax.Array,
    key_lo: jax.Array,
    updates: Dict[str, jax.Array],
    valid: jax.Array,
    *,
    modes: Tuple[Tuple[str, str], ...],
    probe_rounds: int = 16,
) -> HashTable:
    """Pre-fusion reference probe core (two unrolled probe passes, [C]-sized
    scatter-max claim race), sharing the dedup/aggregate prologue and
    lane-apply epilogue with ``insert_accumulate`` so parity tests compare
    ONLY the probe strategies. Kept for parity tests and before/after
    benchmarking; not used by the engine.
    """
    C = table.capacity
    mode_map = dict(modes)
    s_hi, s_lo, agg, alive = _dedup_and_aggregate(
        key_hi, key_lo, updates, valid, mode_map)
    B = s_hi.shape[0]
    h0 = probe_hash(s_hi, s_lo)

    found_slot = jnp.full((B,), -1, jnp.int32)
    for r in range(probe_rounds):
        slot = _probe_slot(h0, r, C)
        t_hi = table.key_hi[slot]
        t_lo = table.key_lo[slot]
        hit = alive & (found_slot < 0) & (t_hi == s_hi) & (t_lo == s_lo)
        found_slot = jnp.where(hit, slot.astype(jnp.int32), found_slot)

    key_hi_tab, key_lo_tab = table.key_hi, table.key_lo
    placed = found_slot >= 0
    write_slot = found_slot

    for r in range(probe_rounds):
        want = alive & ~placed
        slot = _probe_slot(h0, r, C)
        empty = (key_hi_tab[slot] == 0) & (key_lo_tab[slot] == 0)
        contend = want & empty
        claim = jnp.full((C,), -1, jnp.int32)
        claim = claim.at[slot].max(jnp.where(contend, jnp.arange(B, dtype=jnp.int32), -1))
        won = contend & (claim[slot] == jnp.arange(B, dtype=jnp.int32))
        drop_slot = jnp.where(won, slot.astype(jnp.int32), C)
        key_hi_tab = key_hi_tab.at[drop_slot].set(s_hi, mode="drop")
        key_lo_tab = key_lo_tab.at[drop_slot].set(s_lo, mode="drop")
        write_slot = jnp.where(won, slot.astype(jnp.int32), write_slot)
        placed = placed | won

    dropped = jnp.sum((alive & ~placed).astype(jnp.int32))

    new_lanes = _apply_lane_updates(table.lanes, agg, mode_map,
                                    placed & alive, write_slot, C)
    return HashTable(key_hi_tab, key_lo_tab, new_lanes, table.n_dropped + dropped)


def _bmask(mask: jax.Array, ref: jax.Array) -> jax.Array:
    """Broadcast a [B] mask against a [B, ...] lane update."""
    return mask.reshape(mask.shape + (1,) * (ref.ndim - 1))


# A lookup batch of B rows hands its last unresolved rows to a tail arena of
# B // TAIL_SHARE rows, once that many or fewer are left; batches whose
# arena would hold fewer than TAIL_MIN_ROWS rows probe at full width only.
TAIL_SHARE = 64
TAIL_MIN_ROWS = 1024


def _probe_body(table: HashTable, h0, key_hi, key_lo, live):
    """One probe round of :func:`lookup` over the rows in ``live``."""
    C = table.capacity

    def body(st):
        r, found = st
        slot = _probe_slot_dyn(h0, r, C)
        hit = live & (found < 0) \
            & (table.key_hi[slot] == key_hi) & (table.key_lo[slot] == key_lo)
        return r + 1, jnp.where(hit, slot.astype(jnp.int32), found)
    return body


def _probe_two_phase(table: HashTable, h0, key_hi, key_lo, nonzero,
                     probe_rounds: int):
    """The probe rounds of :func:`lookup` for a large batch.

    Full-width rounds run while more than ``A = B // TAIL_SHARE`` nonzero
    rows are unresolved. The rows still unresolved then are compacted into
    an A-row arena (prefix sum, one scatter), which runs the remaining
    rounds with the same round index, and its found slots are scattered
    back. Every row sees the same probe sequence as in one full-width loop,
    so the result is the same. Returns ``(found_slot, full_rounds,
    tail_rows)``.
    """
    B = key_hi.shape[0]
    A = B // TAIL_SHARE
    body = _probe_body(table, h0, key_hi, key_lo, nonzero)

    def cond(st):
        r, found = st
        return (r < probe_rounds) & (
            jnp.sum((nonzero & (found < 0)).astype(jnp.int32)) > A)

    r, found_slot = jax.lax.while_loop(
        cond, body, (jnp.uint32(0), jnp.full((B,), -1, jnp.int32)))
    left = nonzero & (found_slot < 0)
    n_left = jnp.sum(left.astype(jnp.int32))
    tail = (n_left > 0) & (r < probe_rounds)   # then n_left <= A

    def run_tail(found_slot):
        pos = jnp.cumsum(left.astype(jnp.int32)) - 1
        idx = jnp.full((A,), B, jnp.int32).at[jnp.where(left, pos, A)].set(
            jnp.arange(B, dtype=jnp.int32), mode="drop")
        in_arena = idx < B
        safe = jnp.where(in_arena, idx, 0)

        def tail_cond(st):
            r, found = st
            return (r < probe_rounds) & jnp.any(in_arena & (found < 0))

        _, found_a = jax.lax.while_loop(
            tail_cond,
            _probe_body(table, h0[safe], key_hi[safe], key_lo[safe],
                        in_arena),
            (r, jnp.full((A,), -1, jnp.int32)))
        return found_slot.at[idx].set(found_a, mode="drop")

    found_slot = jax.lax.cond(tail, run_tail, lambda f: f, found_slot)
    return found_slot, r, jnp.where(tail, n_left, 0)


@partial(jax.jit, static_argnames=("probe_rounds", "decay_cfg", "decay_lanes",
                                   "tick_lane", "with_stats"))
def lookup(
    table: HashTable,
    key_hi: jax.Array,
    key_lo: jax.Array,
    *,
    probe_rounds: int = 16,
    decay_cfg=None,
    decay_lanes: Tuple[str, ...] = ("weight",),
    tick_lane: str = "last_tick",
    now=None,
    with_stats: bool = False,
):
    """Batched lookup. Returns (lanes_at_key, found_mask, slot), and with
    ``with_stats`` also ``i32[2]``: the probe rounds run over the whole
    batch, and the rows handed to the tail arena (see
    :func:`_probe_two_phase`; batches of fewer than
    ``TAIL_SHARE * TAIL_MIN_ROWS`` rows never hand any).

    Lazy decay policy (``decay_cfg`` + ``now``): the returned ``decay_lanes``
    are the *read-time decayed view* ``w * factor(now - last_tick)`` — the
    store itself is untouched; maintenance is amortized into reads.
    """
    key_hi = jnp.asarray(key_hi, jnp.uint32)
    key_lo = jnp.asarray(key_lo, jnp.uint32)
    h0 = probe_hash(key_hi, key_lo)
    B = key_hi.shape[0]
    nonzero = (key_hi != 0) | (key_lo != 0)

    if B // TAIL_SHARE >= TAIL_MIN_ROWS:
        found_slot, r, tail_rows = _probe_two_phase(
            table, h0, key_hi, key_lo, nonzero, probe_rounds)
    else:
        # while_loop with early exit: most batches resolve in 1-2 rounds
        # (only genuinely-absent nonzero keys force the full prune-safe
        # scan).
        def cond(st):
            r, found = st
            return (r < probe_rounds) & jnp.any(nonzero & (found < 0))

        r, found_slot = jax.lax.while_loop(
            cond, _probe_body(table, h0, key_hi, key_lo, nonzero),
            (jnp.uint32(0), jnp.full((B,), -1, jnp.int32)))
        tail_rows = 0
    found = found_slot >= 0
    safe = jnp.where(found, found_slot, 0)
    f = None
    if decay_cfg is not None:
        f = decay_cfg.factor(jnp.maximum(now - table.lanes[tick_lane][safe], 0))
    out = {}
    for name, lane in table.lanes.items():
        v = lane[safe]
        if f is not None and name in decay_lanes:
            v = v * f
        out[name] = jnp.where(_bmask(found, v), v, jnp.zeros_like(v))
    if with_stats:
        stats = jnp.stack([r.astype(jnp.int32),
                           jnp.asarray(tail_rows, jnp.int32)])
        return out, found, found_slot, stats
    return out, found, found_slot


def diff_leading_rows(prev: np.ndarray, new: np.ndarray) -> np.ndarray:
    """Dirty-slot extraction for incremental (delta) snapshots.

    Returns the leading indices (slots) where ``new`` differs from ``prev``
    — the rows a delta snapshot must record. Under the lazy decay policy
    this set is exactly what the store mutation paths touched since the
    base snapshot: slots written by ``insert_accumulate`` /
    ``region_insert_accumulate`` (rebase-on-write refreshes ``last_tick``,
    so a touched slot always differs) plus slots the prune sweeps reclaimed
    or compacted. Computing it by content-compare instead of threading
    dirty masks through every jitted op keeps it exact under *every*
    policy/layout combination (eager sweeps rewrite all live weights — the
    delta correctly grows to match) and keeps ``EngineState`` free of
    snapshot-cadence-dependent lanes that would break the bit-exact
    crash→restore→replay property. NaN-unsafe compares only ever *add*
    rows (NaN != NaN), never lose one.
    """
    assert prev.shape == new.shape and prev.dtype == new.dtype
    neq = prev != new
    if neq.ndim > 1:
        neq = neq.reshape(neq.shape[0], -1).any(axis=1)
    return np.nonzero(neq)[0].astype(np.int64)


def apply_row_delta(base: np.ndarray, idx: np.ndarray,
                    rows: np.ndarray) -> np.ndarray:
    """Scatter a delta's changed rows back onto the base snapshot's array
    (in place when writable — npz loads are). Inverse of
    :func:`diff_leading_rows` given the base it was diffed against."""
    if not base.flags.writeable:
        base = base.copy()
    base[idx] = rows
    return base


def export_live(table: HashTable) -> Dict[str, np.ndarray]:
    """Host-side export of live entries (for persistence / suggestion build)."""
    mask = np.asarray(table.live_mask)
    out = {
        "key_hi": np.asarray(table.key_hi)[mask],
        "key_lo": np.asarray(table.key_lo)[mask],
    }
    for name, lane in table.lanes.items():
        out[name] = np.asarray(lane)[mask]
    return out


# ---------------------------------------------------------------------------
# Sessions store: per-session sliding window ring buffers (paper §4.2).
# ---------------------------------------------------------------------------

class SessionTable(NamedTuple):
    key_hi: jax.Array    # u32[S]
    key_lo: jax.Array    # u32[S]
    ring_hi: jax.Array   # u32[S, W] — recent query fingerprints
    ring_lo: jax.Array   # u32[S, W]
    ring_src: jax.Array  # i32[S, W] — interaction source code per entry
    cursor: jax.Array    # i32[S] — next write position
    filled: jax.Array    # i32[S] — number of valid ring entries (<= W)
    last_tick: jax.Array  # i32[S]
    n_dropped: jax.Array  # i32[]

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]

    @property
    def window(self) -> int:
        return self.ring_hi.shape[1]


def make_session_table(capacity: int, window: int) -> SessionTable:
    assert capacity & (capacity - 1) == 0
    return SessionTable(
        key_hi=jnp.zeros((capacity,), jnp.uint32),
        key_lo=jnp.zeros((capacity,), jnp.uint32),
        ring_hi=jnp.zeros((capacity, window), jnp.uint32),
        ring_lo=jnp.zeros((capacity, window), jnp.uint32),
        ring_src=jnp.zeros((capacity, window), jnp.int32),
        cursor=jnp.zeros((capacity,), jnp.int32),
        filled=jnp.zeros((capacity,), jnp.int32),
        last_tick=jnp.zeros((capacity,), jnp.int32),
        n_dropped=jnp.zeros((), jnp.int32),
    )


class PairBatch(NamedTuple):
    """Emitted (predecessor -> new query) cooccurrence pairs, [B*W] flat."""
    src_hi: jax.Array
    src_lo: jax.Array
    src_code: jax.Array
    dst_hi: jax.Array
    dst_lo: jax.Array
    dst_code: jax.Array
    valid: jax.Array


@partial(jax.jit, static_argnames=("probe_rounds",))
def update_sessions(
    table: SessionTable,
    sess_hi: jax.Array,
    sess_lo: jax.Array,
    q_hi: jax.Array,
    q_lo: jax.Array,
    src_code: jax.Array,
    tick: jax.Array,
    valid: jax.Array,
    *,
    probe_rounds: int = 16,
) -> Tuple[SessionTable, PairBatch]:
    """Append a micro-batch of queries to their sessions; emit pairs.

    Exact order semantics: events are processed in batch order *per session*
    (stable sort groups a session's events while preserving arrival order);
    a new query pairs with the W most recent predecessors, drawing first from
    earlier same-batch events, then from the pre-batch ring window.
    """
    S, W = table.capacity, table.window
    B = q_hi.shape[0]
    sess_hi = jnp.where(valid, sess_hi, 0).astype(jnp.uint32)
    sess_lo = jnp.where(valid, sess_lo, 0).astype(jnp.uint32)

    perm = jnp.lexsort((sess_lo, sess_hi))  # stable
    e_shi, e_slo = sess_hi[perm], sess_lo[perm]
    e_qhi, e_qlo = q_hi[perm], q_lo[perm]
    e_src = src_code[perm]
    e_valid = valid[perm] & ((e_shi != 0) | (e_slo != 0))

    prev_hi = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), e_shi[:-1]])
    prev_lo = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), e_slo[:-1]])
    is_new_run = (e_shi != prev_hi) | (e_slo != prev_lo)
    seg_id = jnp.cumsum(is_new_run.astype(jnp.int32)) - 1
    pos_in_run = jnp.arange(B, dtype=jnp.int32) - jax.ops.segment_min(
        jnp.arange(B, dtype=jnp.int32), seg_id, num_segments=B)[seg_id]
    run_len = jax.ops.segment_sum(jnp.ones((B,), jnp.int32), seg_id, num_segments=B)[seg_id]

    # ---- find/create the session row: single fused find-or-claim sweep
    # over the run representatives (unique session keys). ----
    rep = is_new_run & e_valid
    key_hi_tab, key_lo_tab, row, placed, dropped = _find_or_claim(
        table.key_hi, table.key_lo, e_shi, e_slo, rep, probe_rounds)
    # Broadcast the representative's row to every event in its run.
    rep_row = jax.ops.segment_max(jnp.where(rep, row, -1), seg_id, num_segments=B)
    row = rep_row[seg_id]
    e_ok = e_valid & (row >= 0)
    safe_row = jnp.where(e_ok, row, 0)

    pre_cursor = table.cursor[safe_row]
    pre_filled = table.filled[safe_row]

    # ---- emit pairs: d-th most recent predecessor, d = 1..W. ----
    n_intra = jnp.minimum(pos_in_run, W)
    pair_src_hi = jnp.zeros((B, W), jnp.uint32)
    pair_src_lo = jnp.zeros((B, W), jnp.uint32)
    pair_src_code = jnp.zeros((B, W), jnp.int32)
    pair_ok = jnp.zeros((B, W), bool)
    idx = jnp.arange(B, dtype=jnp.int32)
    for d in range(1, W + 1):
        take_intra = (d <= n_intra)
        j = jnp.maximum(idx - d, 0)
        intra_hi, intra_lo, intra_src = e_qhi[j], e_qlo[j], e_src[j]
        age = d - 1 - n_intra  # >= 0 when not intra
        ring_ok = (~take_intra) & (age < jnp.minimum(W - n_intra, pre_filled))
        ring_pos = jnp.mod(pre_cursor - 1 - age, W)
        r_hi = table.ring_hi[safe_row, jnp.where(ring_ok, ring_pos, 0)]
        r_lo = table.ring_lo[safe_row, jnp.where(ring_ok, ring_pos, 0)]
        r_src = table.ring_src[safe_row, jnp.where(ring_ok, ring_pos, 0)]
        s_hi = jnp.where(take_intra, intra_hi, r_hi)
        s_lo = jnp.where(take_intra, intra_lo, r_lo)
        s_sc = jnp.where(take_intra, intra_src, r_src)
        ok = e_ok & (take_intra | ring_ok) & ((s_hi != 0) | (s_lo != 0))
        # drop self-pairs (identical consecutive queries)
        ok = ok & ~((s_hi == e_qhi) & (s_lo == e_qlo))
        pair_src_hi = pair_src_hi.at[:, d - 1].set(s_hi)
        pair_src_lo = pair_src_lo.at[:, d - 1].set(s_lo)
        pair_src_code = pair_src_code.at[:, d - 1].set(s_sc)
        pair_ok = pair_ok.at[:, d - 1].set(ok)

    # ---- write the last min(W, run_len) events of each run into the ring. ----
    should_write = e_ok & (pos_in_run >= run_len - W)
    wpos = jnp.mod(pre_cursor + pos_in_run, W)
    w_row = jnp.where(should_write, safe_row, S)  # OOB => dropped
    ring_hi = table.ring_hi.at[w_row, wpos].set(e_qhi, mode="drop")
    ring_lo = table.ring_lo.at[w_row, wpos].set(e_qlo, mode="drop")
    ring_src = table.ring_src.at[w_row, wpos].set(e_src, mode="drop")

    # cursor/filled advance once per run (apply at the run's last event).
    is_last = jnp.concatenate([is_new_run[1:], jnp.ones((1,), bool)])
    adv = e_ok & is_last
    a_row = jnp.where(adv, safe_row, S)
    new_cursor = jnp.mod(pre_cursor + run_len, W)
    new_filled = jnp.minimum(pre_filled + run_len, W)
    cursor = table.cursor.at[a_row].set(new_cursor, mode="drop")
    filled = table.filled.at[a_row].set(new_filled, mode="drop")
    last_tick = table.last_tick.at[a_row].set(
        jnp.full((B,), tick, jnp.int32), mode="drop")

    new_table = SessionTable(key_hi_tab, key_lo_tab, ring_hi, ring_lo, ring_src,
                             cursor, filled, last_tick, table.n_dropped + dropped)

    pairs = PairBatch(
        src_hi=pair_src_hi.reshape(-1),
        src_lo=pair_src_lo.reshape(-1),
        src_code=pair_src_code.reshape(-1),
        dst_hi=jnp.broadcast_to(e_qhi[:, None], (B, W)).reshape(-1),
        dst_lo=jnp.broadcast_to(e_qlo[:, None], (B, W)).reshape(-1),
        dst_code=jnp.broadcast_to(e_src[:, None], (B, W)).reshape(-1),
        valid=pair_ok.reshape(-1),
    )
    return new_table, pairs


@jax.jit
def evict_sessions(table: SessionTable, tick: jax.Array, ttl: int) -> SessionTable:
    """Prune sessions with no recent activity (paper's decay/prune cycle)."""
    live = (table.key_hi != 0) | (table.key_lo != 0)
    stale = live & ((tick - table.last_tick) > ttl)
    keep = ~stale
    return table._replace(
        key_hi=jnp.where(keep, table.key_hi, 0),
        key_lo=jnp.where(keep, table.key_lo, 0),
        cursor=jnp.where(keep, table.cursor, 0),
        filled=jnp.where(keep, table.filled, 0),
    )


# ---------------------------------------------------------------------------
# Source-major region layout for the cooccurrence store.
#
# See the module docstring for the three invariants (region id = source
# qstore slot via the chain directory, spill chain order, freelist
# lifecycle). The per-slot key is the *destination* fingerprint only — the
# region already implies the source, so the four src/dst endpoint lanes of
# the hash layout collapse into the key lanes (≈45% less state per pair).
# ---------------------------------------------------------------------------

class RegionTable(NamedTuple):
    """Source-major cooccurrence store: ``n_regions`` regions of ``width``
    slots; regions are pool-allocated to sources, chained through a
    directory indexed by the source's qstore slot."""
    key_hi: jax.Array        # u32[C] — dst fingerprint; (0,0) == empty slot
    key_lo: jax.Array        # u32[C]
    lanes: Dict[str, jax.Array]   # each [C] (1-D only)
    chain_region: jax.Array  # i32[Q, MC] — region ids, -1 = none (prefix)
    chain_hi: jax.Array      # u32[Q] — source fp owning the chain at slot q
    chain_lo: jax.Array      # u32[Q]
    region_fill: jax.Array   # i32[R] — live pairs, packed at [0, fill)
    region_owner: jax.Array  # i32[R] — owning qstore slot, -1 = free
    n_dropped: jax.Array     # i32[] — src-missing / chain-full / pool-empty

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]

    @property
    def n_regions(self) -> int:
        return self.region_fill.shape[0]

    @property
    def width(self) -> int:
        return self.capacity // self.n_regions

    @property
    def max_chain(self) -> int:
        return self.chain_region.shape[1]

    @property
    def dir_slots(self) -> int:
        return self.chain_region.shape[0]

    @property
    def live_mask(self) -> jax.Array:
        return (self.key_hi != 0) | (self.key_lo != 0)

    def live_count(self) -> jax.Array:
        return jnp.sum(self.live_mask.astype(jnp.int32))

    def free_regions(self) -> jax.Array:
        """Freelist pressure: regions available for allocation."""
        return jnp.sum((self.region_owner < 0).astype(jnp.int32))


def make_region_table(capacity: int, region_width: int, dir_slots: int,
                      max_chain: int, lane_specs: Dict[str, Any]
                      ) -> RegionTable:
    """``dir_slots`` must equal the qstore capacity (region id = qstore
    slot); ``capacity = n_regions * region_width``."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    assert region_width & (region_width - 1) == 0 and region_width > 0
    assert capacity % region_width == 0 and capacity >= region_width
    assert max_chain >= 1
    n_regions = capacity // region_width
    lanes = {}
    for name, spec in lane_specs.items():
        assert not isinstance(spec, tuple), "region lanes must be 1-D"
        lanes[name] = jnp.zeros((capacity,), dtype=spec)
    return RegionTable(
        key_hi=jnp.zeros((capacity,), jnp.uint32),
        key_lo=jnp.zeros((capacity,), jnp.uint32),
        lanes=lanes,
        chain_region=jnp.full((dir_slots, max_chain), -1, jnp.int32),
        chain_hi=jnp.zeros((dir_slots,), jnp.uint32),
        chain_lo=jnp.zeros((dir_slots,), jnp.uint32),
        region_fill=jnp.zeros((n_regions,), jnp.int32),
        region_owner=jnp.full((n_regions,), -1, jnp.int32),
        n_dropped=jnp.zeros((), jnp.int32),
    )


def region_chain_state(table: RegionTable, qstore: HashTable
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """THE chain-validity invariant, shared by ranking and the sweeps: a
    directory row is live iff it has a chain head AND its recorded
    fingerprint still owns that qstore slot (slot reuse / source pruning
    otherwise orphans the chain). Returns

      * ``row_valid`` bool[Q]  — directory rows with a live, owned chain,
      * ``ent_ok``   bool[Q, MC] — live chain entries,
      * ``referenced`` bool[R] — regions reachable from a live chain.
    """
    assert table.dir_slots == qstore.capacity
    R = table.n_regions
    row_valid = (table.chain_region[:, 0] >= 0) \
        & (qstore.key_hi == table.chain_hi) \
        & (qstore.key_lo == table.chain_lo) \
        & ((qstore.key_hi != 0) | (qstore.key_lo != 0))
    ent = table.chain_region
    ent_ok = (ent >= 0) & row_valid[:, None]
    referenced = jnp.zeros((R,), bool).at[
        jnp.where(ent_ok, ent, R).reshape(-1)].set(True, mode="drop")
    return row_valid, ent_ok, referenced


def _group_ranks(slot: jax.Array, mask: jax.Array, Q: int) -> jax.Array:
    """Rank (0-based) of each masked row within its slot group, in row
    order — the claim-side analogue of ``_claim_winners``: one packed
    (slot, idx) u32 sort when it fits 31 bits, (idx, slot) lexsort
    otherwise. Unmasked rows get garbage ranks (callers mask)."""
    B = slot.shape[0]
    idx = jnp.arange(B, dtype=jnp.uint32)
    bits_b = max((B - 1).bit_length(), 1)
    if (Q - 1).bit_length() + bits_b <= 31:
        sent = jnp.uint32(0xFFFFFFFF)
        packed = jnp.where(mask,
                           (slot.astype(jnp.uint32) << jnp.uint32(bits_b))
                           | idx, sent)
        order = jnp.argsort(packed)
        pslot = packed[order] >> jnp.uint32(bits_b)
    else:
        skey = jnp.where(mask, slot.astype(jnp.int32), Q)
        order = jnp.lexsort((idx.astype(jnp.int32), skey))
        pslot = skey[order].astype(jnp.uint32)
    is_new = jnp.concatenate([jnp.ones((1,), bool), pslot[1:] != pslot[:-1]])
    ar = jnp.arange(B, dtype=jnp.int32)
    rank_sorted = ar - jax.lax.cummax(jnp.where(is_new, ar, 0))
    return jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted)


def _chain_find_jnp(khi_r, klo_r, regs, dst_hi, dst_lo, active):
    """Early-exit chain scan: depth d gathers each pair's region tile
    ``[B, W]`` (ONE contiguous W-slot row per pair — the locality the
    layout buys) and matches the dst key. Most chains are one region deep,
    so the steady state costs a single round."""
    B, MC = regs.shape
    W = khi_r.shape[1]

    def cond(st):
        d, found = st
        col = jax.lax.dynamic_slice_in_dim(regs, jnp.minimum(d, MC - 1), 1,
                                           axis=1)[:, 0]
        return (d < MC) & jnp.any(active & (found < 0) & (col >= 0))

    def body(st):
        d, found = st
        col = jax.lax.dynamic_slice_in_dim(regs, d, 1, axis=1)[:, 0]
        want = active & (found < 0) & (col >= 0)
        reg_safe = jnp.where(col >= 0, col, 0)
        m = want[:, None] & (khi_r[reg_safe] == dst_hi[:, None]) \
            & (klo_r[reg_safe] == dst_lo[:, None])
        pos = jnp.argmax(m, axis=1).astype(jnp.int32)
        hit = jnp.any(m, axis=1)
        found = jnp.where(hit, reg_safe * W + pos, found)
        return d + 1, found

    _, found = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.full((B,), -1, jnp.int32)))
    return found


@partial(jax.jit, static_argnames=("modes", "probe_rounds", "decay_cfg",
                                   "decay_lanes", "tick_lane", "use_kernel",
                                   "plan"))
def region_insert_accumulate(
    table: RegionTable,
    qstore: HashTable,
    src_hi: jax.Array,
    src_lo: jax.Array,
    dst_hi: jax.Array,
    dst_lo: jax.Array,
    updates: Dict[str, jax.Array],
    valid: jax.Array,
    *,
    modes: Tuple[Tuple[str, str], ...],
    probe_rounds: int = 16,
    decay_cfg=None,
    decay_lanes: Tuple[str, ...] = ("weight",),
    tick_lane: str = "last_tick",
    now=None,
    use_kernel: Optional[bool] = None,
    plan=None,
) -> RegionTable:
    """Batched insert-or-accumulate of (src -> dst) pairs, region layout.

    The source's qstore slot names its chain directly (no pair-key
    probing): finds scan the chain's region tiles, claims *append* at each
    region's fill tail in chain order, new regions come off the freelist in
    ascending-id order. Accumulation semantics (dedup by the combined pair
    fingerprint, ADD/SET/MAX lane reductions, lazy-decay rebase-on-write)
    match :func:`insert_accumulate` exactly. Drops — source absent from the
    qstore, spill chain exhausted, region pool exhausted — are counted in
    ``n_dropped``.
    """
    C, R, W, MC = (table.capacity, table.n_regions, table.width,
                   table.max_chain)
    Q = table.dir_slots
    assert Q == qstore.capacity, "directory must be indexed by qstore slot"
    mode_map = dict(modes)
    B = src_hi.shape[0]

    # -- dedup by the combined pair fp (same grouping as the hash layout);
    # src/dst ride along as SET lanes so representatives carry them. --
    p_hi, p_lo = combine_fp_device(src_hi, src_lo, dst_hi, dst_lo)
    full_updates = dict(updates)
    full_updates.update({"_src_hi": src_hi, "_src_lo": src_lo,
                         "_dst_hi": dst_hi, "_dst_lo": dst_lo})
    full_modes = dict(mode_map)
    full_modes.update({"_src_hi": SET, "_src_lo": SET,
                       "_dst_hi": SET, "_dst_lo": SET})
    s_hi, s_lo, agg, alive = _dedup_and_aggregate(
        p_hi, p_lo, full_updates, valid, full_modes)
    a_src_hi = agg.pop("_src_hi").astype(jnp.uint32)
    a_src_lo = agg.pop("_src_lo").astype(jnp.uint32)
    a_dst_hi = agg.pop("_dst_hi").astype(jnp.uint32)
    a_dst_lo = agg.pop("_dst_lo").astype(jnp.uint32)

    # -- the source's qstore slot IS the region chain id. --
    _, src_found, qslot = lookup(qstore, a_src_hi, a_src_lo,
                                 probe_rounds=probe_rounds)
    alive2 = alive & src_found
    n_src_miss = jnp.sum((alive & ~src_found).astype(jnp.int32))
    qslot_safe = jnp.where(alive2, qslot, 0)

    chain_ok = alive2 & (table.chain_region[qslot_safe, 0] >= 0) \
        & (table.chain_hi[qslot_safe] == a_src_hi) \
        & (table.chain_lo[qslot_safe] == a_src_lo)
    regs = jnp.where(chain_ok[:, None], table.chain_region[qslot_safe], -1)

    khi_r = table.key_hi.reshape(R, W)
    klo_r = table.key_lo.reshape(R, W)
    # kernel-vs-jnp for the chain find: legacy bool wins, else the tuned
    # plan (``core/plan.TunedPlan``), else the jnp reference. Both paths
    # are bit-exact, so the choice is pure dispatch.
    if use_kernel is None:
        use_kernel = plan.uses_kernel("chain_find") if plan is not None \
            else False
    if use_kernel:
        from ..kernels import ops as kops
        found = kops.chain_find(khi_r, klo_r, regs, a_dst_hi, a_dst_lo,
                                alive2)
    else:
        found = _chain_find_jnp(khi_r, klo_r, regs, a_dst_hi, a_dst_lo,
                                alive2)

    # -- claim: rank new pairs within their source, map ranks onto the
    # chain's free tail space (earlier regions' tails refill first). --
    new = alive2 & (found < 0)
    rank = _group_ranks(qslot_safe, new, Q)
    f_d = jnp.where(regs >= 0,
                    table.region_fill[jnp.clip(regs, 0, R - 1)], 0)
    avail = jnp.int32(W) - f_d            # unallocated depth: W free
    cumavail = jnp.cumsum(avail, axis=1)
    prev_cum = cumavail - avail
    in_d = new[:, None] & (rank[:, None] >= prev_cum) \
        & (rank[:, None] < cumavail)
    d_star = jnp.argmax(in_d, axis=1).astype(jnp.int32)
    has_room = jnp.any(in_d, axis=1)
    take1 = lambda a: jnp.take_along_axis(a, d_star[:, None], axis=1)[:, 0]
    pos = rank - take1(prev_cum) + take1(f_d)
    reg_at = take1(regs)
    n_chain_full = jnp.sum((new & ~has_room).astype(jnp.int32))

    # allocation: one representative per needed (slot, depth), assigned
    # free regions in ascending region-id order, deterministically.
    need_alloc = new & has_room & (reg_at < 0)
    rep = need_alloc & (pos == 0)
    BIG = jnp.int32(np.iinfo(np.int32).max)
    okey = jnp.where(rep, qslot_safe * MC + d_star, BIG)
    order = jnp.argsort(okey)
    t = jnp.zeros((B,), jnp.int32).at[order].set(
        jnp.where(okey[order] < BIG, jnp.arange(B, dtype=jnp.int32), B))
    free = table.region_owner < 0
    n_free = jnp.sum(free.astype(jnp.int32))
    frank = jnp.cumsum(free.astype(jnp.int32)) - 1
    rank2region = jnp.full((R,), -1, jnp.int32).at[
        jnp.where(free, frank, R)].set(jnp.arange(R, dtype=jnp.int32),
                                       mode="drop")
    alloc_region = jnp.where(rep & (t < n_free),
                             rank2region[jnp.clip(t, 0, R - 1)], -1)
    success_rep = rep & (alloc_region >= 0)

    # directory writes: stale/new rows reset wholesale (the previous
    # owner's chain is orphaned; the prune sweep reclaims it), then the
    # allocated entries land, then the owning fp is stamped.
    row_reset = new & ~chain_ok
    cr = table.chain_region.at[jnp.where(row_reset, qslot_safe, Q)].set(
        jnp.full((B, MC), -1, jnp.int32), mode="drop")
    cr = cr.at[jnp.where(success_rep, qslot_safe, Q), d_star].set(
        alloc_region, mode="drop")
    ch_hi = table.chain_hi.at[jnp.where(row_reset, qslot_safe, Q)].set(
        a_src_hi, mode="drop")
    ch_lo = table.chain_lo.at[jnp.where(row_reset, qslot_safe, Q)].set(
        a_src_lo, mode="drop")
    owner = table.region_owner.at[
        jnp.where(success_rep, alloc_region, R)].set(qslot_safe, mode="drop")

    # final placement (re-read the directory: covers freshly allocated
    # regions AND pool-exhaustion failures in one gather).
    reg_final = jnp.where(reg_at >= 0, reg_at, cr[qslot_safe, d_star])
    placed_new = new & has_room & (reg_final >= 0)
    n_pool_full = jnp.sum(
        (new & has_room & (reg_final < 0)).astype(jnp.int32))
    gslot = reg_final * W + pos

    key_hi = table.key_hi.at[jnp.where(placed_new, gslot, C)].set(
        a_dst_hi, mode="drop")
    key_lo = table.key_lo.at[jnp.where(placed_new, gslot, C)].set(
        a_dst_lo, mode="drop")
    fill = table.region_fill.at[jnp.where(placed_new, reg_final, R)].add(
        1, mode="drop")

    write_slot = jnp.where(found >= 0, found,
                           jnp.where(placed_new, gslot, -1))
    ok = alive2 & (write_slot >= 0)
    rebase = None
    if decay_cfg is not None:
        safe = jnp.where(ok, write_slot, 0)
        f = decay_cfg.factor(
            jnp.maximum(now - table.lanes[tick_lane][safe], 0))
        rebase = {name: table.lanes[name][safe] * f for name in decay_lanes
                  if mode_map.get(name) == ADD}
    new_lanes = _apply_lane_updates(table.lanes, agg, mode_map, ok,
                                    write_slot, C, rebase=rebase)
    n_drop = n_src_miss + n_chain_full + n_pool_full
    return RegionTable(key_hi, key_lo, new_lanes, cr, ch_hi, ch_lo, fill,
                       owner, table.n_dropped + n_drop)


@partial(jax.jit, static_argnames=("probe_rounds", "decay_cfg",
                                   "decay_lanes", "tick_lane"))
def region_lookup(
    table: RegionTable,
    qstore: HashTable,
    src_hi: jax.Array,
    src_lo: jax.Array,
    dst_hi: jax.Array,
    dst_lo: jax.Array,
    *,
    probe_rounds: int = 16,
    decay_cfg=None,
    decay_lanes: Tuple[str, ...] = ("weight",),
    tick_lane: str = "last_tick",
    now=None,
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Batched pair lookup under the region layout; mirrors
    :func:`lookup`'s contract (read-time decayed view under the lazy
    policy). Returns (lanes_at_pair, found_mask, global_slot)."""
    R, W = table.n_regions, table.width
    src_hi = jnp.asarray(src_hi, jnp.uint32)
    src_lo = jnp.asarray(src_lo, jnp.uint32)
    dst_hi = jnp.asarray(dst_hi, jnp.uint32)
    dst_lo = jnp.asarray(dst_lo, jnp.uint32)
    nonzero = (src_hi != 0) | (src_lo != 0)
    _, src_found, qslot = lookup(qstore, src_hi, src_lo,
                                 probe_rounds=probe_rounds)
    active = nonzero & src_found
    qslot_safe = jnp.where(active, qslot, 0)
    chain_ok = active & (table.chain_hi[qslot_safe] == src_hi) \
        & (table.chain_lo[qslot_safe] == src_lo)
    regs = jnp.where(chain_ok[:, None], table.chain_region[qslot_safe], -1)
    found_slot = _chain_find_jnp(table.key_hi.reshape(R, W),
                                 table.key_lo.reshape(R, W),
                                 regs, dst_hi, dst_lo, chain_ok)
    found = found_slot >= 0
    safe = jnp.where(found, found_slot, 0)
    f = None
    if decay_cfg is not None:
        f = decay_cfg.factor(
            jnp.maximum(now - table.lanes[tick_lane][safe], 0))
    out = {}
    for name, lane in table.lanes.items():
        v = lane[safe]
        if f is not None and name in decay_lanes:
            v = v * f
        out[name] = jnp.where(found, v, jnp.zeros_like(v))
    return out, found, found_slot
