"""Association scoring + ranking cycles (paper §2.4, §4.3 "Ranking cycles").

The ranker periodically traverses the entire cooccurrence store, scores each
(A -> B) pair with several association statistics computed against the query
store marginals, combines them linearly (the paper's "simplest workable
strategy ... linear combination with hand-tuned weights"), and emits top-k
suggestions per source query.

Score lanes (all named in §2.4):
  * conditional relative frequency   P(B|A) = w_ab / W_a
  * pointwise mutual information     log( w_ab * T / (W_a * W_b) )
  * log-likelihood ratio             Dunning's G² over the 2x2 count table
  * chi-squared                      χ² over the same 2x2 table

Selection — three implementations of the same per-source top-k contract:

  * :func:`ranking_cycle_region` — **region layout** (source-major store,
    see ``stores.RegionTable``). The store is already partitioned into
    per-source regions at insert time, so the ``[n_regions, width]``
    bucket grid is a **pure reshape** of the live table: no prefix-sum
    compaction, no grouping sort, no gathers before selection. Source
    marginals come from ONE direct index per region (region id = qstore
    slot — no per-pair qstore probing for the source side), per-region
    top-k reads the grid rows straight from HBM tiles (``lax.top_k`` or
    the fused ``kernels/topk_select.region_rank`` Pallas pass), and a
    source's spill-chain regions are merged by a second tiny top-k over
    ``max_chain * K`` candidates. Every live pair is in exactly one
    region, so selection itself never cuts: ``n_overflow`` counts only
    gate-passing pairs of sources beyond the ``max_sources`` cap.

  * :func:`ranking_cycle` (default) — **segmented top-k**. Every
    gate-passing pair is bucketed by its *source query's qstore slot* (the
    open-addressing placement is a hash-derived bucket that is collision-free
    across live keys, so no two sources share a bucket). Gate-passing rows
    are stream-compacted (prefix-sum scatter, no sort) into a selection
    arena, grouped by ONE two-key u32 sort — bucket id, then the inverted
    exact score bits, so each bucket's best rows lead its run — and laid
    out as a dense ``[buckets, L]`` grid by pure gathers. The
    per-bucket partial selection (top-k / iterated masked argmax along the
    L axis, Pallas kernel variant in ``kernels/topk_select.py``) then runs
    fully vectorized. The capacity-sized f32 ``argsort`` and the 3-key
    lexsort of the old pipeline are both gone: the only remaining sort is
    the grouping sort over the compacted arena, so cycle cost scales with
    gate-passing rows, not table capacity.
  * :func:`ranking_cycle_lexsort` — the pre-segmented reference pipeline
    (compact-by-argsort + 3-key lexsort + run extraction), kept verbatim for
    parity tests and before/after benchmark rows.

Exactness: rows beyond the per-bucket arena ``L`` are cut in exact-score
order, so with ``L >= top_k`` no true top-k member is ever cut; every cut
row is counted in ``SuggestionTable.n_overflow``, never silent. (The
grouping key used to pack bucket id and score into one u32: at a 2^22-slot
query store only 9 score bits were left — about the exponent — and head
sources lost top-k members to the cut.)

Cadence model under the **lazy** decay policy (``DecayConfig.policy ==
"lazy"``): the ranking cycle is a *read*, so it applies the read-time decayed
view per row — ``w * factor(now - last_tick)`` for pair weights, source and
destination marginals, and the query-store totals — instead of relying on a
periodic full decay sweep. The engine then only runs a prune-only sweep at
the much longer ``EngineConfig.prune_every`` cadence (see ``decay.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import stores
from ..kernels.assoc_score import llr_g2
from .decay import lazy_decayed
from .plan import TunedPlan
from .stores import HashTable, RegionTable


@dataclasses.dataclass(frozen=True)
class RankConfig:
    top_k: int = 8
    # linear combination coefficients over (condprob, pmi, llr, chi2)
    coef_condprob: float = 1.0
    coef_pmi: float = 0.15
    coef_llr: float = 0.02
    coef_chi2: float = 0.0
    # evidence gates: "accumulating sufficient evidence" (§2.2)
    min_pair_weight: float = 0.25
    min_src_weight: float = 0.5
    min_pair_count: float = 1.0
    # Legacy kernel override (None = defer to ``plan``): an explicit bool
    # forces score/gate + selection through Pallas (True) or jnp (False).
    use_kernel: Optional[bool] = None
    # Measured dispatch plan — normally attached from ``EngineConfig.plan``
    # (its ``__post_init__`` copies it here); standalone ranking callers
    # can set it directly.
    plan: Optional[TunedPlan] = None
    # lexsort path only: compact gated rows by argsort before the 3-key
    # lexsort; cuts the globally lowest-scoring pairs on overflow (counted).
    # >= 1.0 disables compaction entirely.
    compact_frac: float = 0.5
    # segmented path: the selection arena holds seg_arena_frac * capacity
    # gate-passing rows (sort-free prefix-sum compaction). Unlike the
    # lexsort path's score-ordered cut, arena overflow is cut by table
    # position — so the default matches the <=50% prune policy (§4.4):
    # positional cuts can only happen when more than half the table passes
    # the gates, the same regime where the old default overflowed. Always
    # counted in n_overflow. >= 1.0 disables compaction.
    seg_arena_frac: float = 0.5
    # segmented path: per-bucket arena width L — a source's gate-passing
    # rows beyond its L best are cut and counted.
    bucket_rows: int = 64
    # max sources emitted per cycle (grid height cap; sources beyond it are
    # cut and counted in n_overflow). 0 (the default) derives the cap from
    # the query store's capacity — a store can never hold more live sources
    # than qstore slots, so the derived cap cuts nothing while a fixed
    # default would silently cap large stores at its value.
    max_sources: int = 0

    def source_cap(self, qstore_capacity: int) -> int:
        return (self.max_sources if self.max_sources > 0
                else qstore_capacity)

    def kernel_on(self, op: str) -> bool:
        """Kernel-vs-jnp resolution for one ranking hot path: the legacy
        ``use_kernel`` bool wins; else the tuned plan; else jnp."""
        if self.use_kernel is not None:
            return self.use_kernel
        if self.plan is not None:
            return self.plan.uses_kernel(op)
        return False


def assoc_scores_jnp(w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c):
    """Reference (pure jnp) association score lanes. All inputs f32 arrays.

    Returns (condprob, pmi, llr, chi2); invalid/degenerate entries -> 0.
    """
    eps = 1e-9
    w_a = jnp.maximum(w_a, 0.0)
    w_b = jnp.maximum(w_b, 0.0)
    condprob = jnp.where(w_a > 0, w_ab / jnp.maximum(w_a, eps), 0.0)
    pmi = jnp.where(
        (w_ab > 0) & (w_a > 0) & (w_b > 0),
        jnp.log(jnp.maximum(w_ab * jnp.maximum(total_w, eps), eps)
                / jnp.maximum(w_a * w_b, eps)),
        0.0,
    )
    # 2x2 contingency over raw counts: events where A precedes B.
    k11 = c_ab
    k12 = jnp.maximum(c_a - c_ab, 0.0)
    k21 = jnp.maximum(c_b - c_ab, 0.0)
    k22 = jnp.maximum(total_c - c_a - c_b + c_ab, 0.0)
    n = jnp.maximum(k11 + k12 + k21 + k22, eps)
    row1, row2 = k11 + k12, k21 + k22
    col1, col2 = k11 + k21, k12 + k22
    llr = jnp.maximum(llr_g2(k11, k12, k21, k22), 0.0)
    denom = jnp.maximum(row1 * row2 * col1 * col2, eps)
    chi2 = n * (k11 * k22 - k12 * k21) ** 2 / denom
    valid = c_ab > 0
    return (jnp.where(valid, condprob, 0.0), jnp.where(valid, pmi, 0.0),
            jnp.where(valid, llr, 0.0), jnp.where(valid, chi2, 0.0))


def combine_scores(cfg: RankConfig, condprob, pmi, llr, chi2):
    """The paper's linear-combination ranker (hand-tuned coefficients)."""
    return (cfg.coef_condprob * condprob
            + cfg.coef_pmi * jax.nn.sigmoid(pmi)          # squash unbounded lanes
            + cfg.coef_llr * jnp.log1p(llr)
            + cfg.coef_chi2 * jnp.log1p(chi2))


class SuggestionTable(NamedTuple):
    """Dense top-k suggestion output of one ranking cycle."""
    src_hi: jax.Array    # u32[M]
    src_lo: jax.Array    # u32[M]
    dst_hi: jax.Array    # u32[M, K]
    dst_lo: jax.Array    # u32[M, K]
    score: jax.Array     # f32[M, K]  (0 => empty slot)
    n_rows: jax.Array    # i32[]
    n_overflow: jax.Array  # i32[] — gate-passing rows beyond the compaction cap
    # i32[n_lookups, 2] — per qstore lookup of the cycle: probe rounds run
    # over the whole batch, rows finished in the tail arena
    lookup_stats: Optional[jax.Array] = None


def _score_and_gate(cooc: HashTable, qstore: HashTable, cfg: RankConfig,
                    decay_cfg, now):
    """Shared ranking prologue: marginals lookup, association scoring and
    evidence gating — with the read-time decayed view under the lazy policy.

    Returns (score [-inf where gated], ok mask, src qstore slot, key lanes,
    the two lookups' stats).
    """
    live = cooc.live_mask
    src_hi = cooc.lanes["src_hi"]
    src_lo = cooc.lanes["src_lo"]
    dst_hi = cooc.lanes["dst_hi"]
    dst_lo = cooc.lanes["dst_lo"]
    w_ab = cooc.lanes["weight"]
    c_ab = cooc.lanes["count"]

    dkw = dict(decay_cfg=decay_cfg, now=now) if decay_cfg is not None else {}
    with jax.named_scope("rank.lookup_src"):
        src_vals, src_found, src_slot, src_stats = stores.lookup(
            qstore, src_hi, src_lo, with_stats=True, **dkw)
    with jax.named_scope("rank.lookup_dst"):
        dst_vals, dst_found, _, dst_stats = stores.lookup(
            qstore, dst_hi, dst_lo, with_stats=True, **dkw)
    with jax.named_scope("rank.score_gate"):
        if decay_cfg is not None:
            total_w = jnp.sum(lazy_decayed(decay_cfg, qstore.lanes["weight"],
                                           qstore.lanes["last_tick"], now))
        else:
            total_w = jnp.sum(qstore.lanes["weight"])
        total_c = jnp.sum(qstore.lanes["count"])

        base_ok = live & src_found & dst_found
        if cfg.kernel_on("score_gate"):
            from ..kernels import ops as kops
            score = kops.score_gate(
                w_ab, c_ab, src_vals["weight"], dst_vals["weight"],
                src_vals["count"], dst_vals["count"], base_ok, total_w,
                total_c, coefs=(cfg.coef_condprob, cfg.coef_pmi, cfg.coef_llr,
                                cfg.coef_chi2),
                min_pair_weight=cfg.min_pair_weight,
                min_src_weight=cfg.min_src_weight,
                min_pair_count=cfg.min_pair_count,
                decay_cfg=decay_cfg, last_tick=cooc.lanes["last_tick"],
                now=now,
                block_rows=(cfg.plan.score_block_rows
                            if cfg.plan is not None else None))
            ok = score > -jnp.inf
        else:
            if decay_cfg is not None:
                w_ab = lazy_decayed(decay_cfg, w_ab, cooc.lanes["last_tick"],
                                    now)
            lanes = assoc_scores_jnp(w_ab, c_ab, src_vals["weight"],
                                     dst_vals["weight"], src_vals["count"],
                                     dst_vals["count"], total_w, total_c)
            score = combine_scores(cfg, *lanes)
            ok = (base_ok
                  & (w_ab >= cfg.min_pair_weight)
                  & (c_ab >= cfg.min_pair_count)
                  & (src_vals["weight"] >= cfg.min_src_weight))
            score = jnp.where(ok, score, -jnp.inf)
    return (score, ok, src_slot, (src_hi, src_lo, dst_hi, dst_lo),
            jnp.stack([src_stats, dst_stats]))


def _sortable_f32(x: jax.Array) -> jax.Array:
    """Monotonic f32 -> u32 bit transform (IEEE total order)."""
    sb = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(sb >= 0, sb.astype(jnp.uint32) + jnp.uint32(0x80000000),
                     (~sb).astype(jnp.uint32))


@partial(jax.jit, static_argnames=("cfg", "decay_cfg"))
def ranking_cycle(
    cooc: HashTable,
    qstore: HashTable,
    cfg: RankConfig,
    *,
    decay_cfg=None,
    now=None,
) -> SuggestionTable:
    """One full ranking cycle — segmented top-k (the fast path).

    Pipeline (see module docstring): score+gate -> prefix-sum compaction of
    gate-passing row ids into an arena of M rows -> ONE grouping sort on
    (bucket id, inverted exact score) -> dense [R, L] bucket grid by
    gathers -> exact per-bucket top-k. Output rows are indexed by bucket
    run, so the table has ``min(Q, M, cfg.max_sources)`` rows; empty rows
    keep the (0, 0) src key and are skipped by :func:`suggestions_to_host`.
    Pass ``decay_cfg``/``now`` under the lazy decay policy to rank against
    the read-time decayed view.
    """
    C = cooc.capacity
    Q = qstore.capacity
    K = cfg.top_k
    L = max(cfg.bucket_rows, K)
    score, ok, src_slot, keys, lookup_stats = _score_and_gate(
        cooc, qstore, cfg, decay_cfg, now)
    src_hi, src_lo, dst_hi, dst_lo = keys

    # ---- sort-free stream compaction of gate-passing ROW IDS (one scatter;
    # payloads stay in place and are gathered on demand). Overflow beyond
    # the arena is cut by table position — counted, never silent. ----
    with jax.named_scope("rank.compact"):
        if cfg.seg_arena_frac >= 1.0:
            M = C
            idx = jnp.arange(C, dtype=jnp.int32)
            arena_spill = jnp.zeros((), jnp.int32)
            s = jnp.where(ok, score, -jnp.inf)
            seg = jnp.where(ok, src_slot, Q)
        else:
            M = min(C, max(K, int(C * cfg.seg_arena_frac)))
            pos = jnp.cumsum(ok.astype(jnp.int32)) - 1
            tgt = jnp.where(ok & (pos < M), pos, M)
            idx = jnp.full((M,), C, jnp.int32).at[tgt].set(
                jnp.arange(C, dtype=jnp.int32), mode="drop")
            arena_spill = jnp.maximum(jnp.sum(ok.astype(jnp.int32)) - M, 0)
            filled = idx < C
            safe_idx = jnp.clip(idx, 0, C - 1)
            s = jnp.where(filled, score[safe_idx], -jnp.inf)
            seg = jnp.where(filled, src_slot[safe_idx], Q)

    # ---- ONE grouping sort on two u32 keys: bucket id (the empty/gated
    # sentinel Q sorts last), then the inverted exact score, so each
    # bucket's rows are contiguous, best-first. ----
    with jax.named_scope("rank.group_sort"):
        sseg, _, sidx = jax.lax.sort(
            (seg.astype(jnp.uint32), ~_sortable_f32(s), idx), num_keys=2,
            is_stable=True)
        valid_row = sseg < Q
        is_new = jnp.concatenate(
            [jnp.ones((1,), bool), sseg[1:] != sseg[:-1]]) & valid_row
        run_id = jnp.cumsum(is_new.astype(jnp.int32)) - 1
        ar = jnp.arange(M, dtype=jnp.int32)
        pos_in_run = ar - jax.lax.cummax(jnp.where(is_new, ar, 0))

    # ---- dense [R, L] bucket grid, built by gathers only. run_id is
    # non-decreasing, so run starts come from a vectorized binary search. --
    with jax.named_scope("rank.grid"):
        R = min(Q, M, max(cfg.source_cap(Q), 1))
        run_start = jnp.searchsorted(run_id, jnp.arange(R + 1, dtype=jnp.int32)
                                     ).astype(jnp.int32)
        cell = run_start[:R, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
        in_run = cell < run_start[1:, None]   # the next run's start bounds it
        cell_c = jnp.clip(cell, 0, M - 1)
        # sorted position -> original table row (sidx carries the permuted row
        # ids; C is the empty-arena-slot sentinel) -> exact score.
        cell_orig = sidx[cell_c]
        grid = jnp.where(in_run & (cell_orig < C),
                         score[jnp.clip(cell_orig, 0, C - 1)], -jnp.inf)

    with jax.named_scope("rank.topk"):
        if cfg.kernel_on("bucket_topk"):
            from ..kernels import ops as kops
            vals, args = kops.bucket_topk(grid, K)
        else:
            vals, args = jax.lax.top_k(grid, K)

    with jax.named_scope("rank.gather_out"):
        good = vals > -jnp.inf
        win_sorted = jnp.clip(run_start[:R, None] + args, 0, M - 1)
        win_orig = jnp.clip(sidx[win_sorted], 0, C - 1)
        out_dst_hi = jnp.where(good, dst_hi[win_orig], jnp.uint32(0))
        out_dst_lo = jnp.where(good, dst_lo[win_orig], jnp.uint32(0))
        out_score = jnp.where(good, vals, 0.0)
        has_run = run_start[:R] < M
        head_orig = jnp.clip(sidx[jnp.clip(run_start[:R], 0, M - 1)], 0, C - 1)
        out_src_hi = jnp.where(has_run, src_hi[head_orig], jnp.uint32(0))
        out_src_lo = jnp.where(has_run, src_lo[head_orig], jnp.uint32(0))

    n_rows = jnp.sum(has_run.astype(jnp.int32))   # rows actually emitted
    select_spill = jnp.sum(
        (valid_row & ((pos_in_run >= L) | (run_id >= R))).astype(jnp.int32))
    return SuggestionTable(out_src_hi, out_src_lo, out_dst_hi, out_dst_lo,
                           out_score, n_rows, arena_spill + select_spill,
                           lookup_stats)


@partial(jax.jit, static_argnames=("cfg", "decay_cfg"))
def ranking_cycle_lexsort(
    cooc: HashTable,
    qstore: HashTable,
    cfg: RankConfig,
    *,
    decay_cfg=None,
    now=None,
) -> SuggestionTable:
    """Pre-segmented reference ranking cycle (compact-by-argsort + 3-key
    lexsort). Kept for parity tests and before/after benchmark rows,
    mirroring the ``insert_accumulate_twopass`` pattern; not used by the
    engine."""
    C = cooc.capacity
    score, ok, _, keys, lookup_stats = _score_and_gate(cooc, qstore, cfg,
                                                       decay_cfg, now)
    src_hi, src_lo, dst_hi, dst_lo = keys

    # ---- compact gate-passing rows so the 3-key lexsort runs over M << C
    # rows. Evidence gates + the <=50% prune policy keep the survivor count
    # far below capacity; overflow beyond M is counted, not silent. ----
    if cfg.compact_frac >= 1.0:
        M = C
        c_src_hi, c_src_lo = src_hi, src_lo
        c_dst_hi, c_dst_lo = dst_hi, dst_lo
        c_score, c_ok = score, ok
        n_overflow = jnp.zeros((), jnp.int32)
    else:
        M = min(C, max(cfg.top_k, int(C * cfg.compact_frac)))
        # single-key sort by descending score: gate-passing rows (finite
        # score) land before gated rows (-inf), so sel = the M *best* rows.
        # If more than M rows pass the gates, the overflow cut removes the
        # globally lowest-scoring pairs — counted, and never a source's top
        # suggestion before its worse ones.
        sel = jnp.argsort(-score)[:M]
        c_score = score[sel]
        c_ok = c_score > -jnp.inf
        gath = lambda a, fill: jnp.where(c_ok, a[sel], fill)
        # filler rows get an all-ones src key so they cluster in their own
        # (never-emitted) run after the sort instead of merging with a real
        # source's run.
        c_src_hi = gath(src_hi, jnp.uint32(0xFFFFFFFF))
        c_src_lo = gath(src_lo, jnp.uint32(0xFFFFFFFF))
        c_dst_hi = gath(dst_hi, jnp.uint32(0))
        c_dst_lo = gath(dst_lo, jnp.uint32(0))
        n_overflow = jnp.maximum(jnp.sum(ok.astype(jnp.int32)) - M, 0)

    # group by src, descending score: stable lexsort, last key is primary.
    order = jnp.lexsort((-c_score, c_src_lo, c_src_hi))
    s_hi, s_lo = c_src_hi[order], c_src_lo[order]
    s_dhi, s_dlo = c_dst_hi[order], c_dst_lo[order]
    s_score = c_score[order]
    s_ok = c_ok[order]

    prev_hi = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), s_hi[:-1]])
    prev_lo = jnp.concatenate([jnp.full((1,), 0xFFFFFFFF, jnp.uint32), s_lo[:-1]])
    is_new = (s_hi != prev_hi) | (s_lo != prev_lo)
    seg_id = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    first_idx = jax.ops.segment_min(jnp.arange(M, dtype=jnp.int32), seg_id,
                                    num_segments=M)
    pos = jnp.arange(M, dtype=jnp.int32) - first_idx[seg_id]

    K = cfg.top_k
    keep = s_ok & (pos < K)
    row = seg_id
    out_src_hi = jnp.zeros((M,), jnp.uint32).at[jnp.where(is_new & s_ok, row, M)].set(s_hi, mode="drop")
    out_src_lo = jnp.zeros((M,), jnp.uint32).at[jnp.where(is_new & s_ok, row, M)].set(s_lo, mode="drop")
    r_idx = jnp.where(keep, row, M)
    p_idx = jnp.where(keep, pos, 0)
    out_dst_hi = jnp.zeros((M, K), jnp.uint32).at[r_idx, p_idx].set(s_dhi, mode="drop")
    out_dst_lo = jnp.zeros((M, K), jnp.uint32).at[r_idx, p_idx].set(s_dlo, mode="drop")
    out_score = jnp.zeros((M, K), jnp.float32).at[r_idx, p_idx].set(
        jnp.where(keep, s_score, 0.0), mode="drop")
    n_rows = jnp.sum((is_new & s_ok).astype(jnp.int32))
    return SuggestionTable(out_src_hi, out_src_lo, out_dst_hi, out_dst_lo,
                           out_score, n_rows, n_overflow, lookup_stats)


@partial(jax.jit, static_argnames=("cfg", "decay_cfg"))
def ranking_cycle_region(
    cooc: RegionTable,
    qstore: HashTable,
    cfg: RankConfig,
    *,
    decay_cfg=None,
    now=None,
) -> SuggestionTable:
    """One full ranking cycle over the **source-major region layout**.

    The bucket grid is ``score.reshape(n_regions, width)`` — a pure view
    of the live table, built with zero sorts, zero compaction scatters and
    zero pre-selection gathers. Source marginals are read by direct index
    (region id = qstore slot), destination marginals by one batched qstore
    lookup over the key lanes. Selection is per-region top-k (grid rows
    stream straight from HBM; ``cfg.use_kernel`` routes the fused
    score+gate+select Pallas pass in ``kernels/topk_select.region_rank``)
    followed by a per-source merge of the spill chain's ``max_chain * K``
    candidates. Tie order (documented): within a region, the lower slot
    position wins (insertion order); across a chain, the earlier chain
    region wins — both may differ from the segmented path's table-position
    order on exact ties.

    Every live pair sits in exactly one region, so selection never cuts;
    ``n_overflow`` counts gate-passing pairs of sources beyond
    ``cfg.max_sources`` (derived from the qstore capacity by default,
    i.e. normally zero).
    """
    C, R, W, MC = cooc.capacity, cooc.n_regions, cooc.width, cooc.max_chain
    Q = cooc.dir_slots
    K = cfg.top_k
    assert Q == qstore.capacity, "directory must be indexed by qstore slot"

    live = cooc.live_mask
    w_ab = cooc.lanes["weight"]
    c_ab = cooc.lanes["count"]

    # dst marginals: the key lanes ARE the destination fingerprints.
    dkw = dict(decay_cfg=decay_cfg, now=now) if decay_cfg is not None else {}
    dst_vals, dst_found, _, lookup_stats = stores.lookup(
        qstore, cooc.key_hi, cooc.key_lo, with_stats=True, **dkw)
    if decay_cfg is not None:
        total_w = jnp.sum(lazy_decayed(decay_cfg, qstore.lanes["weight"],
                                       qstore.lanes["last_tick"], now))
    else:
        total_w = jnp.sum(qstore.lanes["weight"])
    total_c = jnp.sum(qstore.lanes["count"])

    # src marginals: ONE direct index per region — no per-pair probing.
    # region_chain_state is the ONE statement of chain validity (shared
    # with the sweeps in decay.py).
    row_valid, ent_ok, referenced = stores.region_chain_state(cooc, qstore)
    ent = cooc.chain_region
    o = jnp.clip(cooc.region_owner, 0, Q - 1)
    w_a_r = qstore.lanes["weight"][o]
    c_a_r = qstore.lanes["count"][o]
    if decay_cfg is not None:
        w_a_r = lazy_decayed(decay_cfg, w_a_r,
                             qstore.lanes["last_tick"][o], now)

    # ---- [R, W] grid scoring: the pure-reshape bucket grid. ----
    shape = (R, W)
    w_ab2 = w_ab.reshape(shape)
    c_ab2 = c_ab.reshape(shape)
    w_b2 = dst_vals["weight"].reshape(shape)
    c_b2 = dst_vals["count"].reshape(shape)
    base_ok = (live & dst_found).reshape(shape) & referenced[:, None]
    w_a_b = jnp.broadcast_to(w_a_r[:, None], shape)
    c_a_b = jnp.broadcast_to(c_a_r[:, None], shape)
    # a single region holds at most W pairs: per-region selection takes
    # min(K, W) winners and the chain merge below restores K (a source's
    # top-k beyond W can only come from its spill regions).
    K1 = min(K, W)
    if cfg.kernel_on("region_rank"):
        from ..kernels import ops as kops
        vals, args, npass_r = kops.region_rank(
            w_ab2, c_ab2, w_a_b, w_b2, c_a_b, c_b2, base_ok, total_w,
            total_c, k=K1,
            coefs=(cfg.coef_condprob, cfg.coef_pmi, cfg.coef_llr,
                   cfg.coef_chi2),
            min_pair_weight=cfg.min_pair_weight,
            min_src_weight=cfg.min_src_weight,
            min_pair_count=cfg.min_pair_count,
            decay_cfg=decay_cfg,
            last_tick=cooc.lanes["last_tick"].reshape(shape), now=now)
    else:
        w_eff = w_ab2 if decay_cfg is None else lazy_decayed(
            decay_cfg, w_ab, cooc.lanes["last_tick"], now).reshape(shape)
        lanes_s = assoc_scores_jnp(w_eff, c_ab2, w_a_b, w_b2, c_a_b, c_b2,
                                   total_w, total_c)
        score = combine_scores(cfg, *lanes_s)
        pass_mask = base_ok & (w_eff >= cfg.min_pair_weight) \
            & (c_ab2 >= cfg.min_pair_count) \
            & (w_a_b >= cfg.min_src_weight)
        grid = jnp.where(pass_mask, score, -jnp.inf)
        vals, args = jax.lax.top_k(grid, K1)
        npass_r = jnp.sum(pass_mask.astype(jnp.int32), axis=1)

    # ---- per-source chain merge: top-k over max_chain * K candidates. --
    S = min(Q, R, max(cfg.source_cap(Q), 1))
    act = row_valid
    posq = jnp.cumsum(act.astype(jnp.int32)) - 1
    slot_of_row = jnp.full((S,), Q, jnp.int32).at[
        jnp.where(act & (posq < S), posq, S)].set(
        jnp.arange(Q, dtype=jnp.int32), mode="drop")
    has_slot = slot_of_row < Q
    slot_safe = jnp.where(has_slot, slot_of_row, 0)
    ch = jnp.where(has_slot[:, None], cooc.chain_region[slot_safe], -1)
    cand = jnp.where((ch >= 0)[:, :, None],
                     vals[jnp.clip(ch, 0, R - 1)],
                     -jnp.inf).reshape(S, MC * K1)
    if MC * K1 < K:   # K exceeds the whole chain's candidate pool
        cand = jnp.pad(cand, ((0, 0), (0, K - MC * K1)),
                       constant_values=-jnp.inf)
    fvals, fidx = jax.lax.top_k(cand, K)
    depth = jnp.minimum(fidx // K1, MC - 1)
    reg_w = jnp.take_along_axis(ch, depth, axis=1)
    col = args[jnp.clip(reg_w, 0, R - 1), fidx % K1]
    gslot = jnp.clip(reg_w, 0, R - 1) * W + jnp.clip(col, 0, W - 1)
    good = fvals > -jnp.inf
    out_dst_hi = jnp.where(good, cooc.key_hi[gslot], jnp.uint32(0))
    out_dst_lo = jnp.where(good, cooc.key_lo[gslot], jnp.uint32(0))
    out_score = jnp.where(good, fvals, 0.0)
    has_out = jnp.any(good, axis=1)
    out_src_hi = jnp.where(has_out, cooc.chain_hi[slot_safe], jnp.uint32(0))
    out_src_lo = jnp.where(has_out, cooc.chain_lo[slot_safe], jnp.uint32(0))
    n_rows = jnp.sum(has_out.astype(jnp.int32))

    npass_row = jnp.sum(jnp.where(ent_ok, npass_r[jnp.clip(ent, 0, R - 1)],
                                  0), axis=1)
    n_overflow = jnp.sum(jnp.where(act & (posq >= S), npass_row, 0))
    return SuggestionTable(out_src_hi, out_src_lo, out_dst_hi, out_dst_lo,
                           out_score, n_rows, n_overflow, lookup_stats[None])


def suggestions_to_host(table: SuggestionTable) -> dict:
    """Export a SuggestionTable to host numpy dict keyed by src fp64.

    Skips empty rows (src key (0, 0)) AND the all-ones filler src key that
    the lexsort path assigns to compaction-overflow filler rows — explicitly,
    rather than relying on every filler entry carrying score 0.
    """
    from .hashing import join_fp
    with obs.span("rank.to_host"):
        src_hi = np.asarray(table.src_hi)
        src_lo = np.asarray(table.src_lo)
        mask = ((src_hi != 0) | (src_lo != 0)) \
            & ~((src_hi == 0xFFFFFFFF) & (src_lo == 0xFFFFFFFF))
        out = {}
        dst_fp = join_fp(np.asarray(table.dst_hi), np.asarray(table.dst_lo))
        score = np.asarray(table.score)
        for i in np.nonzero(mask)[0]:
            fp = int(join_fp(src_hi[i], src_lo[i]))
            row = [(int(d), float(s)) for d, s in zip(dst_fp[i], score[i])
                   if s > 0.0]
            if row:
                out[fp] = row
        obs.count("rank.rows_exported", len(out))
    return out
