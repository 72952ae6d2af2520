"""Segmented-top-k ranking fast path: the two Pallas kernels (TPU).

The segmented ranking cycle (``core/ranking.py``) is three stages:

  1. an **elementwise table pass** — read-time lazy decay of the pair
     weight, the four association-score lanes and their linear combination
     (``assoc_score.score_body``), and the evidence gates, producing one
     gated score lane (``-inf`` where gated);
  2. **grouping** — prefix-sum compaction of gate-passing row ids plus one
     flat u32 sort on (bucket id | coarse score), laying the rows out as a
     dense ``[buckets, L]`` grid;
  3. **per-bucket partial selection** — ``top_k`` rounds of masked argmax
     along each bucket's L-row arena.

``score_gate`` fuses stage 1 into ONE pass: each (8, 128) table tile is
read into VMEM once and the whole decay -> score -> gate chain runs on it
in-register, instead of XLA materializing the decayed weight, four score
lanes, the combined score and the gate mask as separate [C] HBM arrays.
``bucket_topk`` runs stage 3: each block of bucket rows sits in VMEM while
the K argmax rounds run fully vectorized along the lane axis — no sort and
no scatter in the selection itself. Stage 2 (compaction scatter + flat
sort) is scatter/sort-shaped and stays on XLA, which is exactly the
efficient cut for a TPU: Pallas kernels have no efficient cross-tile
scatter. Dispatch in ``ops.score_gate`` / ``ops.bucket_topk``, oracles in
``ref.py``.

Layout mirrors decay_prune: (C/1024, 8, 128) tiles, 1-D grid for
``score_gate``; transposed (L, buckets) blocks for ``bucket_topk``. The
in-kernel lazy decay covers the (default) exponential kind; other kinds
pre-decay in jnp before the call.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret
from .assoc_score import score_body
from .decay_prune import LANE, SUBLANE, TILE, ROWS_PER_BLOCK


def _make_kernel(coefs: Tuple[float, float, float, float],
                 min_pair_weight: float, min_src_weight: float,
                 min_pair_count: float, half_life: Optional[float]):
    coefs = tuple(float(c) for c in coefs)   # compile-time literals
    mpw = float(min_pair_weight)
    msw = float(min_src_weight)
    mpc = float(min_pair_count)

    def kernel(*refs):
        if half_life is not None:
            (w_ab_ref, c_ab_ref, w_a_ref, w_b_ref, c_a_ref, c_b_ref,
             ok_ref, lt_ref, tw_ref, tc_ref, now_ref, out_ref) = refs
            dt = jnp.maximum(now_ref[0] - lt_ref[...], 0.0)
            w_ab = w_ab_ref[...] * jnp.exp2(-dt / jnp.float32(half_life))
        else:
            (w_ab_ref, c_ab_ref, w_a_ref, w_b_ref, c_a_ref, c_b_ref,
             ok_ref, tw_ref, tc_ref, out_ref) = refs
            w_ab = w_ab_ref[...]
        c_ab = c_ab_ref[...]
        w_a = w_a_ref[...]
        score = score_body(w_ab, c_ab, w_a, w_b_ref[...], c_a_ref[...],
                           c_b_ref[...], tw_ref[0], tc_ref[0], coefs)
        ok = ((ok_ref[...] > 0) & (w_ab >= mpw) & (c_ab >= mpc)
              & (w_a >= msw))
        out_ref[...] = jnp.where(ok, score, -jnp.inf)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "coefs", "min_pair_weight", "min_src_weight", "min_pair_count",
    "half_life", "interpret", "block_rows"))
def score_gate(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, last_tick, total_w,
               total_c, now, *, coefs: Tuple[float, float, float, float],
               min_pair_weight: float, min_src_weight: float,
               min_pair_count: float, half_life: Optional[float] = None,
               interpret: bool | None = None,
               block_rows: int | None = None) -> jax.Array:
    """Fused lazy-decay + association-scoring + gating over full lanes.

    ``half_life`` (static) enables in-kernel exponential read-time decay of
    ``w_ab`` from ``last_tick`` to ``now``; pass None when the caller
    already holds the effective pair weight (eager policy, or a non-exp
    decay pre-applied in jnp). Returns the gated combined score, ``-inf``
    where any evidence gate fails. ``block_rows`` overrides the tile rows
    per grid step (a ``TunedPlan.score_block_rows`` knob — in interpret
    mode fewer, larger blocks amortize per-step interpreter overhead).
    """
    interpret = resolve_interpret(interpret)
    C = w_ab.shape[0]
    assert C % TILE == 0
    rows = C // TILE
    blk = min(ROWS_PER_BLOCK if block_rows is None else block_rows, rows)
    assert rows % blk == 0, (rows, blk)
    grid = rows // blk
    shape3 = (rows, SUBLANE, LANE)

    spec = pl.BlockSpec((blk, SUBLANE, LANE), lambda i: (i, 0, 0))
    sspec = pl.BlockSpec((1,), lambda i: (0,))
    args = [x.astype(jnp.float32).reshape(shape3)
            for x in (w_ab, c_ab, w_a, w_b, c_a, c_b, ok)]
    scalars = [jnp.asarray(total_w, jnp.float32).reshape(1),
               jnp.asarray(total_c, jnp.float32).reshape(1)]
    if half_life is not None:
        args.append(last_tick.astype(jnp.float32).reshape(shape3))
        scalars.append(jnp.asarray(now, jnp.float32).reshape(1))

    out = pl.pallas_call(
        _make_kernel(coefs, min_pair_weight, min_src_weight, min_pair_count,
                     None if half_life is None else float(half_life)),
        grid=(grid,),
        in_specs=[spec] * len(args) + [sspec] * len(scalars),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape3, jnp.float32),
        interpret=interpret,
    )(*args, *scalars)
    return out.reshape(C)


# ---------------------------------------------------------------------------
# bucket_topk: per-bucket iterated masked argmax over the [R, L] grid.
# ---------------------------------------------------------------------------

_BUCKET_BLOCK = 128   # region rows per region_rank grid step
_BUCKET_LANES = 1024  # bucket columns per bucket_topk grid step


def _make_bucket_kernel(K: int, Lp: int):
    def kernel(g_ref, vals_ref, args_ref):
        g = g_ref[...]                                   # (Lp, BR)
        iota = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        args_ref[...] = jnp.full(args_ref.shape, Lp, jnp.int32)
        for k in range(K):
            m = jnp.max(g, axis=0, keepdims=True)        # (1, BR)
            hit = (g == m) & (m > -jnp.inf)
            am = jnp.min(jnp.where(hit, iota, Lp), axis=0, keepdims=True)
            vals_ref[k:k + 1, :] = m
            args_ref[k:k + 1, :] = am
            g = jnp.where(iota == am, -jnp.inf, g)       # retire the winner

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def bucket_topk(grid: jax.Array, k: int, *, interpret: bool | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-k of each bucket row of ``grid`` [R, L] by K rounds of masked
    argmax — each block of buckets stays in VMEM for all K rounds.

    The kernel sees the grid transposed, ``[L, R]``: buckets run along the
    128-wide lane axis and each round's max/argmax reduces over sublanes,
    so the K winners of a block leave as lane-dense ``(K, buckets)`` rows.
    Padding is to 8 rows of L and 8 rows of K only (the untransposed form
    padded both to 128 lanes, several times the grid's own bytes at a
    deployment-size query store).

    Ties resolve to the lowest column (identical to ``lax.top_k``).
    Returns (vals f32[R, k], args i32[R, k]); exhausted rounds yield
    ``-inf`` vals and the sentinel column ``Lp`` (the padded width).
    """
    interpret = resolve_interpret(interpret)
    R, L = grid.shape
    Lp = ((max(L, 1) + SUBLANE - 1) // SUBLANE) * SUBLANE
    Kp = ((max(k, 1) + SUBLANE - 1) // SUBLANE) * SUBLANE
    BR = min(_BUCKET_LANES, ((max(R, 1) + LANE - 1) // LANE) * LANE)
    Rp = ((R + BR - 1) // BR) * BR
    gp = grid.astype(jnp.float32).T
    if (Lp, Rp) != (L, R):           # aligned grid: no padded copy
        gp = jnp.full((Lp, Rp), -jnp.inf, jnp.float32).at[:L, :R].set(gp)

    spec_in = pl.BlockSpec((Lp, BR), lambda i: (0, i))
    spec_out = pl.BlockSpec((Kp, BR), lambda i: (0, i))
    vals, args = pl.pallas_call(
        _make_bucket_kernel(int(k), Lp),
        grid=(Rp // BR,),
        in_specs=[spec_in],
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((Kp, Rp), jnp.float32),
                   jax.ShapeDtypeStruct((Kp, Rp), jnp.int32)],
        interpret=interpret,
    )(gp)
    return vals[:k, :R].T, args[:k, :R].T


# ---------------------------------------------------------------------------
# region_rank: the region layout's ONE fused pass — lazy decay + scoring +
# gating + per-region top-k, reading the [n_regions, width] grid (a pure
# reshape of the store) straight from HBM tiles. No intermediate [C] score
# array ever materializes: each block of region rows is read once into
# VMEM, scored in-register, and leaves only its K winners.
# ---------------------------------------------------------------------------


def _make_region_kernel(K: int, Wp: int,
                        coefs: Tuple[float, float, float, float],
                        min_pair_weight: float, min_src_weight: float,
                        min_pair_count: float, half_life: Optional[float]):
    coefs = tuple(float(c) for c in coefs)
    mpw = float(min_pair_weight)
    msw = float(min_src_weight)
    mpc = float(min_pair_count)

    def kernel(*refs):
        if half_life is not None:
            (w_ab_ref, c_ab_ref, w_a_ref, w_b_ref, c_a_ref, c_b_ref,
             ok_ref, lt_ref, tw_ref, tc_ref, now_ref,
             vals_ref, args_ref) = refs
            dt = jnp.maximum(now_ref[0] - lt_ref[...], 0.0)
            w_ab = w_ab_ref[...] * jnp.exp2(-dt / jnp.float32(half_life))
        else:
            (w_ab_ref, c_ab_ref, w_a_ref, w_b_ref, c_a_ref, c_b_ref,
             ok_ref, tw_ref, tc_ref, vals_ref, args_ref) = refs
            w_ab = w_ab_ref[...]
        c_ab = c_ab_ref[...]
        w_a = w_a_ref[...]
        score = score_body(w_ab, c_ab, w_a, w_b_ref[...], c_a_ref[...],
                           c_b_ref[...], tw_ref[0], tc_ref[0], coefs)
        ok = ((ok_ref[...] > 0) & (w_ab >= mpw) & (c_ab >= mpc)
              & (w_a >= msw))
        g = jnp.where(ok, score, -jnp.inf)
        iota = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        args_ref[...] = jnp.full(args_ref.shape, Wp, jnp.int32)
        for k in range(K):
            m = jnp.max(g, axis=1, keepdims=True)
            hit = (g == m) & (m > -jnp.inf)
            am = jnp.min(jnp.where(hit, iota, Wp), axis=1, keepdims=True)
            vals_ref[:, k] = m[:, 0]
            args_ref[:, k] = am[:, 0]
            g = jnp.where(iota == am, -jnp.inf, g)
        # the gate-pass count rides in the spare column K of the args block
        args_ref[:, K] = jnp.sum(ok.astype(jnp.int32), axis=1)

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "k", "coefs", "min_pair_weight", "min_src_weight", "min_pair_count",
    "half_life", "interpret"))
def region_rank(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, last_tick, total_w,
                total_c, now, *, k: int,
                coefs: Tuple[float, float, float, float],
                min_pair_weight: float, min_src_weight: float,
                min_pair_count: float, half_life: Optional[float] = None,
                interpret: bool | None = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused score+gate+top-k over the region grid: all inputs ``[R, W]``
    (source marginals pre-broadcast along W by the caller — XLA fuses the
    broadcast into the feed). Ties resolve to the lowest slot position
    (insertion order). Returns (vals f32[R, k], args i32[R, k],
    npass i32[R] — gate-passing slots per region, the caller's overflow
    accounting, so no second jnp gate pass over the store is needed);
    exhausted rounds yield ``-inf`` and the padded-width sentinel."""
    interpret = resolve_interpret(interpret)
    R, W = w_ab.shape
    Wp = ((max(W, 1) + LANE - 1) // LANE) * LANE
    Kp = ((k + 1 + LANE - 1) // LANE) * LANE   # + the npass column
    BR = min(_BUCKET_BLOCK, max(SUBLANE, R))
    Rp = ((R + BR - 1) // BR) * BR

    def pad(x, fill=0.0):
        x = x.astype(jnp.float32)
        if (Rp, Wp) == (R, W):       # lane-aligned width: no padded copy
            return x
        buf = jnp.full((Rp, Wp), fill, jnp.float32)
        return buf.at[:R, :W].set(x)

    args = [pad(a) for a in (w_ab, c_ab, w_a, w_b, c_a, c_b, ok)]
    scalars = [jnp.asarray(total_w, jnp.float32).reshape(1),
               jnp.asarray(total_c, jnp.float32).reshape(1)]
    if half_life is not None:
        args.append(pad(last_tick))
        scalars.append(jnp.asarray(now, jnp.float32).reshape(1))

    spec_in = pl.BlockSpec((BR, Wp), lambda i: (i, 0))
    spec_out = pl.BlockSpec((BR, Kp), lambda i: (i, 0))
    sspec = pl.BlockSpec((1,), lambda i: (0,))
    vals, cols = pl.pallas_call(
        _make_region_kernel(int(k), Wp, coefs, min_pair_weight,
                            min_src_weight, min_pair_count,
                            None if half_life is None else float(half_life)),
        grid=(Rp // BR,),
        in_specs=[spec_in] * len(args) + [sspec] * len(scalars),
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((Rp, Kp), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, Kp), jnp.int32)],
        interpret=interpret,
    )(*args, *scalars)
    return vals[:R, :k], cols[:R, :k], cols[:R, k]
