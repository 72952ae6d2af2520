"""Fused association scoring (Pallas TPU kernel) — the ranking-cycle hot loop.

One pass over the cooccurrence store computes all four association lanes
(conditional probability, PMI, log-likelihood ratio, chi-squared — paper
§2.4) AND their linear combination. Unfused, XLA materializes several
intermediate [C]-sized lanes in HBM; fused, each of the six input lanes is
read once and one output lane is written.

Layout mirrors decay_prune: (C/1024, 8, 128) tiles, 1-D grid.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret
from .decay_prune import LANE, SUBLANE, TILE, ROWS_PER_BLOCK


def llr_g2(k11, k12, k21, k22):
    """Dunning's G² of the 2x2 count table, in f32 without cancellation.

    The textbook form sums ``x·ln(x)`` over the cells, the margins and the
    total: terms of size ``n·ln(n)`` whose f32 rounding leaves an absolute
    error of ``~1e-7·n·ln(n)`` in a statistic of order one (0.1 at
    ``n ≈ 1.4e5`` events, more with a chip's less exact ``log``). Each cell
    instead contributes ``k·ln(k·n / (R·C))`` for its row and column sums,
    and ``k·n − R·C = ±D`` with ``D = k11·k22 − k12·k21`` (plus on the
    diagonal), so ``G² = 2·Σ k·log1p(±D / (R·C))``: small terms, nothing
    to cancel. Pure jnp on values, usable inside a Pallas kernel.
    """
    d = k11 * k22 - k12 * k21
    r1, r2 = k11 + k12, k21 + k22
    q1, q2 = k11 + k21, k12 + k22

    def cell(k, r, q, x_num):
        x = x_num / jnp.maximum(r * q, 1e-9)
        # k > 0 keeps k·n/(R·C) = 1 + x away from 0; the floor only guards
        # the rounding of x
        return jnp.where(
            k > 0, k * jnp.log1p(jnp.maximum(x, -1.0 + 1e-7)), 0.0)

    return 2.0 * (cell(k11, r1, q1, d) + cell(k12, r1, q2, -d)
                  + cell(k21, r2, q1, -d) + cell(k22, r2, q2, d))


def score_body(w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c,
               coefs: Tuple[float, float, float, float]):
    """The fused association-scoring body, on block *values* (not refs).

    Shared by this kernel and the segmented-top-k select kernel
    (``topk_select.py``), which folds gating and lazy decay around it.
    ``coefs`` must be python floats so they stay compile-time literals.
    """
    c0, c1, c2, c3 = coefs
    eps = jnp.float32(1e-9)
    w_a = jnp.maximum(w_a, 0.0)
    w_b = jnp.maximum(w_b, 0.0)
    condprob = jnp.where(w_a > 0, w_ab / jnp.maximum(w_a, eps), 0.0)
    pmi = jnp.where(
        (w_ab > 0) & (w_a > 0) & (w_b > 0),
        jnp.log(jnp.maximum(w_ab * jnp.maximum(total_w, eps), eps)
                / jnp.maximum(w_a * w_b, eps)),
        0.0)
    k11 = c_ab
    k12 = jnp.maximum(c_a - c_ab, 0.0)
    k21 = jnp.maximum(c_b - c_ab, 0.0)
    k22 = jnp.maximum(total_c - c_a - c_b + c_ab, 0.0)
    n = jnp.maximum(k11 + k12 + k21 + k22, eps)
    r1, r2 = k11 + k12, k21 + k22
    q1, q2 = k11 + k21, k12 + k22
    llr = jnp.maximum(llr_g2(k11, k12, k21, k22), 0.0)
    chi2 = n * (k11 * k22 - k12 * k21) ** 2 / jnp.maximum(r1 * r2 * q1 * q2, eps)
    valid = c_ab > 0
    condprob = jnp.where(valid, condprob, 0.0)
    pmi = jnp.where(valid, pmi, 0.0)
    llr = jnp.where(valid, llr, 0.0)
    chi2 = jnp.where(valid, chi2, 0.0)
    return (c0 * condprob + c1 * jax.nn.sigmoid(pmi)
            + c2 * jnp.log1p(llr) + c3 * jnp.log1p(chi2))


def _make_kernel(coefs: Tuple[float, float, float, float]):
    coefs = tuple(float(c) for c in coefs)  # python literals, not arrays

    def kernel(w_ab_ref, c_ab_ref, w_a_ref, w_b_ref, c_a_ref, c_b_ref,
               tw_ref, tc_ref, out_ref):
        out_ref[...] = score_body(
            w_ab_ref[...], c_ab_ref[...], w_a_ref[...], w_b_ref[...],
            c_a_ref[...], c_b_ref[...], tw_ref[0], tc_ref[0], coefs)

    return kernel


@functools.partial(jax.jit, static_argnames=("coefs", "interpret",
                                             "block_rows"))
def assoc_score(w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c,
                *, coefs: Tuple[float, float, float, float],
                interpret: bool | None = None,
                block_rows: int | None = None) -> jax.Array:
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
            for x in (w_ab, c_ab, w_a, w_b, c_a, c_b)]
    tw = jnp.asarray(total_w, jnp.float32).reshape(1)
    tc = jnp.asarray(total_c, jnp.float32).reshape(1)

    out = pl.pallas_call(
        _make_kernel(coefs),
        grid=(grid,),
        in_specs=[spec] * 6 + [sspec, sspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shape3, jnp.float32),
        interpret=interpret,
    )(*args, tw, tc)
    return out.reshape(C)
