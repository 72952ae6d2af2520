"""Fused decay + prune + occupancy sweep (Pallas TPU kernel).

The paper's decay/prune cycle (§4.3) touches every store entry: decay all
weights, clear entries under the prune threshold, and (for monitoring /
§4.4 memory control) report live occupancy and total weight. Done naively
this is three full HBM passes over the table (decay write, prune write,
stats read); the fused kernel does ONE read + ONE write per lane plus a
per-block stats reduction.

``decay_prune_multi`` sweeps **every** store lane in that single pass: any
number of weight lanes (decayed then pruned together) plus any number of
auxiliary lanes (counts, timestamps, endpoint fingerprints — cleared on
pruned slots, passed through otherwise). The engine's decay cycle therefore
costs one read + one write of the whole table, with no follow-up jnp passes
per aux lane.

TPU layout: the 1-D table arrays (capacity C, a power of two) are viewed as
(C/1024, 8, 128) so each block is an aligned (8, 128) VPU tile; the grid
walks row-blocks of ROWS_PER_BLOCK tiles. The live count and total weight
are jnp reductions over the kernel's outputs: the same reductions, in the
same order, as the jnp sweep, so both paths return bit-identical scalars
(a per-block stats output would round differently, and rank-1 per-block
outputs do not tile on the TPU).

``interpret`` defaults to auto-detection: the kernel compiles for real on a
TPU backend and falls back to the Pallas interpreter elsewhere (CPU CI).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

LANE = 128
SUBLANE = 8
TILE = LANE * SUBLANE            # 1024 elements per tile
ROWS_PER_BLOCK = 16              # 16 tiles = 16KiB f32 per lane per block

# Back-compat alias: the auto-detect now lives in kernels/__init__ (the one
# shared copy); older call sites imported it from here.
_resolve_interpret = resolve_interpret


def _make_kernel(n_w: int, n_aux: int):
    """Build the fused sweep kernel for n_w weight lanes + n_aux aux lanes.

    Ref order: inputs  [f, thresh, key_hi, key_lo, w_0..w_{n_w-1}, a_0..]
               outputs [key_hi', key_lo', w'_0.., a'_0..]
    """
    def kernel(*refs):
        f = refs[0][0]
        thresh = refs[1][0]
        k_hi = refs[2][...]
        k_lo = refs[3][...]
        w_ins = [refs[4 + i][...] for i in range(n_w)]
        a_ins = [refs[4 + n_w + i][...] for i in range(n_aux)]
        o = 4 + n_w + n_aux
        out_hi_ref, out_lo_ref = refs[o], refs[o + 1]
        w_out_refs = [refs[o + 2 + i] for i in range(n_w)]
        a_out_refs = [refs[o + 2 + n_w + i] for i in range(n_aux)]

        live = (k_hi != 0) | (k_lo != 0)
        w0 = w_ins[0] * f
        keep = live & (w0 >= thresh)
        w0 = jnp.where(keep, w0, 0.0)
        out_hi_ref[...] = jnp.where(keep, k_hi, jnp.uint32(0))
        out_lo_ref[...] = jnp.where(keep, k_lo, jnp.uint32(0))
        w_out_refs[0][...] = w0
        for i in range(1, n_w):
            w_out_refs[i][...] = jnp.where(keep, w_ins[i] * f, 0.0)
        for a_in, a_out in zip(a_ins, a_out_refs):
            a_out[...] = jnp.where(keep, a_in, jnp.zeros_like(a_in))

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def decay_prune_multi(
    key_hi: jax.Array,
    key_lo: jax.Array,
    weight_lanes: Tuple[jax.Array, ...],
    aux_lanes: Tuple[jax.Array, ...],
    decay_factor: jax.Array,
    threshold: jax.Array,
    *,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, Tuple[jax.Array, ...],
           Tuple[jax.Array, ...], jax.Array, jax.Array]:
    """Full-lane fused sweep over a store's dense arrays.

    ``weight_lanes[0]`` is the primary lane: it decides pruning against
    ``threshold`` after decay. Further weight lanes decay by the same factor;
    ``aux_lanes`` are cleared on pruned slots and passed through otherwise.
    All lanes must be 1-D of the same capacity (a multiple of 1024).

    Returns (key_hi', key_lo', weight_lanes', aux_lanes',
             live_count i32[], total_weight f32[]).
    """
    assert len(weight_lanes) >= 1
    C = key_hi.shape[0]
    assert C % TILE == 0, "table capacity must be a multiple of 1024"
    rows = C // TILE
    blk = min(ROWS_PER_BLOCK, rows)
    assert rows % blk == 0
    grid = rows // blk

    shape3 = (rows, SUBLANE, LANE)
    view = lambda a: a.reshape(shape3)
    f = jnp.asarray(decay_factor, jnp.float32).reshape(1)
    t = jnp.asarray(threshold, jnp.float32).reshape(1)

    n_w, n_aux = len(weight_lanes), len(aux_lanes)
    spec = pl.BlockSpec((blk, SUBLANE, LANE), lambda i: (i, 0, 0))
    sspec = pl.BlockSpec((1,), lambda i: (0,))

    lane_out = lambda a: jax.ShapeDtypeStruct(shape3, a.dtype)
    outs = pl.pallas_call(
        _make_kernel(n_w, n_aux),
        grid=(grid,),
        in_specs=[sspec, sspec, spec, spec] + [spec] * (n_w + n_aux),
        out_specs=[spec, spec] + [spec] * (n_w + n_aux),
        out_shape=[
            jax.ShapeDtypeStruct(shape3, jnp.uint32),
            jax.ShapeDtypeStruct(shape3, jnp.uint32),
            *[lane_out(w) for w in weight_lanes],
            *[lane_out(a) for a in aux_lanes],
        ],
        interpret=resolve_interpret(interpret),
    )(f, t, view(key_hi), view(key_lo),
      *[view(w) for w in weight_lanes], *[view(a) for a in aux_lanes])

    out_hi, out_lo = outs[0].reshape(C), outs[1].reshape(C)
    w_out = tuple(o.reshape(C) for o in outs[2:2 + n_w])
    a_out = tuple(o.reshape(C) for o in outs[2 + n_w:])
    keep = (out_hi != 0) | (out_lo != 0)
    return (out_hi, out_lo, w_out, a_out,
            jnp.sum(keep.astype(jnp.int32)), jnp.sum(w_out[0]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def decay_prune(key_hi: jax.Array, key_lo: jax.Array, weight: jax.Array,
                decay_factor: jax.Array, threshold: jax.Array,
                *, interpret: bool | None = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-lane sweep over (key_hi, key_lo, weight) table arrays.

    Returns (key_hi', key_lo', weight', live_count i32[], total_weight f32[]).
    Auxiliary lanes of the store are cleared by the caller using the
    returned keys (a pruned slot has key (0,0)) — or fused directly via
    :func:`decay_prune_multi`.
    """
    kh, kl, (w,), _, live, tot = decay_prune_multi(
        key_hi, key_lo, (weight,), (), decay_factor, threshold,
        interpret=interpret)
    return kh, kl, w, live, tot
