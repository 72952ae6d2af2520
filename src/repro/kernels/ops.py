"""Public jit'd wrappers for the Pallas kernels, with ref fallbacks.

Execution mode is decided once, in ``kernels.resolve_interpret``: kernels
compile for real on a native-Pallas backend (TPU) and run under the Pallas
interpreter elsewhere (CPU CI). Whether a hot path runs its kernel *at
all* is the ``TunedPlan``'s call (see the package docstring) — these
wrappers keep kernel-vs-oracle shape handling in ONE place so the
engine/models just call ops.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from . import decay_prune as _dp
from . import assoc_score as _as
from . import edit_distance as _ed
from . import flash_attention as _fa
from . import topk_select as _tk

# None = let each kernel auto-resolve via kernels.resolve_interpret.
_INTERPRET = None
# The blocked sweeps require 1024-multiple capacities.
_TILE = _dp.TILE


def decay_prune_table(table, dticks, *, cfg, weight_lanes: Tuple[str, ...]):
    """Fused decay/prune sweep over a HashTable (engine decay cycle).

    Every 1-D lane rides the single Pallas read+write pass: weight lanes are
    decayed+pruned, aux lanes cleared on pruned slots, all in-kernel. Only
    ragged capacities or multi-dim lanes fall back to jnp masking.
    """
    primary = weight_lanes[0]
    f = cfg.factor(dticks)
    lanes = dict(table.lanes)
    aux_1d = [n for n, lane in table.lanes.items()
              if n not in weight_lanes and lane.ndim == 1]
    if table.capacity % _TILE:
        # ragged capacity: fall back to the jnp path semantics
        kh, kl, w, keep, live, tot = ref.decay_prune_ref(
            table.key_hi, table.key_lo, table.lanes[primary], f,
            cfg.prune_threshold)
        lanes[primary] = w
        for name in weight_lanes[1:]:
            lanes[name] = jnp.where(keep, lanes[name] * f, 0.0)
        for name in aux_1d:
            lanes[name] = jnp.where(keep, lanes[name],
                                    jnp.zeros_like(lanes[name]))
    else:
        kh, kl, w_out, a_out, live, tot = _dp.decay_prune_multi(
            table.key_hi, table.key_lo,
            tuple(table.lanes[n] for n in weight_lanes),
            tuple(table.lanes[n] for n in aux_1d),
            f, jnp.float32(cfg.prune_threshold), interpret=_INTERPRET)
        keep = (kh != 0) | (kl != 0)
        for name, w in zip(weight_lanes, w_out):
            lanes[name] = w
        for name, a in zip(aux_1d, a_out):
            lanes[name] = a
    # multi-dim lanes (none in the engine stores today) still need a mask
    for name, lane in lanes.items():
        if name not in weight_lanes and lane.ndim > 1:
            kb = keep.reshape(keep.shape + (1,) * (lane.ndim - 1))
            lanes[name] = jnp.where(kb, lane, jnp.zeros_like(lane))
    return table._replace(key_hi=kh, key_lo=kl, lanes=lanes), live, tot


def assoc_score(w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c, *,
                coefs: Tuple[float, float, float, float]):
    """Fused association scoring over full store lanes."""
    if w_ab.shape[0] % _TILE:
        return ref.assoc_score_ref(w_ab, c_ab, w_a, w_b, c_a, c_b,
                                   total_w, total_c, coefs)
    return _as.assoc_score(w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c,
                           coefs=tuple(float(c) for c in coefs),
                           interpret=_INTERPRET)


def score_gate(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, total_w, total_c, *,
               coefs: Tuple[float, float, float, float],
               min_pair_weight: float, min_src_weight: float,
               min_pair_count: float,
               decay_cfg=None, last_tick=None, now=None,
               block_rows: int | None = None):
    """Fused (lazy decay +) scoring + gating — the elementwise stage of the
    segmented-top-k ranking cycle.

    One Pallas pass per table tile: optional read-time exponential decay of
    ``w_ab`` from ``last_tick``, the four association lanes + linear
    combination, and the evidence gates, emitting one gated score lane
    (``-inf`` where gated). Non-exp decay kinds and ragged capacities
    pre-decay / fall back in jnp with identical semantics.
    """
    coefs = tuple(float(c) for c in coefs)
    C = w_ab.shape[0]
    half_life = None
    if decay_cfg is not None:
        if decay_cfg.kind == "exp" and C % _TILE == 0:
            half_life = float(decay_cfg.half_life_ticks)
        else:
            w_ab = w_ab * decay_cfg.factor(jnp.maximum(now - last_tick, 0))
    if C % _TILE:
        return ref.score_gate_ref(w_ab, c_ab, w_a, w_b, c_a, c_b, ok,
                                  total_w, total_c, coefs,
                                  min_pair_weight, min_src_weight,
                                  min_pair_count)
    return _tk.score_gate(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, last_tick,
                          total_w, total_c, now, coefs=coefs,
                          min_pair_weight=float(min_pair_weight),
                          min_src_weight=float(min_src_weight),
                          min_pair_count=float(min_pair_count),
                          half_life=half_life, interpret=_INTERPRET,
                          block_rows=block_rows)


def bucket_topk(grid, k: int):
    """Per-bucket top-k over the segmented-ranking [R, L] grid (values +
    in-bucket columns), via K rounds of in-VMEM masked argmax. Same tie
    rule as ``lax.top_k`` (lowest column wins)."""
    return _tk.bucket_topk(grid, int(k), interpret=_INTERPRET)


def chain_find(key_hi_r, key_lo_r, regs, dst_hi, dst_lo, active):
    """Region-layout chain find (insert fast path): one scalar-prefetched
    region tile in VMEM per batch row, one pass per chain depth. Returns
    the global slot of each pair's key, or -1 (same contract as the jnp
    reference ``stores._chain_find_jnp``)."""
    from . import region_probe as _rp
    return _rp.chain_find(key_hi_r, key_lo_r, regs, dst_hi, dst_lo, active,
                          interpret=_INTERPRET)


def region_rank(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, total_w, total_c, *,
                k: int, coefs: Tuple[float, float, float, float],
                min_pair_weight: float, min_src_weight: float,
                min_pair_count: float,
                decay_cfg=None, last_tick=None, now=None):
    """The region ranking cycle's ONE fused Pallas pass: (lazy decay +)
    association scoring + evidence gates + per-region top-k, reading the
    ``[n_regions, width]`` grid — a pure reshape of the store — straight
    from HBM tiles. Exponential decay runs in-kernel; other kinds
    pre-decay in jnp with identical semantics. Returns (vals, args,
    npass) — npass i32[R] is the per-region gate-pass count for overflow
    accounting, emitted by the same pass."""
    coefs = tuple(float(c) for c in coefs)
    half_life = None
    if decay_cfg is not None:
        if decay_cfg.kind == "exp":
            half_life = float(decay_cfg.half_life_ticks)
        else:
            w_ab = w_ab * decay_cfg.factor(jnp.maximum(now - last_tick, 0))
    return _tk.region_rank(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, last_tick,
                           total_w, total_c, now, k=int(k), coefs=coefs,
                           min_pair_weight=float(min_pair_weight),
                           min_src_weight=float(min_src_weight),
                           min_pair_count=float(min_pair_count),
                           half_life=half_life, interpret=_INTERPRET)


def edit_distance(a_chars, a_len, b_chars, b_len, *,
                  first_char_cost: float = 1.5, use_kernel: bool = True):
    """Batched weighted OSA edit distance."""
    a_chars = jnp.asarray(a_chars)
    b_chars = jnp.asarray(b_chars)
    a_len = jnp.asarray(a_len, jnp.int32)
    b_len = jnp.asarray(b_len, jnp.int32)
    if not use_kernel:
        return ref.edit_distance_ref(a_chars, a_len, b_chars, b_len,
                                     first_char_cost)
    return _ed.edit_distance(a_chars, a_len, b_chars, b_len,
                             first_char_cost=float(first_char_cost),
                             interpret=_INTERPRET)


# ---------------------------------------------------------------------------
# flash attention with a custom_vjp: Pallas forward, oracle backward.
# ---------------------------------------------------------------------------

def _fa_fwd_impl(q, k, v, causal, window):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=_INTERPRET)


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    return _fa_fwd_impl(q, k, v, causal, window)


def _fa_fwd(q, k, v, causal, window):
    return _fa_fwd_impl(q, k, v, causal, window), (q, k, v)


def _fa_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
