"""Region-probe Pallas kernel: in-VMEM region-tile find for the
source-major cooccurrence store (closes the ROADMAP "Pallas probe kernel"
item for the layout that replaced global open addressing).

The region layout (``core/stores.RegionTable``) turns the store's find
step from K rounds of random [capacity]-wide gathers into a *chain scan*:
each pair's source names its region chain directly (region id = qstore
slot), and a find only has to match the destination key against the W
contiguous slots of each chain region. ``chain_find_depth`` is that scan
as a Pallas kernel: the grid walks the batch, and a scalar-prefetched
region id steers the BlockSpec index map so each step DMAs ONE aligned
row group — the ``(8, W)`` rows of the key lanes that hold the pair's
region — from HBM into VMEM, matches the pair's key (also prefetched into
SMEM) against the region's row in-register, and writes the match position
to an SMEM output. The probe working set is one row group, never the whole
table; consecutive batch rows that hit the same group re-use the block.

``chain_find`` wraps the kernel over the (short) spill chain: one call per
chain depth, folding hits into the running found-slot vector exactly like
the jnp reference (``stores._chain_find_jnp``).

Layout note: a row group is 8 regions, the sublane tiling, and its width
is the whole region (``W`` equals the array's last dimension, so any width
tiles); the engine's TPU deployments use ``W = 128``. The batch is cut
into ``_BATCH_CHUNK``-row calls so the prefetched keys and the output fit
SMEM at any batch size. ``interpret=None`` auto-detects like the other
kernels in this package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret


_GROUP = 8            # regions per DMA'd row group (the sublane tiling)
_BATCH_CHUNK = 4096   # batch rows per kernel call (SMEM budget)


def _find_kernel(G: int, W: int):
    def kernel(reg_ref, dhi_ref, dlo_ref, khi_ref, klo_ref, out_ref):
        # reg/dhi/dlo are the scalar-prefetch operands in SMEM; the key
        # refs hold the (G, W) row group that contains this row's region.
        i = pl.program_id(0)
        rows = jax.lax.broadcasted_iota(jnp.int32, (G, W), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (G, W), 1)
        # SMEM scalars are 32-bit signed, so the dst keys arrive as i32
        # bit patterns; the (u32) tile is bitcast to match, in-register.
        as_i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
        m = ((as_i32(khi_ref[...]) == dhi_ref[i])
             & (as_i32(klo_ref[...]) == dlo_ref[i])
             & (rows == reg_ref[i] % G))
        out_ref[i] = jnp.min(jnp.where(m, cols, W))

    return kernel


def _find_chunk(khi, klo, regs, dhi, dlo, interpret: bool) -> jax.Array:
    R, W = khi.shape
    G = min(_GROUP, R)
    B = regs.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((G, W), lambda i, reg, dh, dl: (reg[i] // G, 0)),
            pl.BlockSpec((G, W), lambda i, reg, dh, dl: (reg[i] // G, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        _find_kernel(G, W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        interpret=interpret,
    )(regs, dhi, dlo, khi, klo)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chain_find_depth(key_hi_r: jax.Array, key_lo_r: jax.Array,
                     region_ids: jax.Array, dst_hi: jax.Array,
                     dst_lo: jax.Array, *, interpret: bool | None = None
                     ) -> jax.Array:
    """Match ``dst`` keys against one region tile per batch row.

    ``key_hi_r``/``key_lo_r`` are the store's key lanes viewed as
    ``[n_regions, W]``; ``region_ids`` i32[B] picks each row's tile (must
    be pre-clipped to a valid region). Returns i32[B]: the in-region match
    position, or ``W`` when the key is absent from that tile.
    """
    interpret = resolve_interpret(interpret)
    B = dst_hi.shape[0]
    as_i32 = lambda x: jax.lax.bitcast_convert_type(
        x.astype(jnp.uint32), jnp.int32)
    khi, klo = key_hi_r.astype(jnp.uint32), key_lo_r.astype(jnp.uint32)
    regs = region_ids.astype(jnp.int32)
    dhi, dlo = as_i32(dst_hi), as_i32(dst_lo)
    return jnp.concatenate([
        _find_chunk(khi, klo, regs[lo:lo + _BATCH_CHUNK],
                    dhi[lo:lo + _BATCH_CHUNK], dlo[lo:lo + _BATCH_CHUNK],
                    interpret)
        for lo in range(0, B, _BATCH_CHUNK)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def chain_find(key_hi_r: jax.Array, key_lo_r: jax.Array, regs: jax.Array,
               dst_hi: jax.Array, dst_lo: jax.Array, active: jax.Array,
               *, interpret: bool | None = None) -> jax.Array:
    """Full chain scan: ``regs`` i32[B, max_chain] (-1 = no region at that
    depth) — one :func:`chain_find_depth` pass per depth, first hit wins.
    Returns the *global* slot (region * W + pos), or -1. Semantics are
    identical to the jnp reference ``stores._chain_find_jnp``."""
    R, W = key_hi_r.shape
    B, MC = regs.shape
    found = jnp.full((B,), -1, jnp.int32)
    for d in range(MC):
        col = regs[:, d]
        has = active & (col >= 0) & (found < 0)
        # early exit like the jnp reference: once every row is resolved (or
        # out of chain), the remaining depths skip their kernel launch —
        # steady-state chains are one region deep.
        pos = jax.lax.cond(
            jnp.any(has),
            lambda: chain_find_depth(key_hi_r, key_lo_r,
                                     jnp.where(col >= 0, col, 0),
                                     dst_hi, dst_lo, interpret=interpret),
            lambda: jnp.full((B,), W, jnp.int32))
        hit = has & (pos < W)
        found = jnp.where(hit, jnp.where(col >= 0, col, 0) * W + pos, found)
    return found
