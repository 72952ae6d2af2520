"""Count-min sketch store — the probabilistic point on the paper's
coverage↔memory tradeoff curve (§4.4).

"We can reduce memory consumption by only keeping track of
frequently-occurring query terms (above a threshold), but at the cost of
coverage." A count-min sketch inverts the tradeoff: every key is tracked
(full coverage of counts, within overestimation error) in O(d·w) memory
independent of the key cardinality — at the cost of not being enumerable
(it cannot drive ranking cycles alone; the engine uses it as a pre-filter
for query-likeness and as a memory-bounded heavy-hitter detector).

Supports the same exponential decay as the exact stores (multiply the whole
sketch — a dense elementwise op).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hashing import _mix32

_SALTS = jnp.array([0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F,
                    0x165667B1, 0xD3A2646C, 0xFD7046C5, 0xB55A4F09],
                   dtype=jnp.uint32)


class CountMinSketch(NamedTuple):
    table: jax.Array   # f32[depth, width]

    @property
    def depth(self) -> int:
        return self.table.shape[0]

    @property
    def width(self) -> int:
        return self.table.shape[1]


def make_sketch(depth: int = 4, width: int = 1 << 16) -> CountMinSketch:
    assert width & (width - 1) == 0
    assert depth <= _SALTS.shape[0]
    return CountMinSketch(jnp.zeros((depth, width), jnp.float32))


def _rows(sk_depth: int, width: int, key_hi, key_lo):
    """Per-depth bucket indices for a batch of keys -> i32[depth, B]."""
    idx = []
    for d in range(sk_depth):
        h = _mix32(key_hi ^ _SALTS[d]) ^ _mix32(key_lo * _SALTS[d])
        idx.append((h & jnp.uint32(width - 1)).astype(jnp.int32))
    return jnp.stack(idx)


@jax.jit
def sketch_update(sk: CountMinSketch, key_hi, key_lo, weights, valid
                  ) -> CountMinSketch:
    D, W = sk.table.shape
    idx = _rows(D, W, key_hi, key_lo)                 # [D, B]
    w = jnp.where(valid, weights, 0.0)
    table = sk.table
    for d in range(D):
        table = table.at[d, idx[d]].add(w)
    return CountMinSketch(table)


@jax.jit
def sketch_query(sk: CountMinSketch, key_hi, key_lo) -> jax.Array:
    D, W = sk.table.shape
    idx = _rows(D, W, key_hi, key_lo)
    vals = jnp.stack([sk.table[d, idx[d]] for d in range(D)])
    return jnp.min(vals, axis=0)


@jax.jit
def sketch_decay(sk: CountMinSketch, factor) -> CountMinSketch:
    return CountMinSketch(sk.table * factor)
