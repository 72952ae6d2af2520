"""The hot-path Pallas kernels compile for a TPU v5e at deployment shapes.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets it compile — and refuse — kernels for the chip.
Interpret mode cannot show what the compiler rejects (block shapes off the
(8, 128) tiling, rank-1 per-block outputs, layouts XLA and Mosaic disagree
on), so each kernel the tuned plan can dispatch is compiled here at one
chip's deployment size (cooc 2^25, query 2^22, region width 128) and must
lower to a ``tpu_custom_call``. Nothing runs: this proves compilation only.

The topology is described inside a fixture, never at import (one process at
a time may load the TPU library), and the persistent compilation cache is
off around the compiles (entries written for a described chip cannot be
read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

C = 1 << 25              # cooc slots of one chip's engine
Q = 1 << 22              # query slots
W = 128                  # region width of the TPU deployments
B = 1 << 14              # pairs per insert batch (4096 events x 4)
K = 8
COEFS = (1.0, 0.15, 0.02, 0.0)
GATES = dict(min_pair_weight=0.25, min_src_weight=0.5, min_pair_count=1.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _score_gate(S):
    from repro.kernels.topk_select import score_gate
    fn = lambda *a: score_gate(*a, coefs=COEFS, half_life=36.0,
                               interpret=False, **GATES)
    return fn, [S((C,))] * 7 + [S((C,), jnp.int32), S(()), S(()),
                                S((), jnp.int32)]


def _bucket_topk(S):
    from repro.kernels.topk_select import bucket_topk
    # the segmented rank cycle's grid: one row per query slot, L = 64
    return (lambda g: bucket_topk(g, K, interpret=False)), [S((Q, 64))]


def _decay_prune_multi(S):
    from repro.kernels.decay_prune import decay_prune_multi
    # the hash cooc store: weight decays; count, last_tick, endpoints clear
    fn = lambda kh, kl, w, c, lt, a, b, d, e, f, t: decay_prune_multi(
        kh, kl, (w,), (c, lt, a, b, d, e), f, t, interpret=False)
    u32 = S((C,), jnp.uint32)
    return fn, [u32, u32, S((C,)), S((C,)), S((C,), jnp.int32),
                u32, u32, u32, u32, S(()), S(())]


def _region_rank(S):
    from repro.kernels.topk_select import region_rank
    fn = lambda *a: region_rank(*a, k=K, coefs=COEFS, half_life=36.0,
                                interpret=False, **GATES)
    R = C // W
    return fn, [S((R, W))] * 7 + [S((R, W), jnp.int32), S(()), S(()),
                                  S((), jnp.int32)]


def _chain_find(S):
    from repro.kernels.region_probe import chain_find
    fn = lambda *a: chain_find(*a, interpret=False)
    R = C // W
    return fn, [S((R, W), jnp.uint32), S((R, W), jnp.uint32),
                S((B, 8), jnp.int32), S((B,), jnp.uint32),
                S((B,), jnp.uint32), S((B,), bool)]


KERNELS = {"score_gate": _score_gate, "bucket_topk": _bucket_topk,
           "decay_prune_multi": _decay_prune_multi,
           "region_rank_w128": _region_rank, "chain_find_w128": _chain_find}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](S)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
