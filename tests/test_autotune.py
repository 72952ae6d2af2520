"""Autotuner contract: plans change performance, never results.

Covers the four ISSUE-9 test obligations: plan serialization round-trips
(EngineConfig + snapshot meta), cache-hit determinism (same shape class ->
same plan, no re-benchmark), graceful all-jnp fallback when Pallas is
unavailable, and bit-exact engine parity between any two plans — plus the
derived-region-width mapping and the shared interpret resolver.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.engine import EngineConfig, SearchAssistanceEngine
from repro.core.plan import (JNP_PLAN, TunedPlan, all_kernel_plan,
                             default_region_width, shape_class)
from repro.data.stream import StreamConfig, SyntheticStream
from repro.kernels import resolve_interpret
from repro.launch import autotune


def _cfg(**kw):
    base = dict(query_capacity=1 << 10, cooc_capacity=1 << 12,
                session_capacity=1 << 10, session_window=4,
                decay_every=4, rank_every=6)
    base.update(kw)
    return EngineConfig(**base)


def _run(cfg, ticks=8, qpt=96):
    stream = SyntheticStream(StreamConfig(vocab_size=256, n_users=80,
                                          queries_per_tick=qpt,
                                          tweets_per_tick=0), seed=5)
    eng = SearchAssistanceEngine(cfg)
    for t in range(ticks):
        ev, _ = stream.gen_tick(t)
        eng.step(ev)
    return eng


def _states_equal(a, b) -> bool:
    leaves_a, leaves_b = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b))


# ---------------------------------------------------------------------------
# plan object + serialization
# ---------------------------------------------------------------------------


def test_plan_roundtrip_json():
    plan = all_kernel_plan(score_block_rows=32, ingest_chunk=8192,
                           backend="cpu", shape_class="cpu-x-q10-c12-s10")
    assert TunedPlan.from_json(plan.to_json()) == plan
    assert TunedPlan.loads(plan.dumps()) == plan
    assert plan.uses_kernel("score_gate") and not JNP_PLAN.uses_kernel(
        "score_gate")


def test_plan_rejects_unknown_variant():
    with pytest.raises(ValueError):
        TunedPlan(score_gate="cuda")


def test_plan_propagates_to_rank_config():
    plan = all_kernel_plan()
    cfg = _cfg(plan=plan)
    assert cfg.rank.plan == plan
    assert cfg.kernel_on("decay_prune") and cfg.rank.kernel_on("score_gate")
    # legacy bool still wins over the plan at every site
    forced = _cfg(plan=plan, use_kernel=False)
    assert not forced.kernel_on("decay_prune")


def test_plan_rides_snapshot_meta(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    plan = TunedPlan(decay_prune="kernel", ingest_chunk=8192,
                     backend="cpu")
    eng = _run(_cfg(plan=plan), ticks=4)
    ckpt = CheckpointManager(str(tmp_path))
    eng.save_snapshot(ckpt)
    # restore WITHOUT a plan: the snapshot's tuning must re-attach
    eng2, _ = SearchAssistanceEngine.restore_from_snapshot(_cfg(), ckpt)
    assert eng2.cfg.plan == plan
    assert _states_equal(eng.state, eng2.state)
    # an explicitly configured plan wins over the snapshot's
    other = TunedPlan()
    eng3, _ = SearchAssistanceEngine.restore_from_snapshot(
        _cfg(plan=other), ckpt)
    assert eng3.cfg.plan == other


def test_metrics_surface_tuned_variants(tmp_path):
    from repro.distributed.fault_tolerance import CheckpointManager
    from repro.serving.serve import SuggestFrontend, pack_suggestions
    plan = TunedPlan(bucket_topk="kernel", score_block_rows=32,
                     ingest_chunk=8192)
    eng = _run(_cfg(plan=plan), ticks=6)
    rt_dir = str(tmp_path / "rt")
    CheckpointManager(rt_dir).save(
        5, pack_suggestions(eng.suggestions),
        meta={"tick": 5, "plan": plan.to_json()})
    f = SuggestFrontend(rt_dir)
    f.poll()
    m = f.metrics()
    assert m["tuned_variants"]["bucket_topk"] == "kernel"
    assert m["tuned_variants"]["ingest_chunk"] == 8192
    # an untuned backend surfaces None, not a crash
    plain = str(tmp_path / "plain")
    CheckpointManager(plain).save(1, pack_suggestions(eng.suggestions),
                                  meta={"tick": 1})
    f2 = SuggestFrontend(plain)
    f2.poll()
    assert f2.metrics()["tuned_variants"] is None


# ---------------------------------------------------------------------------
# the tuner: cache determinism + graceful fallback
# ---------------------------------------------------------------------------


def test_cache_hit_determinism(tmp_path, monkeypatch):
    cfg = _cfg()
    p1 = autotune.tune(cfg, cache=str(tmp_path), repeats=1,
                       tune_ingest=False)
    assert p1.shape_class == shape_class(cfg)

    def boom(*a, **k):
        raise AssertionError("cache hit must not re-benchmark")

    monkeypatch.setattr(autotune, "measure_plan", boom)
    p2 = autotune.tune(cfg, cache=str(tmp_path), repeats=1,
                       tune_ingest=False)
    assert p2 == p1
    # a different shape class misses the cache (and here: re-measures)
    with pytest.raises(AssertionError):
        autotune.tune(_cfg(cooc_capacity=1 << 13), cache=str(tmp_path),
                      repeats=1, tune_ingest=False)


def test_graceful_fallback_without_pallas(monkeypatch):
    from repro.kernels import ops as kops

    def boom(*a, **k):
        raise RuntimeError("no Pallas on this backend")

    for fn in ("score_gate", "bucket_topk", "region_rank", "chain_find",
               "decay_prune_table"):
        monkeypatch.setattr(kops, fn, boom)
    # drop compiled entries that already traced the real kernels (the
    # decay sweep is jitted with static use_kernel): a cache hit would
    # skip re-tracing and never reach the patched functions
    jax.clear_caches()
    for layout in ("hash", "region"):
        plan, timings = autotune.measure_plan(
            _cfg(cooc_layout=layout), repeats=1, tune_ingest=False)
        assert plan.variants() == {**JNP_PLAN.variants(),
                                   "score_block_rows":
                                       plan.score_block_rows}
        assert all(v is None for k, v in timings.items()
                   if ":kernel" in k)
        assert all(v is not None for k, v in timings.items()
                   if k.endswith(":jnp"))


def test_native_backend_kernel_failure_raises(monkeypatch):
    """On a backend that compiles kernels natively, a raising kernel
    candidate is a bug the tuner must surface, never a silent jnp win."""
    from repro.kernels import ops as kops

    def boom(*a, **k):
        raise RuntimeError("kernel refused by the TPU compiler")

    monkeypatch.setattr(kops, "score_gate", boom)
    monkeypatch.setattr(autotune, "kernels_native", lambda: True)
    with pytest.raises(RuntimeError, match="refused"):
        autotune.measure_plan(_cfg(), repeats=1, tune_ingest=False)


# ---------------------------------------------------------------------------
# plans change performance only — engine results are plan-invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["hash", "region"])
def test_engine_state_bit_exact_across_plans(layout):
    plans = [None, JNP_PLAN,
             all_kernel_plan(),
             all_kernel_plan(score_block_rows=2)]
    engines = [_run(_cfg(cooc_layout=layout, plan=p)) for p in plans]
    for eng in engines[1:]:
        assert _states_equal(engines[0].state, eng.state)
    if layout == "region":
        # suggestion tables too (hash-layout kernel scores carry ~1e-3
        # fusion-rounding diffs vs jnp; states are exact in both layouts)
        for eng in engines[1:]:
            assert eng.suggestions == engines[0].suggestions


def test_ingest_chunking_bit_exact():
    """Quantum cut points are plan-independent; fusion width changes the
    dispatch count only — a ragged 3.5-quantum batch lands bit-identical
    under no plan, unfused, and fused-by-2 plans."""
    plans = [None, TunedPlan(ingest_chunk=0), TunedPlan(ingest_chunk=128)]
    engines = [_run(_cfg(ingest_quantum=64, plan=p), ticks=3, qpt=209)
               for p in plans]
    for eng in engines[1:]:
        assert _states_equal(engines[0].state, eng.state)


# ---------------------------------------------------------------------------
# satellites: derived region width + shared interpret resolver
# ---------------------------------------------------------------------------


def test_default_region_width_mapping():
    assert {c: default_region_width(1 << c) for c in (14, 16, 18, 20, 22)} \
        == {14: 8, 16: 16, 18: 32, 20: 64, 22: 128}
    assert default_region_width(1 << 10) == 8      # floor
    assert default_region_width(1 << 30) == 128    # ceiling
    assert _cfg(cooc_layout="region",
                cooc_capacity=1 << 16).region_w == 16
    assert _cfg(cooc_layout="region", cooc_capacity=1 << 16,
                region_width=8).region_w == 8      # explicit override wins


def test_resolve_interpret():
    native = jax.default_backend() in ("tpu",)
    assert resolve_interpret(None) == (not native)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
