"""Backend-aware empirical kernel autotuner.

The paper's premise is that the engine must run "as fast as the hardware
allows" (§4) — but which implementation is fastest is a property of the
*backend*, not the code: the fused Pallas kernels win on a TPU and lose
badly under the CPU interpreter (re-entering XLA per grid step), and the
engine's large-batch throughput cliff is a function of the store's
conflict-resolve behaviour at the measured batch size. So instead of a
blind ``use_kernel: bool``, the tuner **measures** each hot-path candidate
pair on the running backend and records the winners in a serializable
:class:`~repro.core.plan.TunedPlan`.

Contract
--------

* :func:`tune` is the entry point: benchmark every hot path applicable to
  the config's layout — kernel vs jnp for ``score_gate``, ``bucket_topk``,
  ``region_rank``, ``chain_find``, ``decay_prune``, the ``score_gate``
  tile shape (``block_rows``), and the ingest dispatch-fusion width
  (``ingest_chunk``) — and return the winning plan.
* Results are cached on disk keyed by :func:`~repro.core.plan.shape_class`
  (backend + device kind + log2 capacities + layout + region width), one
  JSON per shape class, under ``$REPRO_AUTOTUNE_CACHE`` (default
  ``~/.cache/repro-autotune``). A cache hit returns the stored plan with
  NO re-benchmarking.
* Off a native-Pallas backend, kernel candidates that raise (Pallas
  unavailable under the interpreter) are recorded as failed and the jnp
  reference wins — tuning degrades gracefully to the all-jnp plan. On a
  native backend (TPU) a raising kernel is a bug, not a jnp win: the
  exception propagates, so a plan can never hide a kernel that does not
  compile on the device it was tuned for.
* Plans are **result-invariant** by construction: every candidate pair is
  property-tested bit-exact (``tests/test_autotune.py``), so the tuner can
  never change engine states or suggestion tables, only speed.

The plan rides ``EngineConfig.plan`` into every dispatch site (see the
kernel-dispatch table in ``repro/kernels/__init__``), rides snapshot meta
so a recovered engine keeps its tuning, and is surfaced live by
``SuggestFrontend.metrics()``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import ranking, stores
from ..core.decay import sweep_decay_prune
from ..core.plan import (HOT_PATH_OPS, JNP, KERNEL, TunedPlan,
                         default_region_width, shape_class)
from ..kernels import kernels_native

__all__ = ["tune", "tune_engine_config", "measure_plan", "cache_dir",
           "cache_path", "TunedPlan", "shape_class"]

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
# bumped whenever a candidate's code changes, so no plan measured on old
# kernels is served from the cache (2: TPU-compilable kernels)
CACHE_VERSION = 2

# score_gate tile-shape candidates (rows of 1024 slots per grid step).
# Measured on CPU-interpret the spread is ~11x across this range; on TPU
# the default 16 is near-flat but still worth confirming per shape.
BLOCK_ROWS_CANDIDATES = (4, 8, 16, 32, 64)

# ingest dispatch-fusion candidates, in quantum slices per lax.scan
# dispatch (0 = one dispatch per slice). Fusion never changes results —
# the scan body IS ingest_queries — so this is pure dispatch scheduling.
INGEST_FUSE_CANDIDATES = (0, 2, 4)


def cache_dir(override: Optional[str] = None) -> Path:
    if override is not None:
        return Path(override)
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro-autotune"


def cache_path(cfg, override: Optional[str] = None) -> Path:
    return cache_dir(override) / f"{shape_class(cfg)}.json"


def _time_us(fn: Callable[[], object], repeats: int) -> float:
    """Best-of-``repeats`` wall time of ``fn`` in µs (after one warmup
    call that also absorbs jit compilation)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# synthetic per-op workloads (shapes from cfg; content random but fixed)
# ---------------------------------------------------------------------------


def _rank_coefs(rk) -> Tuple[float, float, float, float]:
    return (rk.coef_condprob, rk.coef_pmi, rk.coef_llr, rk.coef_chi2)


def _score_lanes(cfg, key):
    C = cfg.cooc_capacity
    ks = jax.random.split(key, 8)
    u = lambda k: jax.random.uniform(k, (C,), jnp.float32, 0.0, 4.0)
    w_ab, w_a, w_b = u(ks[0]), u(ks[1]) + 1.0, u(ks[2]) + 1.0
    c_ab = jnp.ceil(u(ks[3]))
    c_a, c_b = c_ab + jnp.ceil(u(ks[4])), c_ab + jnp.ceil(u(ks[5]))
    ok = jax.random.uniform(ks[6], (C,)) < 0.7
    tw = jnp.sum(w_a)
    tc = jnp.sum(c_a)
    return w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc


def _score_gate_pair(cfg, key):
    """(kernel_fn(block_rows), jnp_fn) for the fused score+gate pass."""
    from ..kernels import ops as kops
    rk = cfg.rank
    lanes = _score_lanes(cfg, key)
    w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc = lanes
    coefs = _rank_coefs(rk)

    def kernel_fn(block_rows):
        return lambda: kops.score_gate(
            w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc, coefs=coefs,
            min_pair_weight=rk.min_pair_weight,
            min_src_weight=rk.min_src_weight,
            min_pair_count=rk.min_pair_count, block_rows=block_rows)

    @jax.jit
    def jnp_body(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc):
        ls = ranking.assoc_scores_jnp(w_ab, c_ab, w_a, w_b, c_a, c_b, tw, tc)
        score = ranking.combine_scores(rk, *ls)
        m = (ok & (w_ab >= rk.min_pair_weight) & (c_ab >= rk.min_pair_count)
             & (w_a >= rk.min_src_weight))
        return jnp.where(m, score, -jnp.inf)

    return kernel_fn, (lambda: jnp_body(*lanes))


def _bucket_topk_pair(cfg, key):
    from ..kernels import ops as kops
    rk = cfg.rank
    C, Q = cfg.cooc_capacity, cfg.query_capacity
    M = min(C, max(rk.top_k, int(C * min(rk.seg_arena_frac, 1.0))))
    R = min(Q, M, max(rk.source_cap(Q), 1))
    L = max(rk.bucket_rows, rk.top_k)
    grid = jnp.where(jax.random.uniform(key, (R, L)) < 0.8,
                     jax.random.uniform(jax.random.fold_in(key, 1), (R, L)),
                     -jnp.inf)
    K = rk.top_k
    jnp_fn = jax.jit(lambda g: jax.lax.top_k(g, K))
    return (lambda: kops.bucket_topk(grid, K)), (lambda: jnp_fn(grid))


def _region_rank_pair(cfg, key):
    from ..kernels import ops as kops
    rk = cfg.rank
    W = cfg.region_w
    C = cfg.cooc_capacity
    R = C // W
    ks = jax.random.split(key, 8)
    u = lambda k: jax.random.uniform(k, (R, W), jnp.float32, 0.0, 4.0)
    w_ab, w_a, w_b = u(ks[0]), u(ks[1]) + 1.0, u(ks[2]) + 1.0
    c_ab = jnp.ceil(u(ks[3]))
    c_a, c_b = c_ab + 1.0, c_ab + 1.0
    ok = jax.random.uniform(ks[4], (R, W)) < 0.7
    tw, tc = jnp.sum(w_a[:, 0]), jnp.sum(c_a[:, 0])
    K1 = min(rk.top_k, W)
    coefs = _rank_coefs(rk)

    def kernel_fn():
        return kops.region_rank(
            w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc, k=K1, coefs=coefs,
            min_pair_weight=rk.min_pair_weight,
            min_src_weight=rk.min_src_weight,
            min_pair_count=rk.min_pair_count)

    @jax.jit
    def jnp_body(w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc):
        ls = ranking.assoc_scores_jnp(w_ab, c_ab, w_a, w_b, c_a, c_b, tw, tc)
        score = ranking.combine_scores(rk, *ls)
        m = (ok & (w_ab >= rk.min_pair_weight) & (c_ab >= rk.min_pair_count)
             & (w_a >= rk.min_src_weight))
        g = jnp.where(m, score, -jnp.inf)
        vals, args = jax.lax.top_k(g, K1)
        return vals, args, jnp.sum(m.astype(jnp.int32), axis=1)

    args = (w_ab, c_ab, w_a, w_b, c_a, c_b, ok, tw, tc)
    return kernel_fn, (lambda: jnp_body(*args))


def _chain_find_pair(cfg, key):
    from ..kernels import ops as kops
    W = cfg.region_w
    R = cfg.cooc_capacity // W
    MC = cfg.region_chain
    B = min(4096, max(256, cfg.ingest_quantum or 1024))
    ks = jax.random.split(key, 5)
    khi = jax.random.randint(ks[0], (R, W), 1, 1 << 30).astype(jnp.uint32)
    klo = jax.random.randint(ks[1], (R, W), 1, 1 << 30).astype(jnp.uint32)
    regs = jax.random.randint(ks[2], (B, MC), 0, R).astype(jnp.int32)
    regs = jnp.where(jnp.arange(MC)[None, :] < 2, regs, -1)  # short chains
    pick_r = jnp.clip(regs[:, 0], 0, R - 1)
    pick_w = jax.random.randint(ks[3], (B,), 0, W)
    hit = jax.random.uniform(ks[4], (B,)) < 0.5           # ~half hits
    dhi = jnp.where(hit, khi[pick_r, pick_w], jnp.uint32(1))
    dlo = jnp.where(hit, klo[pick_r, pick_w], jnp.uint32(1))
    act = jnp.ones((B,), bool)
    jnp_fn = jax.jit(stores._chain_find_jnp)
    return (lambda: kops.chain_find(khi, klo, regs, dhi, dlo, act)), \
        (lambda: jnp_fn(khi, klo, regs, dhi, dlo, act))


def _decay_prune_pair(cfg, key):
    C = cfg.cooc_capacity
    tab = stores.make_table(C, {"weight": jnp.float32, "count": jnp.float32,
                                "last_tick": jnp.int32})
    ks = jax.random.split(key, 3)
    kh = jax.random.randint(ks[0], (C,), 0, 1 << 30).astype(jnp.uint32)
    live = jax.random.uniform(ks[1], (C,)) < 0.5
    kh = jnp.where(live, kh | jnp.uint32(1), jnp.uint32(0))
    w = jnp.where(live, jax.random.uniform(ks[2], (C,), jnp.float32, 0, 4),
                  0.0)
    tab = tab._replace(key_hi=kh, key_lo=kh,
                       lanes={"weight": w, "count": jnp.ceil(w),
                              "last_tick": jnp.zeros((C,), jnp.int32)})
    dt = jnp.int32(max(cfg.decay_every, 1))

    def mk(use_kernel):
        return lambda: sweep_decay_prune(tab, dt, cfg=cfg.decay,
                                         weight_lanes=("weight",),
                                         use_kernel=use_kernel)

    return mk(True), mk(False)


def _ingest_fuse_timings(cfg, repeats: int) -> Dict[int, float]:
    """Time k quantum slices per dispatch for each fusion candidate.

    Uses the real ingest path (``ingest_queries`` / ``ingest_queries_stack``)
    on a synthetic event stream, so the winner reflects actual dispatch +
    store-update cost at the configured quantum.
    """
    from ..core import engine as eng
    Q = cfg.ingest_quantum
    if Q <= 0:
        return {0: 0.0}
    n = max(INGEST_FUSE_CANDIDATES[-1], 1)
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    B = n * Q
    u32 = lambda k: jax.random.randint(k, (B,), 1, 1 << 30).astype(jnp.uint32)
    # ~Q/8 distinct sessions so the session window actually forms pairs
    sess = jax.random.randint(ks[0], (B,), 0, max(Q // 8, 1))
    s_hi = (sess + 1).astype(jnp.uint32)
    s_lo = (sess.astype(jnp.uint32) * jnp.uint32(2654435761)
            + jnp.uint32(1))
    q_hi, q_lo = u32(ks[1]), u32(ks[2])
    src = jax.random.randint(ks[3], (B,), 0, len(cfg.source_weights)
                             ).astype(jnp.int32)
    valid = jnp.ones((B,), bool)
    arrs = (s_hi, s_lo, q_hi, q_lo, src, valid)
    state0 = eng.init_state(cfg)

    out: Dict[int, float] = {}
    for k_fuse in INGEST_FUSE_CANDIDATES:
        kk = max(k_fuse, 1)
        stacked = tuple(a.reshape(n // kk, kk, Q) for a in arrs) \
            if n % kk == 0 else None
        if stacked is None:
            continue

        def run(k_fuse=k_fuse, kk=kk, stacked=stacked):
            st = state0
            for i in range(n // kk):
                sub = tuple(a[i] for a in stacked)
                if k_fuse == 0:
                    st = eng.ingest_queries(st, *(x[0] for x in sub),
                                            cfg=cfg)
                else:
                    st = eng.ingest_queries_stack(st, *sub, cfg=cfg)
            return st

        out[k_fuse] = _time_us(run, repeats)
    return out


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------


def measure_plan(cfg, *, repeats: int = 3, tune_ingest: bool = True
                 ) -> Tuple[TunedPlan, Dict[str, Optional[float]]]:
    """Benchmark every applicable hot-path candidate pair and build the
    winning plan. Returns ``(plan, timings_us)`` where timings record every
    candidate measured (``None`` = the kernel candidate raised)."""
    timings: Dict[str, Optional[float]] = {}
    choices: Dict[str, str] = {op: JNP for op in HOT_PATH_OPS}
    key = jax.random.PRNGKey(0)
    region = cfg.region_cooc

    native = kernels_native()

    def bench(name: str, fn) -> Optional[float]:
        try:
            t = _time_us(fn, repeats)
        except Exception:
            if native:        # the device compiles kernels: never hide one
                raise
            timings[name] = None              # interpreter-only backend
            return None
        timings[name] = t
        return t

    # -- score_gate (hash-layout ranking prologue) + its tile shape --
    block_rows = 16
    if not region:
        kfn, jfn = _score_gate_pair(cfg, jax.random.fold_in(key, 1))
        rows = cfg.cooc_capacity // 1024
        cands = [b for b in BLOCK_ROWS_CANDIDATES
                 if b <= rows and rows % b == 0] or [min(16, rows)]
        best_k, best_b = None, cands[0]
        for b in cands:
            t = bench(f"score_gate:kernel:blk{b}", kfn(b))
            if t is not None and (best_k is None or t < best_k):
                best_k, best_b = t, b
        t_j = bench("score_gate:jnp", jfn)
        block_rows = best_b
        if best_k is not None and t_j is not None and best_k < t_j:
            choices["score_gate"] = KERNEL

        kfn, jfn = _bucket_topk_pair(cfg, jax.random.fold_in(key, 2))
        t_k = bench("bucket_topk:kernel", kfn)
        t_j = bench("bucket_topk:jnp", jfn)
        if t_k is not None and t_j is not None and t_k < t_j:
            choices["bucket_topk"] = KERNEL
    else:
        # -- region layout: the fused region pass + the chain find --
        kfn, jfn = _region_rank_pair(cfg, jax.random.fold_in(key, 3))
        t_k = bench("region_rank:kernel", kfn)
        t_j = bench("region_rank:jnp", jfn)
        if t_k is not None and t_j is not None and t_k < t_j:
            choices["region_rank"] = KERNEL

        kfn, jfn = _chain_find_pair(cfg, jax.random.fold_in(key, 4))
        t_k = bench("chain_find:kernel", kfn)
        t_j = bench("chain_find:jnp", jfn)
        if t_k is not None and t_j is not None and t_k < t_j:
            choices["chain_find"] = KERNEL

    # -- decay/prune sweep (both layouts sweep the qstore; the hash layout
    # sweeps the cooc store too) --
    kfn, jfn = _decay_prune_pair(cfg, jax.random.fold_in(key, 5))
    t_k = bench("decay_prune:kernel", kfn)
    t_j = bench("decay_prune:jnp", jfn)
    if t_k is not None and t_j is not None and t_k < t_j:
        choices["decay_prune"] = KERNEL

    # -- ingest dispatch fusion --
    ingest_chunk = 0
    if tune_ingest and cfg.ingest_quantum > 0:
        fuse = _ingest_fuse_timings(cfg, repeats)
        for k_fuse, t in fuse.items():
            timings[f"ingest_fuse:{k_fuse}"] = t
        if fuse:
            best = min(fuse, key=fuse.get)
            ingest_chunk = best * cfg.ingest_quantum if best > 0 else 0

    plan = TunedPlan(**choices, score_block_rows=block_rows,
                     ingest_chunk=ingest_chunk,
                     backend=jax.default_backend(),
                     shape_class=shape_class(cfg))
    return plan, timings


def tune(cfg, *, cache: Optional[str] = None, force: bool = False,
         repeats: int = 3, tune_ingest: bool = True) -> TunedPlan:
    """Return the tuned plan for ``cfg`` — from the shape-class disk cache
    when present (no re-benchmark), measured and cached otherwise."""
    path = cache_path(cfg, cache)
    if not force and path.exists():
        try:
            rec = json.loads(path.read_text())
            if rec.get("version") == CACHE_VERSION:
                return TunedPlan.from_json(rec["plan"])
        except (ValueError, KeyError):
            pass                               # corrupt cache: re-measure
    plan, timings = measure_plan(cfg, repeats=repeats,
                                 tune_ingest=tune_ingest)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(
        {"version": CACHE_VERSION, "shape_class": shape_class(cfg),
         "backend": jax.default_backend(), "plan": plan.to_json(),
         "timings_us": timings}, indent=2, sort_keys=True))
    os.replace(tmp, path)
    return plan


def tune_engine_config(cfg, **kw):
    """``tune`` + attach: returns ``cfg`` with the winning plan installed
    (``EngineConfig.plan``; its ``__post_init__`` forwards it to the
    ranking config)."""
    return dataclasses.replace(cfg, plan=tune(cfg, **kw))
