"""The search assistance engine (paper §4.2–§4.3).

Each backend instance consists of
  * the **stats collector** — consumes the query hose and the firehose
    (here: micro-batched event arrays from ``data/stream.py``),
  * three **in-memory stores** (``stores.py``),
  * **rankers** — periodic ranking cycles over the stores (``ranking.py``),
plus the periodic **decay/prune cycles** and persistence hooks.

The data flow mirrors §4.3 exactly:

Query path (per query event):
  1. query statistics store: raw count + source-weighted score update,
  2. sessions store: append to the session's sliding window,
  3. a cooccurrence is formed with each previous query in the session.

Tweet path (per tweet): n-grams that are "query-like" (observed often enough
as standalone queries) are processed like the query path, with the tweet
itself as the session (all ordered pairs among its query-like n-grams).

Decay/prune cycles and ranking cycles run at configurable tick cadences.

Under the lazy decay policy (``DecayConfig.policy == "lazy"``) the
per-``decay_every`` full sweep disappears entirely: reads (ranking, lookup)
apply the decayed view per row, writes rebase-then-add, and only a
prune-only sweep runs, every ``prune_every`` ticks (see ``decay.py``).

Durability (paper §4.2): the engine itself is deliberately volatile — "the
importance of individual messages decreases over time, so losing a little
bit of state is tolerable ... a (re)started instance can rewind to an
earlier point in the [fire]hose and consume messages at a faster rate than
real time to catch up to the present". :func:`ingest_many` is the catch-up
primitive: one ``lax.scan`` over a stack of logged micro-batches (including
the in-scan decay/prune maintenance at the exact live cadences), one device
dispatch per chunk instead of one per tick. ``streaming/`` provides the
durable log and the replay controller built on it; snapshots ride on
``distributed/fault_tolerance.CheckpointManager`` with the log offset
recorded in the manifest (snapshot = checkpoint + log offset). Snapshots
may be *incremental*: a manager with ``full_interval > 1`` writes delta
checkpoints (changed store slots only) chained to the last full one, which
shrinks the write volume enough to snapshot ~4x more often — and with the
cadence, the replay tail a restart must cover. The whole serving stack
(rt + background engine + interpolation, ``core/background.py``) recovers
through the same path: ``streaming.replay.recover_service``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import ranking, stores
from .decay import (DecayConfig, prune_sweep, region_decay_sweep,
                    region_prune_sweep, sweep_decay_prune)
from .hashing import combine_fp_device, split_fp
from .plan import TunedPlan, default_region_width
from .ranking import RankConfig, SuggestionTable
from .stores import HashTable, RegionTable, SessionTable


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    # store capacities (powers of two)
    query_capacity: int = 1 << 16
    cooc_capacity: int = 1 << 18
    session_capacity: int = 1 << 15
    session_window: int = 5
    probe_rounds: int = 16
    # source weighting (paper §4.2: typed > related click > hashtag click)
    source_weights: Tuple[float, ...] = (1.0, 0.5, 0.7)
    tweet_weight: float = 0.25
    min_querylike_count: float = 3.0   # tweet n-gram must be a real query
    max_tweet_grams: int = 16
    # cycles (in ticks; a tick is one micro-batch ~ cfg.tick_seconds of data)
    decay_every: int = 6
    rank_every: int = 30               # ~5 sim-minutes at 10 s ticks (§2.3)
    # lazy decay policy only: full sweeps leave the per-``decay_every`` path
    # entirely (reads decay themselves); a prune-only sweep reclaims slots
    # at this much longer cadence. Tuned via the (prune_every, decay_every)
    # sweeps in bench_churn/bench_memory_coverage: suggestion churn and
    # coverage are cadence-INVARIANT under the lazy policy (read-time decay
    # is exact), so the cadence only trades live-slot load / probe-failure
    # drops against sweep cost — 24 matches 48's quality with lower table
    # load (0.24 vs 0.31 live at the sweep's pressure point) and ~7x fewer
    # drops under capacity pressure.
    prune_every: int = 24
    session_ttl: int = 360
    decay: DecayConfig = DecayConfig()
    rank: RankConfig = RankConfig()
    # Legacy kernel override: None (default) defers each hot path to the
    # tuned ``plan`` below; an explicit bool forces every store/decay hot
    # path to its kernel (True) or jnp (False) variant regardless of plan.
    use_kernel: Optional[bool] = None
    # The measured per-hot-path dispatch plan (``core/plan.TunedPlan``,
    # built by ``launch/autotune``). None = all-jnp reference dispatch.
    # Rides snapshot meta so a recovered engine keeps its tuning. Plans are
    # result-invariant: any two plans produce bit-exact engine states.
    plan: Optional[TunedPlan] = None
    # The semantic ingest slice: step()/ingest_many ALWAYS break a query
    # micro-batch larger than this into sequential quantum-sized slices
    # (plan-INDEPENDENT, so tuning cannot change results; the plan's
    # ``ingest_chunk`` only fuses quantum slices into one dispatch). This
    # is the large-batch-cliff fix: insert_accumulate's conflict-resolve
    # rounds degrade superlinearly past ~4k events. 0 disables slicing.
    ingest_quantum: int = 4096
    # cooccurrence-store layout: "hash" = open addressing keyed by the pair
    # fingerprint; "region" = source-major region layout (fixed-width
    # per-source regions, chain directory indexed by qstore slot — see
    # stores.RegionTable). The region layout makes every ranking bucket a
    # pure reshape and drops the four endpoint lanes from the store.
    cooc_layout: str = "hash"
    # pairs per region; None derives from cooc capacity via
    # ``plan.default_region_width`` ({2^16: 16, 2^18: 32, 2^20: 64} — read
    # it through ``region_w``). Real-TPU deployments want 128.
    region_width: Optional[int] = None
    region_chain: int = 8              # max spill-chain regions per source

    def __post_init__(self):
        if self.cooc_layout not in ("hash", "region"):
            raise ValueError(
                f"unknown cooc_layout {self.cooc_layout!r} "
                f"(expected 'hash' or 'region')")
        # the ranking hot paths read the plan off RankConfig; attach it so
        # callers only ever set EngineConfig.plan. An explicitly planned
        # RankConfig wins (it was set on purpose).
        if self.plan is not None and self.rank.plan is None:
            object.__setattr__(
                self, "rank", dataclasses.replace(self.rank, plan=self.plan))

    @property
    def lazy_decay(self) -> bool:
        return self.decay.policy == "lazy"

    @property
    def region_cooc(self) -> bool:
        return self.cooc_layout == "region"

    @property
    def region_w(self) -> int:
        """Effective region width (explicit override or capacity-derived)."""
        if self.region_width is not None:
            return self.region_width
        return default_region_width(self.cooc_capacity)

    def kernel_on(self, op: str) -> bool:
        """Kernel-vs-jnp resolution for one hot path: the legacy
        ``use_kernel`` bool wins; else the tuned plan; else jnp."""
        if self.use_kernel is not None:
            return self.use_kernel
        if self.plan is not None:
            return self.plan.uses_kernel(op)
        return False


class EngineState(NamedTuple):
    qstore: HashTable
    cooc: HashTable
    sessions: SessionTable
    tick: jax.Array  # i32


def make_cooc_store(cfg: EngineConfig, capacity: Optional[int] = None):
    """The cooccurrence store under ``cfg.cooc_layout`` (``capacity``
    overrides ``cfg.cooc_capacity`` — the sharded engine divides it)."""
    cap = capacity if capacity is not None else cfg.cooc_capacity
    if cfg.region_cooc:
        return stores.make_region_table(
            cap, cfg.region_w, cfg.query_capacity, cfg.region_chain, {
                "weight": jnp.float32, "count": jnp.float32,
                "last_tick": jnp.int32})
    return stores.make_table(cap, {
        "weight": jnp.float32, "count": jnp.float32, "last_tick": jnp.int32,
        "src_hi": jnp.uint32, "src_lo": jnp.uint32,
        "dst_hi": jnp.uint32, "dst_lo": jnp.uint32,
    })


def init_state(cfg: EngineConfig) -> EngineState:
    qstore = stores.make_table(cfg.query_capacity, {
        "weight": jnp.float32, "count": jnp.float32, "last_tick": jnp.int32,
    })
    cooc = make_cooc_store(cfg)
    sessions = stores.make_session_table(cfg.session_capacity, cfg.session_window)
    return EngineState(qstore, cooc, sessions, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# jitted step functions
# ---------------------------------------------------------------------------

_Q_MODES = (("weight", "add"), ("count", "add"), ("last_tick", "set"))
_C_MODES = (("weight", "add"), ("count", "add"), ("last_tick", "set"),
            ("src_hi", "set"), ("src_lo", "set"),
            ("dst_hi", "set"), ("dst_lo", "set"))
_R_MODES = _Q_MODES   # region layout: endpoints live in keys/directory


def cooc_insert_pairs(cooc, qstore: HashTable, src_hi, src_lo, dst_hi,
                      dst_lo, w_pair, valid, tick, cfg: EngineConfig, dkw):
    """Layout dispatch for one micro-batch of (src -> dst) pair updates —
    shared by the query path, the tweet path and the sharded engine."""
    P = src_hi.shape[0]
    count = jnp.ones((P,), jnp.float32)
    lt = jnp.full((P,), tick, jnp.int32)
    if cfg.region_cooc:
        return stores.region_insert_accumulate(
            cooc, qstore, src_hi, src_lo, dst_hi, dst_lo,
            {"weight": w_pair, "count": count, "last_tick": lt},
            valid, modes=_R_MODES, probe_rounds=cfg.probe_rounds,
            use_kernel=cfg.use_kernel, plan=cfg.plan, **dkw)
    p_hi, p_lo = combine_fp_device(src_hi, src_lo, dst_hi, dst_lo)
    return stores.insert_accumulate(
        cooc, p_hi, p_lo,
        {"weight": w_pair, "count": count, "last_tick": lt,
         "src_hi": src_hi, "src_lo": src_lo,
         "dst_hi": dst_hi, "dst_lo": dst_lo},
        valid, modes=_C_MODES, probe_rounds=cfg.probe_rounds, **dkw)


@partial(jax.jit, static_argnames=("cfg",))
def ingest_queries(
    state: EngineState,
    sess_hi: jax.Array, sess_lo: jax.Array,
    q_hi: jax.Array, q_lo: jax.Array,
    src: jax.Array, valid: jax.Array,
    *, cfg: EngineConfig,
) -> EngineState:
    """The query path of §4.3 for one micro-batch."""
    sw = jnp.asarray(cfg.source_weights, jnp.float32)
    w = sw[jnp.clip(src, 0, len(cfg.source_weights) - 1)]
    B = q_hi.shape[0]
    tick_vec = jnp.full((B,), state.tick, jnp.int32)
    # lazy policy: rebase-on-write so refreshing last_tick never un-decays
    dkw = dict(decay_cfg=cfg.decay, now=state.tick) if cfg.lazy_decay else {}

    with jax.named_scope("ingest.qstore"):
        qstore = stores.insert_accumulate(
            state.qstore, q_hi, q_lo,
            {"weight": w, "count": jnp.ones((B,), jnp.float32),
             "last_tick": tick_vec},
            valid, modes=_Q_MODES, probe_rounds=cfg.probe_rounds, **dkw)

    with jax.named_scope("ingest.sessions"):
        sessions, pairs = stores.update_sessions(
            state.sessions, sess_hi, sess_lo, q_hi, q_lo, src, state.tick,
            valid, probe_rounds=cfg.probe_rounds)

    with jax.named_scope("ingest.cooc_insert"):
        # pair weight: geometric mean of the two interaction-source weights
        w_src = sw[jnp.clip(pairs.src_code, 0, len(cfg.source_weights) - 1)]
        w_dst = sw[jnp.clip(pairs.dst_code, 0, len(cfg.source_weights) - 1)]
        w_pair = jnp.sqrt(w_src * w_dst)
        cooc = cooc_insert_pairs(state.cooc, qstore, pairs.src_hi,
                                 pairs.src_lo, pairs.dst_hi, pairs.dst_lo,
                                 w_pair, pairs.valid, state.tick, cfg, dkw)

    return EngineState(qstore, cooc, sessions, state.tick)


def quantum_slices(B: int, quantum: int) -> List[Tuple[int, int]]:
    """THE statement of where an oversized query micro-batch is cut.

    ``EngineConfig.ingest_quantum`` is semantic: slice boundaries depend
    only on (B, quantum) — never on the tuned plan — so live ``step()``,
    the replay scan and every plan produce identical ingest sequences.
    """
    if quantum <= 0 or B <= quantum:
        return [(0, B)]
    return [(off, min(off + quantum, B)) for off in range(0, B, quantum)]


@partial(jax.jit, static_argnames=("cfg",))
def ingest_queries_stack(state: EngineState, sess_hi, sess_lo, q_hi, q_lo,
                         src, valid, *, cfg: EngineConfig) -> EngineState:
    """K same-tick quantum slices (leading dim K) in ONE device dispatch:
    a ``lax.scan`` whose body is exactly :func:`ingest_queries`, so the
    result is bit-identical to K separate dispatches — the plan's
    ``ingest_chunk`` buys dispatch amortization only."""
    def body(st, xs):
        return ingest_queries(st, *xs, cfg=cfg), None

    state, _ = jax.lax.scan(body, state,
                            (sess_hi, sess_lo, q_hi, q_lo, src, valid))
    return state


@partial(jax.jit, static_argnames=("cfg",))
def ingest_tweets(
    state: EngineState,
    g_hi: jax.Array, g_lo: jax.Array,   # [T, G]
    valid: jax.Array,                    # [T]
    *, cfg: EngineConfig,
) -> EngineState:
    """The tweet path of §4.3 for one micro-batch of tweets."""
    T, G = g_hi.shape
    flat_hi, flat_lo = g_hi.reshape(-1), g_lo.reshape(-1)
    vals, found, _ = stores.lookup(state.qstore, flat_hi, flat_lo,
                                   probe_rounds=cfg.probe_rounds)
    querylike = (found & (vals["count"] >= cfg.min_querylike_count)
                 & valid[:, None].repeat(G, 1).reshape(-1))
    B = T * G
    tick_vec = jnp.full((B,), state.tick, jnp.int32)
    w = jnp.full((B,), cfg.tweet_weight, jnp.float32)
    dkw = dict(decay_cfg=cfg.decay, now=state.tick) if cfg.lazy_decay else {}
    qstore = stores.insert_accumulate(
        state.qstore, flat_hi, flat_lo,
        {"weight": w, "count": jnp.ones((B,), jnp.float32), "last_tick": tick_vec},
        querylike, modes=_Q_MODES, probe_rounds=cfg.probe_rounds, **dkw)

    # all ordered pairs among query-like grams of the same tweet
    ql = querylike.reshape(T, G)
    src_hi = jnp.broadcast_to(g_hi[:, :, None], (T, G, G)).reshape(-1)
    src_lo = jnp.broadcast_to(g_lo[:, :, None], (T, G, G)).reshape(-1)
    dst_hi = jnp.broadcast_to(g_hi[:, None, :], (T, G, G)).reshape(-1)
    dst_lo = jnp.broadcast_to(g_lo[:, None, :], (T, G, G)).reshape(-1)
    ok = (ql[:, :, None] & ql[:, None, :]).reshape(-1)
    same = (src_hi == dst_hi) & (src_lo == dst_lo)
    ok = ok & ~same
    P = src_hi.shape[0]
    cooc = cooc_insert_pairs(
        state.cooc, qstore, src_hi, src_lo, dst_hi, dst_lo,
        jnp.full((P,), cfg.tweet_weight, jnp.float32), ok, state.tick,
        cfg, dkw)
    return EngineState(qstore, cooc, state.sessions, state.tick)


@partial(jax.jit, static_argnames=("cfg",))
def decay_cycle(state: EngineState, dticks: jax.Array, *, cfg: EngineConfig
                ) -> Tuple[EngineState, Dict[str, jax.Array]]:
    """Decay/prune cycle (§4.3): decay all weights, prune small entries and
    stale sessions. Runs every ``decay_every`` ticks under the (paper
    faithful) eager "sweep" policy only."""
    qstore, q_live, q_tot = sweep_decay_prune(
        state.qstore, dticks, cfg=cfg.decay, weight_lanes=("weight",),
        use_kernel=cfg.kernel_on("decay_prune"))
    stats: Dict[str, jax.Array] = {"q_live": q_live, "q_total_w": q_tot}
    if cfg.region_cooc:
        # region maintenance validates chains against the post-sweep
        # qstore, so chains of just-pruned sources free immediately.
        cooc, c_live, c_tot, c_rec = region_decay_sweep(
            state.cooc, qstore, dticks, cfg=cfg.decay)
        stats["c_reclaimed"] = c_rec
        stats["c_free_regions"] = cooc.free_regions()
    else:
        cooc, c_live, c_tot = sweep_decay_prune(
            state.cooc, dticks, cfg=cfg.decay, weight_lanes=("weight",),
            use_kernel=cfg.kernel_on("decay_prune"))
    sessions = stores.evict_sessions(state.sessions, state.tick, cfg.session_ttl)
    stats.update({"c_live": c_live, "c_total_w": c_tot})
    return EngineState(qstore, cooc, sessions, state.tick), stats


@partial(jax.jit, static_argnames=("cfg",))
def evict_sessions_cycle(state: EngineState, *, cfg: EngineConfig
                         ) -> EngineState:
    """Session-TTL eviction alone — an O(session_capacity) mask, no weight
    sweep. Under the lazy policy this keeps eviction on the ``decay_every``
    cadence (TTL semantics are unrelated to weight-decay laziness) while
    the store sweeps move to ``prune_every``."""
    sessions = stores.evict_sessions(state.sessions, state.tick,
                                     cfg.session_ttl)
    return state._replace(sessions=sessions)


@partial(jax.jit, static_argnames=("cfg",))
def prune_cycle(state: EngineState, *, cfg: EngineConfig
                ) -> Tuple[EngineState, Dict[str, jax.Array]]:
    """Lazy policy's slow-cadence maintenance: prune-only sweep (decay is
    amortized into reads/writes), every ``prune_every`` ticks. Stats
    report the reclaimed-slot counts (and, under the region layout, the
    freelist pressure) so the engine can surface them to the frontends."""
    qstore, q_live, q_tot, q_rec = prune_sweep(state.qstore, state.tick,
                                               cfg=cfg.decay)
    if cfg.region_cooc:
        cooc, c_live, c_tot, c_rec = region_prune_sweep(
            state.cooc, qstore, state.tick, cfg=cfg.decay)
    else:
        cooc, c_live, c_tot, c_rec = prune_sweep(state.cooc, state.tick,
                                                 cfg=cfg.decay)
    sessions = stores.evict_sessions(state.sessions, state.tick, cfg.session_ttl)
    stats = {"q_live": q_live, "q_total_w": q_tot,
             "c_live": c_live, "c_total_w": c_tot,
             "q_reclaimed": q_rec, "c_reclaimed": c_rec}
    if cfg.region_cooc:
        stats["c_free_regions"] = cooc.free_regions()
    return EngineState(qstore, cooc, sessions, state.tick), stats


@jax.jit
def advance_tick(state: EngineState) -> EngineState:
    return state._replace(tick=state.tick + 1)


# ---------------------------------------------------------------------------
# Fused multi-tick ingestion (the §4.2 catch-up primitive)
# ---------------------------------------------------------------------------

class TickStack(NamedTuple):
    """A stack of R consecutive micro-batches (leading dim = tick).

    Shapes: query lanes are [R, B] (B may be 0: no query hose), tweet grams
    are [R, T, G] with valid [R, T] (T or G may be 0: no firehose).
    """
    sess_hi: jax.Array
    sess_lo: jax.Array
    q_hi: jax.Array
    q_lo: jax.Array
    src: jax.Array
    q_valid: jax.Array
    g_hi: jax.Array
    g_lo: jax.Array
    t_valid: jax.Array

    @property
    def n_ticks(self) -> int:
        return self.sess_hi.shape[0]


def rank_due(cfg: EngineConfig, tick: int) -> bool:
    """Is a ranking cycle due at ``tick``? The single statement of the
    rank cadence, shared by live ``step()``, the catch-up replay counting
    (``streaming/replay.py``) and the overload controller's rank
    governance (``streaming/overload.py``) — shed/suppressed cycles are
    counted against exactly this predicate."""
    return cfg.rank_every > 0 and tick > 0 and tick % cfg.rank_every == 0


def cadence_due(cfg: EngineConfig, tick: int) -> Optional[str]:
    """Which maintenance cycle is due at ``tick`` (host-side, concrete).

    THE single statement of the cadence semantics: ``step()`` branches on
    it live, ``step_many()`` counts cycle crossings with it, and
    ``maintenance_cadence`` below is its traced twin for the replay scans
    (the crash→restore→replay bit-for-bit property test pins the two
    together). Lazy policy: "prune" at ``prune_every`` wins over "evict"
    at ``decay_every`` (the prune cycle evicts sessions itself); eager
    policy: "decay" at ``decay_every``.
    """
    if tick <= 0:
        return None
    if cfg.lazy_decay:
        if cfg.prune_every > 0 and tick % cfg.prune_every == 0:
            return "prune"
        if cfg.decay_every > 0 and tick % cfg.decay_every == 0:
            return "evict"
        return None
    if cfg.decay_every > 0 and tick % cfg.decay_every == 0:
        return "decay"
    return None


def maintenance_cadence(state, tick: jax.Array, cfg: EngineConfig,
                        prune_fn, evict_fn, decay_fn):
    """Traced twin of :func:`cadence_due` as ``lax.cond``s, shared by the
    unsharded and sharded replay scans — same prune-wins/evict/decay
    ladder, same ``tick > 0`` guard. ``state`` may be any pytree the
    branch callables accept.
    """
    ident = lambda s: s
    if cfg.lazy_decay:
        prune_on = cfg.prune_every > 0
        evict_on = cfg.decay_every > 0
        do_prune = ((tick > 0) & (tick % max(cfg.prune_every, 1) == 0)
                    if prune_on else None)
        do_evict = ((tick > 0) & (tick % max(cfg.decay_every, 1) == 0)
                    if evict_on else None)
        if prune_on and evict_on:
            return jax.lax.cond(
                do_prune, prune_fn,
                lambda s: jax.lax.cond(do_evict, evict_fn, ident, s), state)
        if prune_on:
            return jax.lax.cond(do_prune, prune_fn, ident, state)
        if evict_on:
            return jax.lax.cond(do_evict, evict_fn, ident, state)
        return state
    if cfg.decay_every > 0:
        do_decay = (tick > 0) & (tick % cfg.decay_every == 0)
        return jax.lax.cond(do_decay, decay_fn, ident, state)
    return state


def tick_maintenance(state: EngineState, cfg: EngineConfig) -> EngineState:
    """Traced equivalent of the host-side cadence logic in ``step()``.

    Runs the decay/prune/evict cycle due at ``state.tick`` (if any) so a
    replayed tick performs exactly the same state mutations as a live one —
    the crash→restore→replay == uninterrupted-run property depends on it.
    Ranking is deliberately absent: rank cycles read state but never mutate
    it, so replay may suppress them freely (§4.2: serve stale tables while
    catching up).
    """
    return maintenance_cadence(
        state, state.tick, cfg,
        prune_fn=lambda s: prune_cycle(s, cfg=cfg)[0],
        evict_fn=lambda s: evict_sessions_cycle(s, cfg=cfg),
        decay_fn=lambda s: decay_cycle(s, jnp.int32(cfg.decay_every),
                                       cfg=cfg)[0])


@partial(jax.jit, static_argnames=("cfg",))
def ingest_many(state: EngineState, stack: TickStack, *, cfg: EngineConfig
                ) -> EngineState:
    """Replay R logged ticks in ONE device dispatch (``lax.scan``).

    Per scan iteration this performs exactly what one live ``step()`` does to
    ``EngineState`` — query-path ingest, tweet-path ingest, then the cadence
    maintenance, then the tick advance — so replaying a logged tail is
    bit-for-bit identical to having lived through it. The win over live
    stepping is dispatch amortization: no per-tick host sync, one fused XLA
    program per chunk — which is what lets a restarted instance "consume
    messages at a faster rate than real time" (§4.2).
    """
    have_q = stack.q_hi.shape[1] > 0
    have_t = stack.g_hi.shape[1] > 0 and stack.g_hi.shape[2] > 0

    def body(st: EngineState, xs: TickStack):
        if have_q:
            # oversized tick batches cut at the SAME quantum boundaries as
            # live step() (statically unrolled inside the one scan dispatch)
            for lo, hi in quantum_slices(stack.q_hi.shape[1],
                                         cfg.ingest_quantum):
                st = ingest_queries(st, xs.sess_hi[lo:hi], xs.sess_lo[lo:hi],
                                    xs.q_hi[lo:hi], xs.q_lo[lo:hi],
                                    xs.src[lo:hi], xs.q_valid[lo:hi], cfg=cfg)
        if have_t:
            with jax.named_scope("ingest.tweets"):
                st = ingest_tweets(st, xs.g_hi, xs.g_lo, xs.t_valid, cfg=cfg)
        with jax.named_scope("ingest.maintenance"):
            st = tick_maintenance(st, cfg)
        return advance_tick(st), None

    state, _ = jax.lax.scan(body, state, stack)
    return state


# ---------------------------------------------------------------------------
# Host orchestrator
# ---------------------------------------------------------------------------

def _sync(x):
    """``x`` on the host: every blocking device->host read that
    ``SearchAssistanceEngine`` makes goes through here, timed as
    ``engine.sync`` and counted by ``engine.syncs``."""
    with obs.span("engine.sync"):
        obs.count("engine.syncs")
        return jax.device_get(x)


class SearchAssistanceEngine:
    """Host-side driver of one backend instance (paper Figure 4).

    Call :meth:`step` once per tick with the tick's micro-batches; the engine
    runs decay and ranking cycles at their configured cadences and keeps the
    latest suggestion table for the frontend.
    """

    def __init__(self, cfg: EngineConfig, name: str = "rt"):
        self.cfg = cfg
        self.name = name
        self.state = init_state(cfg)
        self.suggestions: Dict[int, List[Tuple[int, float]]] = {}
        self.last_rank_tick: int = -1
        self.n_rank_cycles = 0
        self.n_decay_cycles = 0
        self.n_prune_cycles = 0
        # last maintenance-cycle stats (reclaimed slots, freelist
        # pressure); rides into snapshot meta -> SuggestFrontend.metrics().
        self.last_maintenance: Dict[str, float] = {}

    # ---- ingestion ----
    def step(self, query_events=None, tweets=None) -> Optional[Dict]:
        """Process one tick. Returns rank-cycle stats when a cycle ran."""
        out = None
        if query_events is not None:
            s_hi, s_lo = split_fp(query_events.sess_fp)
            q_hi, q_lo = split_fp(query_events.q_fp)
            self._ingest_query_batch(
                jnp.asarray(s_hi), jnp.asarray(s_lo),
                jnp.asarray(q_hi), jnp.asarray(q_lo),
                jnp.asarray(query_events.src, jnp.int32),
                jnp.asarray(query_events.valid))
        if tweets is not None:
            g_hi, g_lo = split_fp(tweets.grams)
            self.state = ingest_tweets(
                self.state, jnp.asarray(g_hi), jnp.asarray(g_lo),
                jnp.asarray(tweets.valid), cfg=self.cfg)

        tick = int(_sync(self.state.tick))
        # one cadence authority for live, counters, and replay: cadence_due
        # (lazy: decay is amortized into reads/writes, only the prune-only
        # sweep remains at the longer prune cadence; session TTL eviction
        # stays on decay_every — a cheap mask with time-based semantics).
        due = cadence_due(self.cfg, tick)
        if due == "evict":
            self.state = evict_sessions_cycle(self.state, cfg=self.cfg)
        elif due == "prune":   # prune_cycle evicts sessions itself
            self.state, stats = prune_cycle(self.state, cfg=self.cfg)
            self.n_prune_cycles += 1
            self.last_maintenance = {k: float(v)
                                     for k, v in _sync(stats).items()}
        elif due == "decay":
            self.state, stats = decay_cycle(
                self.state, jnp.int32(self.cfg.decay_every), cfg=self.cfg)
            self.n_decay_cycles += 1
            self.last_maintenance = {k: float(v)
                                     for k, v in _sync(stats).items()}
        if rank_due(self.cfg, tick):
            out = self.run_rank_cycle()
        self.state = advance_tick(self.state)
        return out

    def _ingest_query_batch(self, *arrs) -> None:
        """Live side of the large-batch-cliff fix: cut the batch at the
        shared :func:`quantum_slices` boundaries, then fuse up to
        ``plan.ingest_chunk // quantum`` full slices into one dispatch via
        :func:`ingest_queries_stack`. The cut points are plan-independent;
        the fusion width changes dispatch count only, so any two plans
        leave bit-identical state."""
        cfg = self.cfg
        Q = cfg.ingest_quantum
        cuts = quantum_slices(arrs[2].shape[0], Q)
        if len(cuts) == 1:
            self.state = ingest_queries(self.state, *arrs, cfg=cfg)
            return
        chunk = cfg.plan.ingest_chunk if cfg.plan is not None else 0
        k = max(1, chunk // Q) if chunk > 0 else 1
        i = 0
        while i < len(cuts):
            lo, hi = cuts[i]
            n = 1
            if k > 1 and hi - lo == Q:
                while (i + n < len(cuts) and n < k
                       and cuts[i + n][1] - cuts[i + n][0] == Q):
                    n += 1
            if n > 1:
                sub = tuple(a[lo:lo + n * Q].reshape(n, Q) for a in arrs)
                self.state = ingest_queries_stack(self.state, *sub, cfg=cfg)
            else:
                self.state = ingest_queries(
                    self.state, *(a[lo:hi] for a in arrs), cfg=cfg)
            i += n

    def run_rank_cycle(self) -> Dict:
        dkw = (dict(decay_cfg=self.cfg.decay, now=self.state.tick)
               if self.cfg.lazy_decay else {})
        cycle = (ranking.ranking_cycle_region if self.cfg.region_cooc
                 else ranking.ranking_cycle)
        table = cycle(self.state.cooc, self.state.qstore,
                      self.cfg.rank, **dkw)
        with obs.span("rank.wait"):
            jax.block_until_ready(table)
        self.suggestions = ranking.suggestions_to_host(table)
        self.last_rank_tick = int(_sync(self.state.tick))
        self.n_rank_cycles += 1
        n_rows, n_overflow, lookups = _sync(
            (table.n_rows, table.n_overflow, table.lookup_stats))
        for full_rounds, tail_rows in lookups:
            obs.count("rank.lookup_full_rounds", int(full_rounds))
            obs.count("rank.lookup_tail_rows", int(tail_rows))
        return {"tick": self.last_rank_tick, "n_rows": int(n_rows),
                "n_overflow": int(n_overflow),
                "n_suggest": len(self.suggestions)}

    def step_many(self, stack: TickStack) -> None:
        """Fused multi-tick ingestion (catch-up replay / bulk live ingest).

        Applies :func:`ingest_many` and keeps the host-side cycle counters
        consistent with what the equivalent ``step()`` loop would have done.
        Ranking cycles are NOT run (the caller decides when lag is low
        enough to resume them — see ``streaming/replay.py``).
        """
        t0 = int(_sync(self.state.tick))
        self.state = ingest_many(self.state, stack, cfg=self.cfg)
        t1 = int(_sync(self.state.tick))
        due = [cadence_due(self.cfg, t) for t in range(t0, t1)]
        self.n_prune_cycles += sum(d == "prune" for d in due)
        self.n_decay_cycles += sum(d == "decay" for d in due)

    # ---- serving-side reads (the frontend cache pulls these) ----
    def suggest_fp(self, fp: int, k: int = 8) -> List[Tuple[int, float]]:
        return self.suggestions.get(int(fp), [])[:k]

    # ---- persistence (every rank cycle the leader persists, §4.2) ----
    def save_snapshot(self, ckpt, extra_meta: Optional[Dict] = None) -> str:
        """Snapshot = checkpoint + log offset (§4.2 rewind/catch-up).

        The manifest records ``log_tick`` — the first tick a restarted
        instance must replay from the firehose log to catch up to where
        this snapshot left off. Whether the manager writes a full
        checkpoint or a delta against the previous snapshot (changed slots
        only) is the manager's decision (``CheckpointManager.full_interval``);
        either way ``restore_from_snapshot`` sees the composed state.
        """
        tick = int(_sync(self.state.tick))
        meta = {"log_tick": tick, "engine": self.name,
                "layout": self.cfg.cooc_layout}
        if self.cfg.plan is not None:
            # the tuned plan rides the snapshot so a recovered engine keeps
            # its tuning without re-benchmarking (restore re-attaches it)
            meta["plan"] = self.cfg.plan.to_json()
        if self.last_maintenance:
            meta["maintenance"] = self.last_maintenance
        if extra_meta:
            meta.update(extra_meta)
        return ckpt.save(tick, self.state, meta=meta)

    @classmethod
    def restore_from_snapshot(cls, cfg: EngineConfig, ckpt,
                              step: Optional[int] = None, name: str = "rt"
                              ) -> Tuple["SearchAssistanceEngine", int]:
        """Cold-start from the newest (or a given) snapshot.

        Returns ``(engine, log_tick)``: the engine holds the restored
        ``EngineState`` and ``log_tick`` is the offset to resume replaying
        the firehose log from. The restore walks the snapshot's delta
        chain; when a torn/corrupt chain member forces the fallback to an
        older intact full snapshot (``ckpt.last_restore["fell_back"]``),
        the returned ``log_tick`` is that older snapshot's offset — replay
        simply covers the longer tail.
        """
        eng = cls(cfg, name)
        eng.state, step = ckpt.restore(eng.state, step)
        meta = ckpt.manifest(step).get("meta", {})
        if cfg.plan is None and meta.get("plan"):
            # re-attach the tuning that rode the snapshot (an explicitly
            # configured plan wins — the caller may have re-tuned)
            eng.cfg = dataclasses.replace(
                cfg, plan=TunedPlan.from_json(meta["plan"]))
        return eng, int(meta.get("log_tick", step))

    def state_arrays(self) -> Dict[str, np.ndarray]:
        leaves, treedef = jax.tree.flatten(self.state)
        return {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}

    def load_state_arrays(self, arrays: Dict[str, np.ndarray]) -> None:
        leaves, treedef = jax.tree.flatten(self.state)
        new_leaves = [jnp.asarray(arrays[f"leaf_{i}"]) for i in range(len(leaves))]
        self.state = jax.tree.unflatten(treedef, new_leaves)
