"""Beyond-paper: the sharded search-assistance backend.

The paper's backend is "replicated for fault tolerance, but not sharded
(each instance independently holds the entire state)" and names the two
scalability walls (§4.4): every instance must consume the full hoses, and
store memory bounds coverage. This module shards the engine over a mesh
axis and removes the memory wall:

  * **query store**: replicated (it is orders of magnitude smaller than the
    pair space — the paper's own observation) so ranking marginals and
    query-likeness checks stay local;
  * **sessions store**: sharded by session hash — pair *generation* is local
    to the session owner;
  * **cooccurrence store**: sharded by pair, owner = (hash(src) +
    hash(dst) % n_salts) % n_shards. The salt spreads each source over up
    to ``n_salts`` shards, so a Zipf-skewed source (the skew that produced
    the paper's Hadoop stragglers, §3.2) cannot pile its updates onto one
    shard. The owner is a function of the pair alone, so every pair lives
    in exactly one shard for its whole life; each shard's top-k of a
    source is the top-k of a disjoint part of its pairs, and the host
    merge of the per-shard lists is the exact global top-k (scores read
    only the pair and the replicated query-store marginals);
  * pair routing: fixed-capacity bucketization + ``all_to_all`` along the
    shard axis, one route per ingest quantum slice of the query events and
    one per tick for the tweets (overflow is dropped *and counted*; a
    deployment sizes the capacities so that none is);
  * tweets: every shard applies the whole tick's query-like grams to its
    query-store replica; shard i expands the gram pairs of its 1/n of the
    tweets and routes them like session pairs.

State lives as arrays with a leading shard axis, sharded with shard_map;
the same single-device store/ranking code runs per shard. ``ShardedEngine``
drives it from the host and replays ``TickStack``s from the shared durable
log, as ``SearchAssistanceEngine`` does on one chip.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .. import obs
from . import ranking, stores
from .decay import (prune_sweep, region_decay_sweep, region_prune_sweep,
                    sweep_decay_prune)
from .engine import (EngineConfig, TickStack, _sync, cooc_insert_pairs,
                     maintenance_cadence, make_cooc_store, quantum_slices,
                     tweet_grams, _Q_MODES)
from .hashing import combine_fp_device, probe_hash
from .ranking import SuggestionTable
from .stores import HashTable, SessionTable


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    base: EngineConfig
    n_salts: int = 4                # shards one source's pairs spread over
    # per-destination bucket capacities: the session pairs of one quantum
    # slice (``base.ingest_quantum`` query events), the tweet pairs of one
    # tick. A pair beyond them is dropped and counted (``n_route_drop``).
    route_capacity: int = 4096
    tweet_route_capacity: int = 4096


class ShardedState(NamedTuple):
    qstore: HashTable        # replicated
    cooc: HashTable          # leading dim = shard
    sessions: SessionTable   # leading dim = shard
    tick: jax.Array
    n_route_drop: jax.Array  # per shard: routed pairs dropped on overflow
    n_routed: jax.Array      # per shard: pairs received (u32, wraps)


def _stack_shards(tree, n):
    """Concatenate n per-shard tables along dim 0 (shard_map blocks dim 0).

    Scalars (per-shard counters) become shape (n,) -> (1,) per device.
    Every shard starts as a copy of the freshly initialized per-shard
    table — broadcast+reshape == n concatenated copies, which preserves
    non-zero initial values (the region layout's -1 sentinels).
    """
    def f(x):
        if x.ndim == 0:
            return jnp.broadcast_to(x, (n,))
        return jnp.broadcast_to(x, (n,) + x.shape).reshape(
            (n * x.shape[0],) + x.shape[1:])
    return jax.tree.map(f, tree)


def init_sharded_state(cfg: ShardedConfig, mesh: Mesh, axis: str = "shard"
                       ) -> ShardedState:
    """A fresh sharded state, built in place: one jitted program whose
    output shardings are the state's own, so each device materializes only
    its shard (the whole stacked state never sits on one device — at a
    deployment-size cooc store it would not fit there)."""
    n = mesh.shape[axis]
    base = cfg.base

    def build() -> ShardedState:
        qstore = stores.make_table(base.query_capacity, {
            "weight": jnp.float32, "count": jnp.float32,
            "last_tick": jnp.int32})
        # region layout: each shard gets its own region pool + a full-Q
        # chain directory (the qstore is replicated, so slot ids are global).
        cooc = make_cooc_store(base, capacity=base.cooc_capacity // n)
        sessions = stores.make_session_table(base.session_capacity // n,
                                             base.session_window)
        return ShardedState(
            qstore=qstore,
            cooc=_stack_shards(cooc, n),
            sessions=_stack_shards(sessions, n),
            tick=jnp.zeros((), jnp.int32),
            n_route_drop=jnp.zeros((n,), jnp.int32),
            n_routed=jnp.zeros((n,), jnp.uint32),
        )

    shardings = jax.tree.map(lambda p: NamedSharding(mesh, p),
                             _state_spec(cfg, axis),
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)()


def pair_owner(src_hi, src_lo, dst_hi, dst_lo, n_salts: int, n_shards: int):
    """The shard that owns pair (src, dst): ``(hash(src) + hash(dst) %
    n_salts) % n_shards`` — a function of the pair alone, so live routing
    and resharding agree and a pair never has fragments in two shards."""
    salt = probe_hash(dst_hi, dst_lo) % jnp.uint32(max(n_salts, 1))
    return ((probe_hash(src_hi, src_lo) + salt)
            % jnp.uint32(n_shards)).astype(jnp.int32)


# bytes of one routed pair: four u32 key halves and the valid flag; the
# session pairs carry their f32 weight besides
_PAIR_BYTES = 4 * 4 + 1
_WEIGHT_BYTES = 4


def route_bytes_per_tick(cfg: ShardedConfig, n_shards: int, n_events: int,
                         n_tweets: int, n_grams: int) -> int:
    """Bytes one tick's ``all_to_all``s send off each chip: every route
    sends its ``n_shards - 1`` foreign buckets whole, whatever they hold —
    one route per quantum slice of the query events, one for the tweets."""
    q = (len(quantum_slices(n_events, cfg.base.ingest_quantum))
         * cfg.route_capacity * (_PAIR_BYTES + _WEIGHT_BYTES)
         if n_events else 0)
    t = cfg.tweet_route_capacity * _PAIR_BYTES if n_tweets and n_grams else 0
    return (n_shards - 1) * (q + t)


def _route(owner, lanes: Dict[str, jax.Array], valid, n_shards: int,
           cap: int, axis: str):
    """Bucketize pairs by owner shard and exchange the buckets with
    ``all_to_all`` (inside shard_map). A bucket holds ``cap`` pairs in
    arrival order; a pair beyond it is dropped and counted. Returns the
    received lanes and valid flags, flat ``[n_shards * cap]``, the pairs
    dropped here and the pairs received here."""
    to = valid[:, None] & (owner[:, None] == jnp.arange(n_shards)[None, :])
    pos = jnp.cumsum(to, axis=0, dtype=jnp.int32) - 1      # [P, n_shards]
    dropped = jnp.sum(jnp.maximum(pos[-1] + 1 - cap, 0))
    pos = jnp.sum(jnp.where(to, pos, 0), axis=1)
    ok = valid & (pos < cap)
    row = jnp.where(ok, owner, n_shards)     # out of range: not written
    col = jnp.where(ok, pos, 0)

    def exchange(x):
        buf = jnp.zeros((n_shards, cap), x.dtype).at[row, col].set(
            x, mode="drop")
        # axis 0 is the destination shard
        got = jax.lax.all_to_all(buf, axis, 0, 0, tiled=False)
        return got.reshape(n_shards * cap)

    got_valid = exchange(ok)
    return ({k: exchange(v) for k, v in lanes.items()}, got_valid, dropped,
            jnp.sum(got_valid, dtype=jnp.uint32))


def _each_quantum(body, st, lanes, quantum: int):
    """``body(st, *slice)`` over one tick's query lanes cut at
    ``quantum_slices``, in order: the whole slices in one ``lax.scan``,
    a shorter last slice after it."""
    B = lanes[0].shape[0]
    size = quantum_slices(B, quantum)[0][1]
    k = B // size
    if k > 1:
        st, _ = jax.lax.scan(
            lambda s, xs: (body(s, *xs), None), st,
            tuple(x[:k * size].reshape((k, size) + x.shape[1:])
                  for x in lanes))
    else:
        st = body(st, *(x[:size] for x in lanes))
    if k * size < B:
        st = body(st, *(x[k * size:] for x in lanes))
    return st


def _tick_body(cfg: ShardedConfig, n: int, axis: str):
    """One tick on one device (inside shard_map), what
    ``SearchAssistanceEngine.step`` does to its state: the query path in
    the quantum slices of ``engine.ingest_many``, the tweet path, the
    cadence maintenance, the tick advance. Shared by the live tick step
    and the replay scan, so the two mutate state alike."""
    base = cfg.base
    n_sw = len(base.source_weights)

    def dkw(st):
        # lazy decay policy: same rebase-on-write as the unsharded engine
        return (dict(decay_cfg=base.decay, now=st.tick)
                if base.lazy_decay else {})

    def route_insert(st, qstore, src_hi, src_lo, dst_hi, dst_lo, w, valid,
                     cap):
        """Send each pair to the shard that owns it; insert what this
        shard receives. ``w`` None: every pair weighs ``tweet_weight``."""
        with jax.named_scope("ingest.route"):
            owner = pair_owner(src_hi, src_lo, dst_hi, dst_lo, cfg.n_salts,
                               n)
            lanes = {"src_hi": src_hi, "src_lo": src_lo, "dst_hi": dst_hi,
                     "dst_lo": dst_lo}
            if w is not None:
                lanes["w"] = w
            got, ok, dropped, received = _route(owner, lanes, valid, n, cap,
                                                axis)
        with jax.named_scope("ingest.cooc_insert"):
            w = got["w"] if w is not None else jnp.full(
                ok.shape, base.tweet_weight, jnp.float32)
            cooc = cooc_insert_pairs(
                st.cooc, qstore, got["src_hi"], got["src_lo"], got["dst_hi"],
                got["dst_lo"], w, ok, st.tick, base, dkw(st))
        return st._replace(qstore=qstore, cooc=cooc,
                           n_route_drop=st.n_route_drop + dropped[None],
                           n_routed=st.n_routed + received[None])

    def query_slice(st, s_hi, s_lo, q_hi, q_lo, src, valid):
        B = q_hi.shape[0]
        sw = jnp.asarray(base.source_weights, jnp.float32)
        # replicated query store: every shard applies the whole slice
        with jax.named_scope("ingest.qstore"):
            qstore = stores.insert_accumulate(
                st.qstore, q_hi, q_lo,
                {"weight": sw[jnp.clip(src, 0, n_sw - 1)],
                 "count": jnp.ones((B,), jnp.float32),
                 "last_tick": jnp.full((B,), st.tick, jnp.int32)},
                valid, modes=_Q_MODES, probe_rounds=base.probe_rounds,
                **dkw(st))
        # sessions: each shard keeps those it owns (hash(sess) % n)
        with jax.named_scope("ingest.sessions"):
            owner = (probe_hash(s_hi, s_lo) % jnp.uint32(n)).astype(jnp.int32)
            sessions, pairs = stores.update_sessions(
                st.sessions, s_hi, s_lo, q_hi, q_lo, src, st.tick,
                valid & (owner == jax.lax.axis_index(axis)),
                probe_rounds=base.probe_rounds)
        # pair weight: geometric mean of the two interaction-source weights
        w_pair = jnp.sqrt(sw[jnp.clip(pairs.src_code, 0, n_sw - 1)]
                          * sw[jnp.clip(pairs.dst_code, 0, n_sw - 1)])
        return route_insert(st._replace(sessions=sessions), qstore,
                            pairs.src_hi, pairs.src_lo, pairs.dst_hi,
                            pairs.dst_lo, w_pair, pairs.valid,
                            cfg.route_capacity)

    def tweets(st, g_hi, g_lo, t_valid):
        # every shard looks up and adds every tweet's grams (the query store
        # is replicated); shard i expands the pairs of its own 1/n of them
        pad = -g_hi.shape[0] % n
        if pad:
            g_hi, g_lo = (jnp.pad(x, ((0, pad), (0, 0))) for x in (g_hi, g_lo))
            t_valid = jnp.pad(t_valid, (0, pad))
        part = g_hi.shape[0] // n
        qstore, pairs = tweet_grams(
            st.qstore, g_hi, g_lo, t_valid, st.tick, base,
            part=(jax.lax.axis_index(axis) * part, part))
        return route_insert(st, qstore, pairs.src_hi, pairs.src_lo,
                            pairs.dst_hi, pairs.dst_lo, None, pairs.valid,
                            cfg.tweet_route_capacity)

    def tick(st: ShardedState, xs: TickStack) -> ShardedState:
        if xs.q_hi.shape[0] > 0:
            st = _each_quantum(query_slice, st,
                               (xs.sess_hi, xs.sess_lo, xs.q_hi, xs.q_lo,
                                xs.src, xs.q_valid), base.ingest_quantum)
        if xs.g_hi.shape[0] > 0 and xs.g_hi.shape[1] > 0:
            with jax.named_scope("ingest.tweets"):
                st = tweets(st, xs.g_hi, xs.g_lo, xs.t_valid)
        with jax.named_scope("ingest.maintenance"):
            st = _tick_maintenance(st, base)
        return st._replace(tick=st.tick + 1)

    return tick


def _tick_maintenance(state: ShardedState, base: EngineConfig
                      ) -> ShardedState:
    """Per-tick maintenance on the sharded state: the shared
    ``engine.maintenance_cadence`` ladder (ONE copy of the cadence
    semantics) with sharded branch bodies — lazy: prune-only sweeps at
    ``prune_every``, session eviction at ``decay_every``; eager: full
    decay/prune + eviction at ``decay_every``."""

    def evict_only(s: ShardedState) -> ShardedState:
        sessions = stores.evict_sessions(s.sessions, s.tick, base.session_ttl)
        return s._replace(sessions=sessions)

    def prune_fn(s: ShardedState) -> ShardedState:
        qstore, _, _, _ = prune_sweep(s.qstore, s.tick, cfg=base.decay)
        if base.region_cooc:
            cooc, _, _, _ = region_prune_sweep(s.cooc, qstore, s.tick,
                                               cfg=base.decay)
        else:
            cooc, _, _, _ = prune_sweep(s.cooc, s.tick, cfg=base.decay)
        return evict_only(s._replace(qstore=qstore, cooc=cooc))

    def decay_fn(s: ShardedState) -> ShardedState:
        qstore, _, _ = sweep_decay_prune(
            s.qstore, jnp.int32(base.decay_every), cfg=base.decay,
            use_kernel=base.kernel_on("decay_prune"))
        if base.region_cooc:
            cooc, _, _, _ = region_decay_sweep(
                s.cooc, qstore, jnp.int32(base.decay_every), cfg=base.decay)
        else:
            cooc, _, _ = sweep_decay_prune(
                s.cooc, jnp.int32(base.decay_every), cfg=base.decay,
                use_kernel=base.kernel_on("decay_prune"))
        return evict_only(s._replace(qstore=qstore, cooc=cooc))

    return maintenance_cadence(state, state.tick, base,
                               prune_fn=prune_fn, evict_fn=evict_only,
                               decay_fn=decay_fn)


def make_sharded_tick_step(cfg: ShardedConfig, mesh: Mesh,
                           axis: str = "shard"):
    """One live tick, ``(state, tick) -> state``: ``tick`` is one tick of a
    ``TickStack`` (no leading dim), queries and tweets. Its body is the
    replay scan's, so live ticks replay exactly under
    ``make_sharded_ingest_many``."""
    spec = _state_spec(cfg, axis)
    return jax.jit(shard_map(_tick_body(cfg, mesh.shape[axis], axis),
                             mesh=mesh, in_specs=(spec, P()),
                             out_specs=spec, check_vma=False))


def make_sharded_ingest_many(cfg: ShardedConfig, mesh: Mesh,
                             axis: str = "shard"):
    """Fused catch-up replay over the sharded engine (§4.2):
    ``(state, stack) -> state`` for a ``TickStack`` of R logged ticks, the
    type ``streaming.chunk_to_stack`` returns, so one durable log feeds
    this and ``engine.ingest_many`` alike.

    Every shard takes the whole logged hose, so replay is parallel by
    construction: one ``lax.scan`` dispatch advances all shards through
    the R ticks — routing ``all_to_all``s and cadence maintenance
    included, the live tick step's body per tick. The program is named
    ``sharded_ingest_many`` in traces.
    """
    spec = _state_spec(cfg, axis)
    tick = _tick_body(cfg, mesh.shape[axis], axis)

    def many(state: ShardedState, stack: TickStack) -> ShardedState:
        state, _ = jax.lax.scan(lambda st, xs: (tick(st, xs), None), state,
                                stack)
        return state

    program = shard_map(many, mesh=mesh, in_specs=(spec, P()),
                        out_specs=spec, check_vma=False)

    def sharded_ingest_many(state: ShardedState, stack: TickStack
                            ) -> ShardedState:
        return program(state, stack)

    return jax.jit(sharded_ingest_many)


class ShardedEngine:
    """Host driver of one sharded instance, as ``SearchAssistanceEngine``
    is of one chip's: the state on ``mesh`` and its replay program.

    ``step_many`` reads the tick and the routing counters in one
    ``engine._sync`` per call and counts, per call: ``shard.route_pairs``
    (pairs routed, all shards), ``shard.route_drops``, ``shard.route_bytes``
    (bytes the call's ``all_to_all``s sent off each chip),
    ``shard.pairs_max`` and ``shard.pairs_mean`` (pairs received by the
    busiest shard, and by the mean one).
    """

    def __init__(self, cfg: ShardedConfig, mesh: Mesh):
        self.cfg = cfg
        self.n_shards = n = mesh.shape["shard"]
        self.state = init_sharded_state(cfg, mesh)
        self.ingest_many = make_sharded_ingest_many(cfg, mesh)
        # the tick and routing counters as last read: a fresh state's
        self._seen = (0, np.zeros(n, np.uint32), np.zeros(n, np.int32))

    @property
    def tick(self) -> int:
        return int(self._seen[0])

    def step_many(self, stack: TickStack) -> None:
        """Replay ``stack``, ranking suppressed, as
        ``SearchAssistanceEngine.step_many`` does."""
        st = self.state = self.ingest_many(self.state, stack)
        (_, routed0, drops0), self._seen = self._seen, _sync(
            (st.tick, st.n_routed, st.n_route_drop))
        _, routed, drops = self._seen
        got = (routed - routed0).astype(np.int64)   # u32: exact across a wrap
        obs.count("shard.route_pairs", int(got.sum()))
        obs.count("shard.route_drops", int(drops.sum() - drops0.sum()))
        obs.count("shard.route_bytes", stack.n_ticks * route_bytes_per_tick(
            self.cfg, self.n_shards, stack.q_hi.shape[1], stack.g_hi.shape[1],
            stack.g_hi.shape[2]))
        obs.count("shard.pairs_max", int(got.max()))
        obs.count("shard.pairs_mean", float(got.mean()))


def make_sharded_rank(cfg: ShardedConfig, mesh: Mesh, axis: str = "shard"):
    def body(state: ShardedState):
        dkw = (dict(decay_cfg=cfg.base.decay, now=state.tick)
               if cfg.base.lazy_decay else {})
        cycle = (ranking.ranking_cycle_region if cfg.base.region_cooc
                 else ranking.ranking_cycle)
        t = cycle(state.cooc, state.qstore, cfg.base.rank, **dkw)
        # scalars -> (1,) per shard
        return t._replace(n_rows=t.n_rows[None], n_overflow=t.n_overflow[None])

    state_spec = _state_spec(cfg, axis)
    out_spec = SuggestionTable(*([P(axis)] * 5), n_rows=P(axis),
                               n_overflow=P(axis), lookup_stats=P(axis))
    fn = shard_map(body, mesh=mesh, in_specs=(state_spec,),
                   out_specs=out_spec, check_vma=False)
    return jax.jit(fn)


def _state_spec(cfg: ShardedConfig, axis: str) -> ShardedState:
    rep, sh = P(), P(axis)
    if cfg.base.region_cooc:
        cooc_tmpl = stores.make_region_table(4, 2, 2, 2, {
            "weight": jnp.float32, "count": jnp.float32,
            "last_tick": jnp.int32})
    else:
        cooc_tmpl = stores.make_table(
            2, {"weight": jnp.float32, "count": jnp.float32,
                "last_tick": jnp.int32, "src_hi": jnp.uint32,
                "src_lo": jnp.uint32, "dst_hi": jnp.uint32,
                "dst_lo": jnp.uint32})
    return ShardedState(
        qstore=jax.tree.map(lambda _: rep, stores.make_table(
            2, {"weight": jnp.float32, "count": jnp.float32,
                "last_tick": jnp.int32})),
        cooc=jax.tree.map(lambda _: sh, cooc_tmpl),
        sessions=jax.tree.map(lambda _: sh, stores.make_session_table(2, 2)),
        tick=rep,
        n_route_drop=sh,
        n_routed=sh,
    )


def save_sharded_snapshot(state: ShardedState, ckpt, meta=None) -> str:
    """Snapshot = checkpoint + log offset for the sharded engine.

    The whole ``ShardedState`` pytree (every shard's stores) goes into one
    checkpoint; the manifest records the shared-log replay offset.

    The save routes through the manager's delta-snapshot chain exactly
    like the unsharded engines: with ``CheckpointManager.full_interval >
    1`` only the changed leading rows of each (fully-addressable, host-
    readable) shard-stacked leaf are written between fulls, chained to the
    last full via the manifest (``kind``/``base_step``). Sharded stores are
    where this pays off most — per-shard capacity shrinks with the shard
    count, so between snapshots each shard touches few rows of its lane.
    ``restore_sharded_snapshot`` sees the composed state transparently
    (chain walk + fallback live in the manager)."""
    tick = int(np.asarray(state.tick))
    m = {"log_tick": tick, "engine": "sharded"}
    if meta:
        m.update(meta)
    return ckpt.save(tick, state, meta=m)


def restore_sharded_snapshot(cfg: ShardedConfig, mesh: Mesh, ckpt,
                             step=None, axis: str = "shard"
                             ) -> Tuple[ShardedState, int]:
    """Cold-start a sharded instance: returns (state, log_tick) — every
    shard restores in one pass, then all replay the shared log in parallel
    via ``make_sharded_ingest_many``."""
    template = init_sharded_state(cfg, mesh, axis)
    state, step = ckpt.restore(template, step)
    meta = ckpt.manifest(step).get("meta", {})
    return state, int(meta.get("log_tick", step))


def merge_sharded_suggestions(table: SuggestionTable, top_k: int
                              ) -> Dict[int, List[Tuple[int, float]]]:
    """Host-side merge of per-shard suggestion tables: a source appears in
    up to ``n_salts`` shards, each holding a disjoint part of its pairs, so
    the best ``top_k`` of the union of its per-shard lists is its exact
    top-k."""
    from .hashing import join_fp
    src_hi = np.asarray(table.src_hi).reshape(-1)
    src_lo = np.asarray(table.src_lo).reshape(-1)
    K = table.score.shape[-1]
    dst_hi = np.asarray(table.dst_hi).reshape(-1, K)
    dst_lo = np.asarray(table.dst_lo).reshape(-1, K)
    score = np.asarray(table.score).reshape(-1, K)
    merged: Dict[int, Dict[int, float]] = {}
    # skip empty rows AND the lexsort path's all-ones filler src key
    # explicitly (same guard as suggestions_to_host)
    mask = ((src_hi != 0) | (src_lo != 0)) \
        & ~((src_hi == 0xFFFFFFFF) & (src_lo == 0xFFFFFFFF))
    src_fp = join_fp(src_hi, src_lo)
    dst_fp = join_fp(dst_hi, dst_lo)
    for i in np.nonzero(mask)[0]:
        d = merged.setdefault(int(src_fp[i]), {})
        for j in range(K):
            if score[i, j] > 0.0:
                d[int(dst_fp[i, j])] = float(score[i, j])
    return {s: sorted(d.items(), key=lambda t: (-t[1], t[0]))[:top_k]
            for s, d in merged.items()}


# ---------------------------------------------------------------------------
# Live shard split/merge (elastic scaling).
#
# Re-partitions a running ShardedState across a different shard count
# without losing state: every live cooccurrence pair and session is
# exported to a canonical host-side form (each pair lives in exactly one
# old shard), then re-inserted into freshly initialized per-shard stores
# under the NEW shard count's ``pair_owner`` — the rule the live ingest
# path routes by, so post-reshard inserts land on the rows the reshard
# placed. The qstore is replicated and copied
# verbatim, which also keeps every region-directory slot id valid.
#
# The reshard is a pure function of the state content: two runs that
# reshard at the same tick from bit-identical states produce bit-identical
# new states, which is what makes the zero-downtime handoff testable
# (serve from the old state while ticks keep arriving, replay the interim
# ticks from the shared log into the new state, compare against a clean
# run — see distributed.elastic.live_reshard).
# ---------------------------------------------------------------------------

_SET_PAIR_MODES = (("weight", "set"), ("count", "set"), ("last_tick", "set"))
_SET_HASH_MODES = _SET_PAIR_MODES + (("src_hi", "set"), ("src_lo", "set"),
                                     ("dst_hi", "set"), ("dst_lo", "set"))
_PAIR_COLS = ("src_hi", "src_lo", "dst_hi", "dst_lo",
              "weight", "count", "last_tick")
_SESS_COLS = ("key_hi", "key_lo", "ring_hi", "ring_lo", "ring_src",
              "cursor", "filled", "last_tick")


def _shard_view(tree, i: int, n: int, scalar_fields=("n_dropped",)):
    """Slice shard ``i`` out of a shard-stacked store tree (inverse of
    ``_stack_shards`` for one shard): leading dims are n x per-shard, the
    named scalar counters are stacked to (n,)."""
    def f(path, x):
        name = path[-1].name if hasattr(path[-1], "name") else str(path[-1])
        if name in scalar_fields and x.ndim == 1 and x.shape[0] == n:
            return x[i]
        m = x.shape[0] // n
        return x[i * m:(i + 1) * m]
    return jax.tree_util.tree_map_with_path(f, tree)


def _stack_trees(trees):
    """Stack per-shard store trees back into the leading-dim layout
    (scalars -> (n,), arrays concatenated — same layout as _stack_shards)."""
    return jax.tree.map(
        lambda *xs: (jnp.stack(xs, 0) if xs[0].ndim == 0
                     else jnp.concatenate(xs, 0)), *trees)


def _export_hash_pairs(tab: HashTable) -> Dict[str, np.ndarray]:
    e = stores.export_live(tab)
    return {k: e[k] for k in _PAIR_COLS}


def _export_region_pairs(tab, qstore: HashTable) -> Dict[str, np.ndarray]:
    """Live pairs of one region-layout shard: walk the packed region pool
    under the shared chain-validity invariant (orphaned chains and stale
    directory rows export nothing, exactly as ranking skips them)."""
    _, _, referenced = stores.region_chain_state(tab, qstore)
    referenced = np.asarray(referenced)
    fill = np.asarray(tab.region_fill)
    owner = np.asarray(tab.region_owner)
    chain_hi = np.asarray(tab.chain_hi)
    chain_lo = np.asarray(tab.chain_lo)
    khi, klo = np.asarray(tab.key_hi), np.asarray(tab.key_lo)
    W, C = tab.width, tab.capacity
    slot = np.arange(C)
    reg, pos = slot // W, slot % W
    live = referenced[reg] & (pos < fill[reg]) & ((khi != 0) | (klo != 0))
    idx = np.nonzero(live)[0]
    src_slot = owner[reg[idx]]
    out = {"src_hi": chain_hi[src_slot], "src_lo": chain_lo[src_slot],
           "dst_hi": khi[idx], "dst_lo": klo[idx]}
    for name in ("weight", "count", "last_tick"):
        out[name] = np.asarray(tab.lanes[name])[idx]
    return out


def export_sharded_pairs(cfg: ShardedConfig, state: ShardedState
                         ) -> Dict[str, np.ndarray]:
    """All live (src -> dst) pairs across shards, in canonical order."""
    n = state.n_route_drop.shape[0]
    cols: Dict[str, list] = {k: [] for k in _PAIR_COLS}
    for i in range(n):
        tab = _shard_view(state.cooc, i, n)
        e = (_export_region_pairs(tab, state.qstore)
             if cfg.base.region_cooc else _export_hash_pairs(tab))
        for k in _PAIR_COLS:
            cols[k].append(e[k])
    e = {k: np.concatenate(v) for k, v in cols.items()}
    order = np.lexsort((e["dst_lo"], e["dst_hi"], e["src_lo"], e["src_hi"]))
    return {k: v[order] for k, v in e.items()}


def export_sharded_sessions(state: ShardedState) -> Dict[str, np.ndarray]:
    """All live sessions across shards, full rows, canonical key order.
    Session ownership is total (one owner per key), so no merging."""
    n = state.n_route_drop.shape[0]
    cols: Dict[str, list] = {k: [] for k in _SESS_COLS}
    for i in range(n):
        t = _shard_view(state.sessions, i, n)
        mask = np.asarray((t.key_hi != 0) | (t.key_lo != 0))
        for k in _SESS_COLS:
            cols[k].append(np.asarray(getattr(t, k))[mask])
    e = {k: np.concatenate(v) for k, v in cols.items()}
    order = np.lexsort((e["key_lo"], e["key_hi"]))
    return {k: v[order] for k, v in e.items()}


def _fill_cooc_shard(cfg: ShardedConfig, new_n: int, qstore: HashTable,
                     pairs: Dict[str, np.ndarray], idx: np.ndarray):
    base = cfg.base
    tab = make_cooc_store(base, capacity=base.cooc_capacity // new_n)
    if idx.size == 0:
        return tab, 0
    upd = {k: jnp.asarray(pairs[k][idx])
           for k in ("weight", "count", "last_tick")}
    valid = jnp.ones((idx.size,), bool)
    s_hi, s_lo = jnp.asarray(pairs["src_hi"][idx]), \
        jnp.asarray(pairs["src_lo"][idx])
    d_hi, d_lo = jnp.asarray(pairs["dst_hi"][idx]), \
        jnp.asarray(pairs["dst_lo"][idx])
    # all-SET modes, no decay kwargs: the merged (weight, last_tick) pairs
    # are copied bit-exactly, which preserves lazy-decay semantics.
    if base.region_cooc:
        tab = stores.region_insert_accumulate(
            tab, qstore, s_hi, s_lo, d_hi, d_lo, upd, valid,
            modes=_SET_PAIR_MODES, probe_rounds=base.probe_rounds,
            use_kernel=base.use_kernel, plan=base.plan)
    else:
        p_hi, p_lo = combine_fp_device(s_hi, s_lo, d_hi, d_lo)
        upd.update({"src_hi": s_hi, "src_lo": s_lo,
                    "dst_hi": d_hi, "dst_lo": d_lo})
        tab = stores.insert_accumulate(
            tab, p_hi, p_lo, upd, valid, modes=_SET_HASH_MODES,
            probe_rounds=base.probe_rounds)
    return tab, int(np.asarray(tab.n_dropped))


def _fill_session_shard(base: EngineConfig, new_n: int,
                        sess: Dict[str, np.ndarray], idx: np.ndarray):
    cap = base.session_capacity // new_n
    tab = stores.make_session_table(cap, base.session_window)
    if idx.size == 0:
        return tab, 0
    kh, kl = jnp.asarray(sess["key_hi"][idx]), jnp.asarray(sess["key_lo"][idx])
    alive = jnp.ones((idx.size,), bool)
    # probe-consistent placement (later live update_sessions probes must
    # FIND these rows) + direct full-row scatter: update_sessions cannot
    # reproduce per-session last_tick (its tick argument is a scalar), and
    # the ring/cursor/filled triple must carry over verbatim.
    key_hi, key_lo, slot, placed, dropped = stores._find_or_claim(
        tab.key_hi, tab.key_lo, kh, kl, alive, base.probe_rounds)
    drop_slot = jnp.where(placed, slot, cap)

    def put(lane, col):
        return lane.at[drop_slot].set(jnp.asarray(sess[col][idx]),
                                      mode="drop")

    tab = tab._replace(
        key_hi=key_hi, key_lo=key_lo,
        ring_hi=put(tab.ring_hi, "ring_hi"),
        ring_lo=put(tab.ring_lo, "ring_lo"),
        ring_src=put(tab.ring_src, "ring_src"),
        cursor=put(tab.cursor, "cursor"),
        filled=put(tab.filled, "filled"),
        last_tick=put(tab.last_tick, "last_tick"),
        n_dropped=tab.n_dropped + dropped)
    return tab, int(np.asarray(dropped))


def reshard_sharded_state(cfg: ShardedConfig, state: ShardedState,
                          new_n: int) -> Tuple[ShardedState, Dict]:
    """Re-partition a live sharded state across ``new_n`` shards.

    Deterministic in the state content (no RNG, canonical ordering
    throughout); ``tick`` and the replicated qstore carry over unchanged,
    so the new state replays the shared log from the same offset. Pairs
    are placed by ``pair_owner`` under ``new_n``, as the live ingest path
    routes them. Per-shard drop counters restart at the insertion drops
    (old totals are returned in stats).
    """
    base = cfg.base
    old_n = state.n_route_drop.shape[0]
    assert new_n >= 1 and new_n & (new_n - 1) == 0, \
        f"new_n must be a power of two, got {new_n}"
    assert base.cooc_capacity % new_n == 0 \
        and base.cooc_capacity // new_n >= base.region_w, \
        "cooc capacity does not divide into new_n region-layout shards"
    assert base.session_capacity % new_n == 0, \
        "session capacity not divisible by new_n"

    pairs = export_sharded_pairs(cfg, state)
    sess = export_sharded_sessions(state)

    # ownership under new_n — the SAME rule as the live ingest path
    owner = np.asarray(pair_owner(
        *(jnp.asarray(pairs[k]) for k in ("src_hi", "src_lo", "dst_hi",
                                          "dst_lo")), cfg.n_salts, new_n))
    sess_owner = (np.asarray(
        probe_hash(jnp.asarray(sess["key_hi"]),
                   jnp.asarray(sess["key_lo"]))).astype(np.uint64)
        % new_n).astype(np.int64)

    coocs, sessions, n_pair_drop, n_sess_drop = [], [], 0, 0
    for j in range(new_n):
        c, dc = _fill_cooc_shard(cfg, new_n, state.qstore, pairs,
                                 np.nonzero(owner == j)[0])
        s, ds = _fill_session_shard(base, new_n, sess,
                                    np.nonzero(sess_owner == j)[0])
        coocs.append(c)
        sessions.append(s)
        n_pair_drop += dc
        n_sess_drop += ds

    new_state = ShardedState(
        qstore=state.qstore,
        cooc=_stack_trees(coocs),
        sessions=_stack_trees(sessions),
        tick=state.tick,
        n_route_drop=jnp.zeros((new_n,), jnp.int32),
        n_routed=jnp.zeros((new_n,), jnp.uint32))
    # hand back UNCOMMITTED arrays: leaves assembled here inherit the OLD
    # mesh's placement (and the qstore its old replication), which the new
    # layout's shard_map would reject — round-tripping through host leaves
    # the new mesh free to place them.
    new_state = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), new_state)
    stats = {"old_n": old_n, "new_n": new_n,
             "n_pairs": int(pairs["src_hi"].size),
             "n_sessions": int(sess["key_hi"].size),
             "n_pair_drop": n_pair_drop, "n_sess_drop": n_sess_drop,
             "old_route_drop": int(np.asarray(state.n_route_drop).sum()),
             "tick": int(np.asarray(state.tick))}
    return new_state, stats


def split_shards(cfg: ShardedConfig, state: ShardedState
                 ) -> Tuple[ShardedState, Dict]:
    """Double the shard count (scale out under lag/memory pressure)."""
    return reshard_sharded_state(cfg, state,
                                 2 * state.n_route_drop.shape[0])


def merge_shards(cfg: ShardedConfig, state: ShardedState
                 ) -> Tuple[ShardedState, Dict]:
    """Halve the shard count (scale in when shards run underfilled)."""
    n = state.n_route_drop.shape[0]
    assert n % 2 == 0, "cannot merge an odd shard count"
    return reshard_sharded_state(cfg, state, n // 2)
