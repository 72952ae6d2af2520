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
    shard axis (overflow is dropped *and counted*, mirroring the paper's
    rate-limiting stance).

State lives as arrays with a leading shard axis, sharded with shard_map;
the same single-device store/ranking code runs per shard.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from . import ranking, stores
from .decay import (prune_sweep, region_decay_sweep, region_prune_sweep,
                    sweep_decay_prune)
from .engine import (EngineConfig, cooc_insert_pairs, maintenance_cadence,
                     make_cooc_store, _Q_MODES)
from .hashing import combine_fp_device, probe_hash
from .ranking import SuggestionTable
from .stores import HashTable, SessionTable


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    base: EngineConfig
    n_salts: int = 4                # shards one source's pairs spread over
    route_capacity: int = 4096      # per-destination bucket capacity


class ShardedState(NamedTuple):
    qstore: HashTable        # replicated
    cooc: HashTable          # leading dim = shard
    sessions: SessionTable   # leading dim = shard
    tick: jax.Array
    n_route_drop: jax.Array  # routed pairs dropped on bucket overflow


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


def _route(pairs_key_hi, pairs_key_lo, owner, payload: Dict[str, jax.Array],
           valid, n_shards: int, cap: int, axis: str):
    """Bucketize by owner shard and all_to_all. Returns routed flat arrays.

    All arrays are per-device (inside shard_map). Overflow beyond ``cap``
    per destination bucket is dropped and counted.
    """
    Bp = pairs_key_hi.shape[0]
    owner = jnp.where(valid, owner, n_shards)  # invalid -> sentinel bucket
    order = jnp.argsort(owner)                  # stable
    o_sorted = owner[order]
    # position within each owner run
    idx = jnp.arange(Bp, dtype=jnp.int32)
    seg_start = jax.ops.segment_min(
        idx, jnp.clip(o_sorted, 0, n_shards).astype(jnp.int32),
        num_segments=n_shards + 1)
    pos = idx - seg_start[jnp.clip(o_sorted, 0, n_shards)]
    ok = (o_sorted < n_shards) & (pos < cap)
    dropped = jnp.sum(((o_sorted < n_shards) & (pos >= cap)).astype(jnp.int32))

    dest_row = jnp.where(ok, o_sorted.astype(jnp.int32), n_shards)
    dest_pos = jnp.where(ok, pos, 0)

    def bucketize(x, fill=0):
        buf = jnp.full((n_shards, cap) + x.shape[1:], fill, x.dtype)
        return buf.at[dest_row, dest_pos].set(x[order], mode="drop")

    b_hi = bucketize(pairs_key_hi)
    b_lo = bucketize(pairs_key_lo)
    b_payload = {k: bucketize(v) for k, v in payload.items()}
    b_valid = jnp.zeros((n_shards, cap), bool).at[dest_row, dest_pos].set(
        ok, mode="drop")

    # exchange: axis 0 is the destination shard
    t_hi = jax.lax.all_to_all(b_hi, axis, 0, 0, tiled=False)
    t_lo = jax.lax.all_to_all(b_lo, axis, 0, 0, tiled=False)
    t_val = jax.lax.all_to_all(b_valid, axis, 0, 0, tiled=False)
    t_payload = {k: jax.lax.all_to_all(v, axis, 0, 0, tiled=False)
                 for k, v in b_payload.items()}
    flat = lambda x: x.reshape((n_shards * cap,) + x.shape[2:])
    return (flat(t_hi), flat(t_lo), {k: flat(v) for k, v in t_payload.items()},
            flat(t_val), dropped)


def _ingest_body(cfg: ShardedConfig, n: int, axis: str):
    """The per-device query-path ingest body (shared by the one-tick step
    and the fused multi-tick replay scan)."""
    base = cfg.base

    def body(state: ShardedState, s_hi, s_lo, q_hi, q_lo, src, valid):
        me = jax.lax.axis_index(axis)
        B = q_hi.shape[0]
        tick_vec = jnp.full((B,), state.tick, jnp.int32)
        sw = jnp.asarray(base.source_weights, jnp.float32)
        w = sw[jnp.clip(src, 0, len(base.source_weights) - 1)]
        # lazy decay policy: same rebase-on-write as the unsharded engine
        dkw = (dict(decay_cfg=base.decay, now=state.tick)
               if base.lazy_decay else {})

        # --- replicated query store: every shard applies the full batch ---
        qstore = stores.insert_accumulate(
            state.qstore, q_hi, q_lo,
            {"weight": w, "count": jnp.ones((B,), jnp.float32),
             "last_tick": tick_vec},
            valid, modes=_Q_MODES, probe_rounds=base.probe_rounds, **dkw)

        # --- sessions: filter to my shard (owner = hash(sess) % n) ---
        sess_owner = (probe_hash(s_hi, s_lo) % jnp.uint32(n)).astype(jnp.int32)
        mine = valid & (sess_owner == me)
        sessions, pairs = stores.update_sessions(
            state.sessions, s_hi, s_lo, q_hi, q_lo, src, state.tick, mine,
            probe_rounds=base.probe_rounds)

        # --- route pairs to their cooccurrence owner ---
        owner = pair_owner(pairs.src_hi, pairs.src_lo, pairs.dst_hi,
                           pairs.dst_lo, cfg.n_salts, n)
        w_src = sw[jnp.clip(pairs.src_code, 0, len(base.source_weights) - 1)]
        w_dst = sw[jnp.clip(pairs.dst_code, 0, len(base.source_weights) - 1)]
        w_pair = jnp.sqrt(w_src * w_dst)
        payload = {"src_hi": pairs.src_hi, "src_lo": pairs.src_lo,
                   "dst_hi": pairs.dst_hi, "dst_lo": pairs.dst_lo,
                   "w": w_pair}
        r_hi, r_lo, r_pl, r_valid, drop = _route(
            pairs.src_hi, pairs.src_lo, owner, payload, pairs.valid,
            n, cfg.route_capacity, axis)
        cooc = cooc_insert_pairs(
            state.cooc, qstore, r_pl["src_hi"], r_pl["src_lo"],
            r_pl["dst_hi"], r_pl["dst_lo"], r_pl["w"], r_valid, state.tick,
            base, dkw)

        return ShardedState(qstore, cooc, sessions, state.tick,
                            state.n_route_drop + drop[None])

    return body


def make_sharded_step(cfg: ShardedConfig, mesh: Mesh, axis: str = "shard"):
    """Build the jitted sharded ingest step (query path)."""
    n = mesh.shape[axis]
    body = _ingest_body(cfg, n, axis)
    rep = P()
    state_spec = _state_spec(cfg, axis)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(state_spec, rep, rep, rep, rep, rep, rep),
                   out_specs=state_spec,
                   check_vma=False)
    return jax.jit(fn)


def _tick_maintenance(state: ShardedState, base: EngineConfig
                      ) -> ShardedState:
    """Per-tick maintenance on the sharded state: the shared
    ``engine.maintenance_cadence`` ladder (ONE copy of the cadence
    semantics) with sharded branch bodies — lazy: prune-only sweeps at
    ``prune_every``, session eviction at ``decay_every``; eager: full
    decay/prune + eviction at ``decay_every``. Runs inside the replay scan
    so replayed ticks mutate state exactly as live ones do."""

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
    """One full live tick (ingest + cadence maintenance + tick advance) —
    the sharded equivalent of ``SearchAssistanceEngine.step``'s state
    mutations, so drivers using it replay exactly under
    ``make_sharded_ingest_many``."""
    n = mesh.shape[axis]
    base = cfg.base
    ingest = _ingest_body(cfg, n, axis)

    def body(state: ShardedState, s_hi, s_lo, q_hi, q_lo, src, valid):
        state = ingest(state, s_hi, s_lo, q_hi, q_lo, src, valid)
        state = _tick_maintenance(state, base)
        return state._replace(tick=state.tick + 1)

    rep = P()
    state_spec = _state_spec(cfg, axis)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(state_spec, rep, rep, rep, rep, rep, rep),
                   out_specs=state_spec, check_vma=False)
    return jax.jit(fn)


def make_sharded_ingest_many(cfg: ShardedConfig, mesh: Mesh,
                             axis: str = "shard"):
    """Fused catch-up replay over the sharded engine (§4.2).

    Each shard consumes the full logged hose (the paper's replicated-
    consumption design), so ONE shared firehose log serves every shard and
    replay is parallel by construction: a single ``lax.scan`` dispatch
    advances all shards through a chunk of R logged ticks — per-tick
    routing ``all_to_all``s included — with the cadence maintenance run
    in-scan (identical state mutations to the live tick step above).

    Takes stacked query-hose arrays ``[R, B]``; returns the advanced state.
    """
    n = mesh.shape[axis]
    base = cfg.base
    ingest = _ingest_body(cfg, n, axis)

    def many(state: ShardedState, s_hi, s_lo, q_hi, q_lo, src, valid):
        def scan_body(st, xs):
            st = ingest(st, *xs)
            st = _tick_maintenance(st, base)
            return st._replace(tick=st.tick + 1), None

        state, _ = jax.lax.scan(
            scan_body, state, (s_hi, s_lo, q_hi, q_lo, src, valid))
        return state

    rep = P()
    state_spec = _state_spec(cfg, axis)
    fn = shard_map(many, mesh=mesh,
                   in_specs=(state_spec, rep, rep, rep, rep, rep, rep),
                   out_specs=state_spec, check_vma=False)
    return jax.jit(fn)


def make_sharded_decay(cfg: ShardedConfig, mesh: Mesh, axis: str = "shard"):
    base = cfg.base

    def body(state: ShardedState, dticks):
        # same fast paths as the unsharded engine: cfg.use_kernel routes the
        # per-shard sweep through the fused multi-lane Pallas kernel; under
        # the lazy policy this degrades to the prune-only sweep (run it at
        # the prune_every cadence, not decay_every).
        if base.lazy_decay:
            qstore, _, _, _ = prune_sweep(state.qstore, state.tick,
                                          cfg=base.decay)
            if base.region_cooc:
                cooc, _, _, _ = region_prune_sweep(
                    state.cooc, qstore, state.tick, cfg=base.decay)
            else:
                cooc, _, _, _ = prune_sweep(state.cooc, state.tick,
                                            cfg=base.decay)
        else:
            qstore, _, _ = sweep_decay_prune(
                state.qstore, dticks, cfg=base.decay,
                use_kernel=base.kernel_on("decay_prune"))
            if base.region_cooc:
                cooc, _, _, _ = region_decay_sweep(
                    state.cooc, qstore, dticks, cfg=base.decay)
            else:
                cooc, _, _ = sweep_decay_prune(
                    state.cooc, dticks, cfg=base.decay,
                    use_kernel=base.kernel_on("decay_prune"))
        sessions = stores.evict_sessions(state.sessions, state.tick,
                                         base.session_ttl)
        return ShardedState(qstore, cooc, sessions, state.tick + 0,
                            state.n_route_drop)

    rep, sh = P(), P(axis)
    state_spec = _state_spec(cfg, axis)
    fn = shard_map(body, mesh=mesh, in_specs=(state_spec, rep),
                   out_specs=state_spec, check_vma=False)
    return jax.jit(fn)


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
        n_route_drop=jnp.zeros((new_n,), jnp.int32))
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
