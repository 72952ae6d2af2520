"""Elastic scaling: reshard a checkpoint onto a different mesh.

Checkpoints store host (unsharded) arrays; loading for a new mesh is a
device_put with the target NamedShardings derived from the same logical
sharding rules — so a run checkpointed on a (16, 16) single pod restarts
unchanged on (2, 16, 16), (8, 8), or 1 device. The only requirement is
that sharded dims remain divisible by the new axis sizes (checked here,
with clear errors)."""
from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from .sharding import params_shardings, use_mesh


def validate_divisibility(tree: Any, shardings: Any, mesh: Mesh) -> List[str]:
    problems = []

    def check(path, leaf, sh):
        if not isinstance(sh, NamedSharding):
            return
        for dim, axes in enumerate(sh.spec):
            if axes is None:
                continue
            ax_tuple = axes if isinstance(axes, tuple) else (axes,)
            size = int(np.prod([mesh.shape[a] for a in ax_tuple]))
            if leaf.shape[dim] % size:
                problems.append(
                    f"{'/'.join(map(str, path))}: dim {dim} ({leaf.shape[dim]})"
                    f" not divisible by {size}")
    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), tree, shardings)
    return problems


def reshard_for_mesh(host_tree: Any, mesh: Mesh,
                     rules: Sequence[Tuple[str, Tuple]]) -> Any:
    """Place a host pytree onto ``mesh`` under the logical rules."""
    shape_tree = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        host_tree)
    shardings = params_shardings(mesh, shape_tree, rules)
    problems = validate_divisibility(shape_tree, shardings, mesh)
    if problems:
        raise ValueError("cannot reshard: " + "; ".join(problems))
    with use_mesh(mesh):
        return jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), host_tree, shardings)


# ---------------------------------------------------------------------------
# Live shard split/merge driver (elastic scaling of the SHARDED engine).
#
# The replicated fleet (distributed.fleet) scales availability; this scales
# capacity: ShardAutoscaler turns metrics pressure (region freelist, replay
# lag, routing drops) into split/merge decisions with hysteresis, and
# live_reshard performs the zero-downtime handoff — re-partition the state
# (core.sharded_engine.reshard_sharded_state), then replay the ticks that
# arrived during the repartition window from the shared firehose log, so
# the new shard layout is bit-exact with a run that resharded with the
# world stopped. The old state keeps serving until the new one is caught
# up; the swap is a pointer flip.
# ---------------------------------------------------------------------------
import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    min_shards: int = 1
    max_shards: int = 64
    split_free_frac: float = 0.10   # split when free-region fraction < this
    split_lag_ticks: float = 8.0    # ... or replay lag exceeds this
    merge_free_frac: float = 0.60   # merge when free fraction > this ...
    merge_lag_ticks: float = 1.0    # ... and lag is at most this
    hold_ticks: int = 3             # hysteresis: pressure must persist


class ShardAutoscaler:
    """Hysteresis-gated split/merge decisions off the serving metrics.

    Feed it one observation per tick; it returns the proposed shard count
    (== current when no action). A single spiky tick never reshards: the
    split signal must persist ``hold_ticks`` consecutive observations, and
    the merge signal likewise — mirroring the overload ladder's up-fast /
    down-slow asymmetry (splits use the same hold, merges also reset on
    any pressure)."""

    def __init__(self, cfg: AutoscaleConfig = AutoscaleConfig()):
        self.cfg = cfg
        self._hot = 0
        self._cold = 0

    def observe(self, n_shards: int, *, free_region_frac: Optional[float],
                lag_ticks: float = 0.0, route_drop_rate: float = 0.0) -> int:
        c = self.cfg
        pressured = ((free_region_frac is not None
                      and free_region_frac < c.split_free_frac)
                     or lag_ticks > c.split_lag_ticks
                     or route_drop_rate > 0.0)
        idle = ((free_region_frac is None
                 or free_region_frac > c.merge_free_frac)
                and lag_ticks <= c.merge_lag_ticks
                and route_drop_rate == 0.0)
        self._hot = self._hot + 1 if pressured else 0
        self._cold = self._cold + 1 if (idle and not pressured) else 0
        if self._hot >= c.hold_ticks and 2 * n_shards <= c.max_shards:
            self._hot = self._cold = 0
            return 2 * n_shards
        if self._cold >= c.hold_ticks and n_shards >= 2 \
                and n_shards // 2 >= c.min_shards:
            self._cold = 0
            return n_shards // 2
        return n_shards


def sharded_pressure(state, base_cfg) -> Dict[str, float]:
    """The autoscaler's inputs from a ShardedState: worst-shard region
    freelist fraction (region layout; None otherwise) and the routing drop
    count since the last reshard."""
    import numpy as np
    n = state.n_route_drop.shape[0]
    free_frac = None
    if base_cfg.region_cooc:
        owner = np.asarray(state.cooc.region_owner)
        per = owner.shape[0] // n
        frac = [(owner[i * per:(i + 1) * per] < 0).mean() for i in range(n)]
        free_frac = float(min(frac))
    return {"free_region_frac": free_frac,
            "route_drop": int(np.asarray(state.n_route_drop).sum())}


def live_reshard(cfg, state, new_n: int, mesh, *, log_dir: Optional[str] = None,
                 log_name: str = "firehose", chunk_ticks: int = 8,
                 axis: str = "shard"):
    """Split/merge a live sharded engine with zero-downtime handoff.

    Re-partitions ``state`` to ``new_n`` shards, then (when ``log_dir`` is
    given) catches the new state up through the shared firehose log's tail
    — the ticks that arrived while the repartition ran and the old state
    kept serving them, queries and tweets alike. Returns ``(new_state,
    stats)``; the caller swaps serving over once ``stats["replayed_ticks"]``
    has covered its head.
    ``mesh`` must span ``new_n`` devices along ``axis`` — the replay runs
    under the new layout's fused scan, whose per-tick state mutations are
    identical to the live sharded tick step (that is the bit-exactness
    property the handoff leans on).
    """
    from ..core.sharded_engine import (make_sharded_ingest_many,
                                      reshard_sharded_state)
    assert mesh.shape[axis] == new_n, \
        f"mesh has {mesh.shape[axis]} shards along {axis!r}, want {new_n}"
    new_state, stats = reshard_sharded_state(cfg, state, new_n)
    stats["replayed_ticks"] = 0
    if log_dir is not None:
        from ..streaming.log import FirehoseLogReader
        from ..streaming.replay import chunk_to_stack
        reader = FirehoseLogReader(log_dir, name=log_name)
        head = reader.last_tick()
        t0 = int(jnp.asarray(new_state.tick))
        if head is not None and head + 1 > t0:
            ingest = make_sharded_ingest_many(cfg, mesh, axis)
            for chunk in reader.read_chunks(t0, chunk_ticks,
                                            upto_tick=head + 1):
                new_state = ingest(new_state, chunk_to_stack(chunk))
                stats["replayed_ticks"] += chunk.n_ticks
    return new_state, stats
