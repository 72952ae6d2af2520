"""TunedPlan — the serializable record of backend-aware kernel tuning.

The engine used to route its hot paths through a blind
``EngineConfig.use_kernel: bool``; on CPU CI that flag sent production
traffic through the Pallas *interpreter* and lost 2-25x to the plain jnp
twins (the ``ranking_cycle_*_pallas`` bench regression). A ``TunedPlan``
replaces the flag with per-hot-path choices *measured* on the running
backend by ``repro.launch.autotune`` and cached to disk keyed by
:func:`shape_class`.

Design constraints (all load-bearing):

* **Hashable + frozen** — ``EngineConfig``/``RankConfig`` are static jit
  arguments, and the plan is embedded in both, so it must hash and
  compare by value.
* **Serializable** — the plan round-trips through JSON (disk cache,
  snapshot/checkpoint meta) so a recovered engine keeps its tuning.
* **Result-invariant** — every field selects between implementations that
  produce bit-exact engine states and suggestion tables; knobs that
  change results (store capacities, ``region_width``, the semantic
  ingest quantum) live in ``EngineConfig`` and are out of bounds for the
  tuner. Tuning may change speed, never results (property-tested in
  ``tests/test_autotune.py``).

This module is deliberately dependency-free (core must import it without
pulling in the launch/tuner machinery).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Tuple

# The kernel-vs-jnp hot-path pairs the tuner measures (see the dispatch
# table in ``repro/kernels/__init__.py``).
HOT_PATH_OPS: Tuple[str, ...] = (
    "score_gate", "bucket_topk", "region_rank", "chain_find", "decay_prune")

KERNEL, JNP = "kernel", "jnp"


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """Per-hot-path execution choices. Defaults = the all-jnp reference
    plan (what an untuned engine without the legacy flag runs)."""
    score_gate: str = JNP
    bucket_topk: str = JNP
    region_rank: str = JNP
    chain_find: str = JNP
    decay_prune: str = JNP
    # tile/grid tuning: rows (of 1024 slots) per score_gate/region grid
    # step. In interpret mode fewer, larger blocks amortize the
    # interpreter's per-step XLA re-entry (measured 11x spread on CPU).
    score_block_rows: int = 16
    # events fused per device dispatch when step()/ingest_many chunk an
    # oversized batch into ``EngineConfig.ingest_quantum``-sized slices:
    # chunk = k * quantum means k quantum slices ride ONE lax.scan
    # dispatch. 0 = one dispatch per slice. Pure dispatch scheduling —
    # the slicing itself is plan-independent, so results are identical.
    ingest_chunk: int = 0
    # provenance (not consulted by dispatch)
    backend: str = ""
    shape_class: str = ""

    def __post_init__(self):
        for op in HOT_PATH_OPS:
            v = getattr(self, op)
            if v not in (KERNEL, JNP):
                raise ValueError(f"plan.{op} must be 'kernel' or 'jnp', "
                                 f"got {v!r}")

    def uses_kernel(self, op: str) -> bool:
        if op not in HOT_PATH_OPS:
            raise KeyError(f"unknown hot path {op!r}")
        return getattr(self, op) == KERNEL

    def variants(self) -> Dict[str, str]:
        """op -> chosen variant, for metrics/telemetry surfaces."""
        d = {op: getattr(self, op) for op in HOT_PATH_OPS}
        d["score_block_rows"] = self.score_block_rows
        d["ingest_chunk"] = self.ingest_chunk
        return d

    # ---- serialization (disk cache + snapshot meta) ----
    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict) -> "TunedPlan":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def dumps(self) -> str:
        return json.dumps(self.to_json(), sort_keys=True)

    @classmethod
    def loads(cls, s: str) -> "TunedPlan":
        return cls.from_json(json.loads(s))


#: The all-jnp plan (also the graceful fallback when Pallas is broken or
#: absent: every choice is the reference path).
JNP_PLAN = TunedPlan()


def all_kernel_plan(**overrides) -> TunedPlan:
    """Every hot path through its Pallas kernel (parity testing)."""
    kw = {op: KERNEL for op in HOT_PATH_OPS}
    kw.update(overrides)
    return TunedPlan(**kw)


def default_region_width(cooc_capacity: int) -> int:
    """Default pairs-per-region derived from the cooc capacity.

    The mapping the benches want — {2^16: 16, 2^18: 32, 2^20: 64} — i.e.
    width grows with the square root of capacity (Asadi & Lin's
    skew-aware allocation argument: bigger stores hold fatter heads),
    clamped to the [8, 128] range the region kernels tile well.
    """
    if cooc_capacity <= 0:
        raise ValueError(f"bad cooc_capacity {cooc_capacity}")
    log2c = cooc_capacity.bit_length() - 1
    return 1 << min(7, max(3, log2c // 2 - 4))


def shape_class(cfg, backend: Optional[str] = None,
                device_kind: Optional[str] = None) -> str:
    """The autotune cache key: same string => same cached plan applies.

    Captures everything dispatch-performance depends on — backend +
    device kind, log2 store capacities, cooc layout and region width —
    and nothing results depend on the plan for.
    """
    import jax
    b = backend if backend is not None else jax.default_backend()
    if device_kind is None:
        # no fallback: a plan keyed to an unknown device would be reused
        # on whatever device next fails the same lookup
        device_kind = jax.devices(b)[0].device_kind
    dk = str(device_kind).replace(" ", "-").replace("/", "-").lower()
    parts = [b, dk,
             f"q{cfg.query_capacity.bit_length() - 1}",
             f"c{cfg.cooc_capacity.bit_length() - 1}",
             f"s{cfg.session_capacity.bit_length() - 1}",
             cfg.cooc_layout]
    if cfg.cooc_layout == "region":
        parts.append(f"w{cfg.region_w}")
    return "-".join(parts)
