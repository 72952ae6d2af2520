"""Three-term roofline from a compiled dry-run artifact.

  compute term    = HLO_FLOPs_global / (chips x peak_FLOP/s)
  memory term     = HLO_bytes_global / (chips x HBM_bw)
  collective term = collective_bytes / (chips x link_bw)

``compiled.cost_analysis()`` reports the PER-DEVICE partitioned module
(SPMD compiles one program), so global = per-device x chips. Collective
bytes are not in cost_analysis: we parse the compiled HLO and sum the
result-shape bytes of every collective op (a device-bytes-moved proxy:
all-reduce moves ~2x this in a ring, all-gather receives exactly this;
we additionally report per-op-kind counts so the §Perf loop can see WHICH
collective dominates).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-gather.3 = bf16[2,1024,512]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^ ]*\s*,?\s*)+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# Ops that genuinely materialize HBM traffic on a fusing (TPU) backend.
# Elementwise chains (convert/multiply/add/broadcast/select/...) fuse into
# their consumers on TPU and are excluded — the CPU backend leaves them
# top-level, which is why raw "bytes accessed" over-states traffic >10x.
_MATERIALIZING = (
    "dot", "convolution", "fusion",
    "dynamic-slice", "dynamic-update-slice", "gather", "scatter",
    "concatenate", "pad", "reverse", "sort", "rng", "rng-bit-generator",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "select-and-scatter", "custom-call", "cholesky",
    "triangular-solve",
)
# copy/transpose/reduce/elementwise are CPU-backend artifacts: on TPU they
# fuse into consumers (layout assignment + loop fusion), so they are not
# counted as HBM traffic.
_OPCODE_RE = re.compile(r"([a-z0-9\-]+)\(")


def fusion_aware_bytes(hlo_text: str) -> int:
    """Fusion-aware HBM traffic estimate from the COMPILED module.

    Sum 2x result bytes (write + downstream read) over instructions whose
    opcode genuinely materializes on TPU (_MATERIALIZING), + parameter
    bytes once. Result shapes of multi-output ops count every element.
    """
    total = 0
    in_fusion = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and "->" in s:       # computation header
            in_fusion = "fused" in s.split("(")[0]
            continue
        if in_fusion or "= " not in line:
            continue
        rhs = line.split("= ", 1)[1]
        mop = _OPCODE_RE.search(rhs)
        if not mop:
            continue
        op = mop.group(1)
        shapes_str = rhs[: mop.start()]
        b = sum(shape_bytes(dt, dims)
                for dt, dims in _SHAPE_RE.findall(shapes_str))
        if op == "parameter":
            total += b
            continue
        base = op.removesuffix("-start").removesuffix("-done")
        if base not in _MATERIALIZING or op.endswith("-done"):
            continue
        total += 2 * b
    return total


def collective_bytes(hlo_text: str) -> Tuple[int, Dict[str, int], Dict[str, int]]:
    """-> (total_bytes, bytes_by_kind, count_by_kind). Sums result shapes;
    `-done` ops are skipped (the `-start` carries the shape)."""
    total = 0
    by_kind: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        b = sum(shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(shapes))
        total += b
        by_kind[kind] += b
        counts[kind] += 1
    return total, by_kind, counts


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_global: float
    bytes_global: float
    coll_bytes: float
    coll_by_kind: Dict[str, int]
    coll_counts: Dict[str, int]
    model_flops: float
    peak_flops: float
    hbm_bw: float
    link_bw: float
    memory_per_device: Optional[Dict] = None

    @property
    def t_compute(self) -> float:
        return self.flops_global / (self.chips * self.peak_flops)

    @property
    def t_memory(self) -> float:
        return self.bytes_global / (self.chips * self.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * self.link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / max(self.flops_global, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-at-peak time over the dominant-term time: how close
        the step is to the best this hardware could do on the useful math."""
        t_ideal = self.model_flops / (self.chips * self.peak_flops)
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_ideal / max(t_bound, 1e-30)

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_global": self.flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "coll_bytes": self.coll_bytes,
            "coll_counts": {k: v for k, v in self.coll_counts.items() if v},
            "memory_per_device": self.memory_per_device,
        }
