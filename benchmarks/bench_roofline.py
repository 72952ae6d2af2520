"""§Roofline summary.

Reads the dry-run sweep output (results/*.json) and prints the per-cell
three-term roofline rows. The dry-run itself is run separately
(512-device flag must be set before jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \\
        --out results/dryrun_baseline.json
"""
from __future__ import annotations

import json
import os
from typing import List

from .common import Row

RESULTS = [
    ("baseline", "results/dryrun_baseline.json"),
    ("optimized", "results/dryrun_optimized.json"),
]


def run() -> List[Row]:
    rows: List[Row] = []
    for tag, path in RESULTS:
        if not os.path.exists(path):
            rows.append((f"roofline_{tag}", 0.0, f"missing {path} (run dryrun)"))
            continue
        with open(path) as f:
            data = json.load(f)
        ok = [r for r in data if r.get("status") == "ok"]
        skip = [r for r in data if r.get("status") == "skipped"]
        err = [r for r in data if r.get("status") == "error"]
        rows.append((f"roofline_{tag}_cells", 0.0,
                     f"ok={len(ok)} skipped={len(skip)} errors={len(err)}"))
        for r in ok:
            name = f"{r['arch']}/{r['shape']}/{r['mesh']}"
            rows.append((
                f"roofline_{tag}:{name}", 0.0,
                f"bound={r['bottleneck']} frac={r['roofline_fraction']:.3f} "
                f"tC={r['t_compute_s']:.2e} tM={r['t_memory_s']:.2e} "
                f"tX={r['t_collective_s']:.2e}"))
    return rows
