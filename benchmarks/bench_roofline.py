"""§Roofline summary.

Two row families:

* dry-run cells: reads the sweep output (results/*.json) and prints the
  per-cell three-term roofline rows. The dry-run itself is run separately
  (512-device flag must be set before jax init):

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \\
        --out results/dryrun_baseline.json

* ``roofline_hot:*``: distance-to-roofline for every TUNED engine hot
  path — the autotuner measures each op under its winning variant and
  ``roofline.hot_path_roofline`` turns the analytic bytes/flops model
  (``autotune.hot_path_traffic``) into a fraction-of-memory-ceiling row.
  Emitted on devices with published peaks (``mesh.DEVICE_PEAKS``), both
  store layouts; elsewhere one "not measured" row.
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


def _hot_path_rows() -> List[Row]:
    import dataclasses

    import jax

    from repro.core.engine import EngineConfig
    from repro.launch.autotune import hot_path_traffic, measure_plan
    from repro.launch.mesh import DEVICE_PEAKS
    from repro.launch.roofline import hot_path_roofline

    from .bench_autotune import _tuned_key

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        # a roofline share needs the device's own peaks: none here
        return [("roofline_hot", 0.0,
                 f"not measured: no published peaks for {kind!r}")]
    rows: List[Row] = []
    base = EngineConfig(query_capacity=1 << 13, cooc_capacity=1 << 15,
                        session_capacity=1 << 13)
    for layout in ("hash", "region"):
        cfg = dataclasses.replace(base, cooc_layout=layout)
        plan, timings = measure_plan(cfg, repeats=2, tune_ingest=False)
        for op, tf in hot_path_traffic(cfg).items():
            t_us = timings.get(_tuned_key(plan, op))
            if t_us is None:
                continue
            r = hot_path_roofline(op, bytes_touched=tf["bytes"],
                                  flops=tf["flops"], measured_us=t_us)
            rows.append((
                f"roofline_hot:{layout}:{op}", t_us,
                f"variant={'kernel' if plan.uses_kernel(op) else 'jnp'} "
                f"bound={r['bottleneck']} "
                f"frac={r['roofline_fraction']:.4f} "
                f"tM={r['t_memory_s']:.2e} tC={r['t_compute_s']:.2e}"))
    return rows


def run() -> List[Row]:
    rows: List[Row] = _hot_path_rows()
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
