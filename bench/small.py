"""A cell shrunk to a size the CPU runs in seconds, for the tests.

Capacities and hose rates are cut; every width and rate of the
semantics (session window, weights, decay, gates, top-k) stays. Only the
tests use it: the benchmark's runs never shrink a cell.
"""
from __future__ import annotations

import copy

SMALL_ENGINE = {"query_capacity": 1 << 12, "cooc_capacity": 1 << 16,
                "session_capacity": 1 << 11}
SMALL_HOSE = {"vocab_size": 1 << 10, "n_users": 512, "queries_per_tick": 256,
              "tweets_per_tick": 16}
SMALL_TRAFFIC = {"fill_ticks": 40, "fill_chunk_ticks": 16, "warm_ticks": 4,
                 "max_cycles": 4, "backlog_ticks": 48}


def shrink(cell):
    """A copy of ``cell`` at the small size."""
    c = copy.deepcopy(cell)
    c.config["engine"].update(SMALL_ENGINE)
    c.config["hose"].update(SMALL_HOSE)
    for k, v in SMALL_TRAFFIC.items():
        if k in c.traffic:
            c.traffic[k] = v
    return c


def run_small(workload: str, root: str, seed: int = 2**31 + 11,
              seconds: float = 0.5) -> dict:
    """One run of ``workload`` at the small size on this process's first
    device, skipping the harness's look for a chip; scratch files go
    under ``root``."""
    import os
    import time

    import jax

    from bench import harness
    bench_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = shrink(harness.resolve(harness.load_benchmark(bench_root),
                                  workload))
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                            root=root, t_start=time.perf_counter(),
                            device=jax.devices()[0], n_devices=1)
