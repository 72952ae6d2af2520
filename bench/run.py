#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload rt_hash_c25.refresh --seed 7 \
        --seconds 10 --trace 0

From the root of a checkout. The cell, its configuration, its traffic and
its metrics are found by name from ``BENCHMARK.json`` (see
``bench/harness.py``). With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. The numbers that decide ``correct`` are
printed last on standard error, each beside its limit, and the result is
the last line of standard output. Without an accelerator, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: run from a checkout of the repository "
              "(src/repro not found)", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench import harness
    try:
        cell = harness.resolve(harness.load_benchmark(ROOT), args.workload)
    except (harness.BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    # the TPU runtime logs to a fixed directory under /tmp by default
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} accelerator chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    counters = {}
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), root=ROOT,
                              t_start=T_START, device=devs[0],
                              n_devices=len(devs), counters=counters)
    for name, v in counters.items():
        print(f"counter {name} = {v!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
