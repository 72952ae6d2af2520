"""Record the small profiler trace that ``bench/test_trace.py`` parses.

    python bench/record_trace.py OUT_DIR

Runs on one accelerator: two jitted programs with host spans and idle
gaps between them, traced with ``jax.profiler``. Copy the resulting
``*.xplane.pb`` to ``bench/testdata/`` to refresh the recorded trace.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main() -> int:
    out = os.path.abspath(sys.argv[1])
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("record_trace: no accelerator", file=sys.stderr)
        return 1

    @jax.jit
    def scale_sort(x):
        return jnp.sort(x * 1.5 + 1.0)

    @jax.jit
    def reduce_sum(x):
        return jnp.sum(x * x)

    x = jnp.arange(1 << 22, dtype=jnp.float32)
    scale_sort(x).block_until_ready()
    reduce_sum(x).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.sort"):
            scale_sort(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.sum"):
            reduce_sum(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    print(f"trace {path} {os.path.getsize(path)} B "
          f"device_kind={dev.device_kind!r}")
    pd = jax.profiler.ProfileData.from_file(path)
    for pl in pd.planes:
        lines = list(pl.lines)
        print(f"PLANE {pl.name!r} lines={[ln.name for ln in lines]}")
        for ln in lines:
            evs = list(ln.events)
            print(f"  LINE {ln.name!r} n={len(evs)}")
            for e in evs[:4]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                      f" stats={dict(e.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
