"""The benchmark harness: finds a cell's files by name and runs it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* configuration ``<c>``: ``bench/configs/<c>.json``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, whose ``mode`` names
  the procedure in ``bench/modes/<mode>.py`` that sets the cell up,
  measures its window and checks what the window produced;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)``
  returns the number or None when the run holds nothing to read.

A mode module has ``setup(run)``, ``window(run, state)`` and
``check(run, state)``; see ``modes/refresh.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional


class BenchError(RuntimeError):
    """The benchmark's files are inconsistent or the machine is wrong."""


BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    if not os.path.exists(path):
        raise BenchError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                         f"file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, name: str) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r} (known: "
                         f"{sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name}: unknown config {w['config']!r}")
    cfile = configs[w["config"]]["file"]
    if os.path.basename(cfile) != f"{w['config']}.json":
        raise BenchError(f"config {w['config']}: file {cfile} is not named "
                         f"after it")
    config = _json("configs", w["config"])
    traffic = _json("traffic", w["traffic"])
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


# ---------------------------------------------------------------------------
# the program under test, built from a configuration file
# ---------------------------------------------------------------------------

def engine_configs(config: dict) -> Dict[str, Any]:
    """``{"rt": EngineConfig[, "bg": EngineConfig]}`` as the configuration
    file states them."""
    from repro.core.background import background_config
    from repro.core.decay import DecayConfig
    from repro.core.engine import EngineConfig
    from repro.core.ranking import RankConfig

    e = config["engine"]
    r = e["rank"]
    rank = RankConfig(top_k=r["top_k"], coef_condprob=r["coefs"][0],
                      coef_pmi=r["coefs"][1], coef_llr=r["coefs"][2],
                      coef_chi2=r["coefs"][3],
                      min_pair_weight=r["min_pair_weight"],
                      min_src_weight=r["min_src_weight"],
                      min_pair_count=r["min_pair_count"])
    decay = DecayConfig(kind="exp", half_life_ticks=e["half_life_ticks"],
                        prune_threshold=e["prune_threshold"],
                        policy=e["decay_policy"])
    rt = EngineConfig(
        query_capacity=e["query_capacity"], cooc_capacity=e["cooc_capacity"],
        session_capacity=e["session_capacity"],
        session_window=e["session_window"],
        source_weights=tuple(e["source_weights"]),
        tweet_weight=e["tweet_weight"],
        min_querylike_count=e["min_querylike_count"],
        decay_every=e["decay_every"], rank_every=e["rank_every"],
        prune_every=e["prune_every"], session_ttl=e["session_ttl"],
        decay=decay, rank=rank, cooc_layout=e["cooc_layout"])
    out = {"rt": rt}
    if config["service"] == "rt+bg":
        b = config["background"]
        bg = background_config(rt, half_life_mult=b["half_life_mult"],
                               rank_every_mult=b["rank_every_mult"])
        want = (e["half_life_ticks"] * b["half_life_mult"],
                e["prune_threshold"] * b["prune_threshold_mult"],
                e["prune_every"])
        got = (bg.decay.half_life_ticks, bg.decay.prune_threshold,
               bg.prune_every)
        if got != want:
            raise BenchError(f"the program's background engine is not the "
                             f"configuration's: {got} vs {want}")
        out["bg"] = bg
    return out


def semantics(config: dict):
    """The reference's view of each engine of the configuration."""
    from bench.reference import Semantics
    e = config["engine"]
    out = {"rt": Semantics.from_config(e)}
    if config["service"] == "rt+bg":
        b = config["background"]
        out["bg"] = Semantics.from_config(
            e, half_life_mult=b["half_life_mult"],
            threshold_mult=b["prune_threshold_mult"])
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Spans:
    """Host spans around the calls into each layer: kept in memory, and
    written into the profiler's trace as ``bench.<layer>`` when tracing."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, layer: str):
        ann = None
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{layer}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((layer, t0, t1))

    def total(self, layer: str) -> float:
        return sum(t1 - t0 for name, t0, t1 in self.spans if name == layer)

    def count(self, layer: str) -> int:
        return sum(name == layer for name, _, _ in self.spans)


@dataclasses.dataclass
class Run:
    """What a mode and a metric reader see of one run."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    root: str
    work: str                       # scratch directory inside the checkout
    spans: Spans
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    summary: Any = None             # trace.TraceSummary of the window
    device_kind: str = ""
    # (name, value, limit): what decides ``correct``
    checks: List[tuple] = dataclasses.field(default_factory=list)

    def autotune_cache(self) -> str:
        return os.path.join(self.root, ".bench_cache", "autotune")


class CompileCounter:
    """Counts the compilations JAX reports while it is open."""

    def __init__(self):
        import jax
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             root: str, t_start: float, device,
             n_devices: int, counters: Optional[dict] = None) -> dict:
    """Set up, measure, check; the result line's keys. ``counters``, when
    given, receives the run's counters (cycles, ticks, sources, ...)."""
    import jax
    from bench import trace_reduce as trace_mod

    work = os.path.join(root, ".bench_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace, root=root,
              work=work, spans=Spans(trace), device_kind=device.device_kind)
    mode = load_module("modes", cell.traffic["mode"])
    compiles = CompileCounter()
    try:
        state = mode.setup(run)
        setup_s = time.perf_counter() - t_start
        n_setup = len(run.spans.spans)
        for name, t0, t1 in run.spans.spans:
            run.counters[f"setup.{name}_s"] = t1 - t0
        trace_dir = os.path.join(work, "trace")
        if trace:
            # the benchmark's spans and the device, without tracing every
            # Python call (which would inflate the host spans)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles.on = True
        with run.spans("window"):
            out = mode.window(run, state)
        compiles.on = False
        if trace:
            jax.profiler.stop_trace()
        for name, t0, t1 in run.spans.spans[n_setup:]:
            key = f"window.{name}_s"
            run.counters[key] = run.counters.get(key, 0.0) + t1 - t0
        run.counters["compiles_in_window"] = compiles.n
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if trace:
            run.summary = trace_mod.summarize(trace_mod.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        t_check = time.perf_counter()
        mode.check(run, state)
        run.counters["check_s"] = time.perf_counter() - t_check
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if counters is not None:
        counters.update(run.counters)
    values = dict(out["metrics"], setup_s=setup_s)
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"mode {cell.traffic['mode']} gave no "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": n_devices, "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for _, v, lim in run.checks)
              and bool(run.checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": dev}
    if trace:
        s = run.summary
        dev["busy_s"] = s.busy_s
        dev["window_s"] = s.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in s.top_ops],
                               "idle_gaps": [list(x) for x in s.idle_gaps]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in run.checks}
    return result


def count_fill(run, engine: str, state) -> None:
    """Counters ``fill.<engine>.<store>``: the share of each store's slots
    that hold a live entry, which is what its capacity is sized for."""
    import jax.numpy as jnp
    for store in ("qstore", "cooc", "sessions"):
        t = getattr(state, store)
        live = int(jnp.count_nonzero((t.key_hi | t.key_lo) != 0))
        run.counters[f"fill.{engine}.{store}"] = live / t.key_hi.shape[0]


def make_reference(config: dict, engine: str, hose, lane_dtype=None):
    """The plain reference of one engine of ``config``, over the hose's
    query universe."""
    from bench.reference import Reference
    return Reference(semantics(config)[engine], hose.fps, lane_dtype)
