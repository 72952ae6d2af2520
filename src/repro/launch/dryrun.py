import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The ONLY entry point that forces 512 placeholder devices (set above before
any other import — jax locks the device count on first init). For every
cell this:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. derives params/optimizer/batch shardings from the arch's logical rules,
  3. jit(train_step | serve_step).lower(<ShapeDtypeStructs>).compile(),
  4. records memory_analysis + cost_analysis + collective bytes (roofline).

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_arch, list_archs
from ..distributed import sharding as shd
from ..models import api, transformer as tr
from ..training import optimizer as optim
from ..training.train_loop import TrainConfig, init_train_state, make_train_step
from . import roofline as rl
from .mesh import device_peaks, make_production_mesh

# the production mesh the dry-run models is made of v5e chips
MODELED_DEVICE = "TPU v5 lite"


def _opt_config(cfg) -> optim.AdamWConfig:
    master = isinstance(cfg, tr.LMConfig) and cfg.dtype == "bfloat16"
    return optim.AdamWConfig(master_weights=master)


def _cache_shardings(mesh, caches_shape):
    """KV caches: [L, B, T, Hkv, D] — batch over dp, cache length over tp
    (kv-head counts rarely divide tp; the T dim always does)."""
    def spec(leaf):
        if leaf.ndim == 5:
            return NamedSharding(
                mesh, shd.resolve(None, "dp", "tp", None, None,
                                  shape=leaf.shape))
        if leaf.ndim >= 2:
            return NamedSharding(
                mesh, shd.resolve(None, "dp", *([None] * (leaf.ndim - 2)),
                                  shape=leaf.shape))
        return NamedSharding(mesh, P())
    return jax.tree.map(spec, caches_shape)


def _compile_for(cfg, spec, cell, mesh, accum=None):
    """Lower + compile one configuration; returns the compiled artifact.

    accum=None uses the memory policy (4-way for LM train); cost probes pass
    accum=1 — a trip-count-4 accumulation scan would be cost-counted once.
    """
    with shd.use_mesh(mesh):
        params_shape = api.abstract_params(cfg)
        rules = (api.sharding_rules(cfg) if cell.kind == "train"
                 else api.serve_rules(cfg))
        p_shard = shd.params_shardings(mesh, params_shape, rules)
        specs = api.input_specs(cfg, cell)
        baxis = api.batch_axis_for(cfg, cell)

        if cell.kind == "train":
            ocfg = _opt_config(cfg)
            # LM train cells: 4-way grad accumulation keeps the live
            # activation set within 16GB/chip (global batch unchanged).
            if accum is None:
                if isinstance(cfg, tr.LMConfig):
                    accum = 8 if cfg.moe else 4
                else:
                    accum = 1
            tcfg = TrainConfig(opt=ocfg, grad_accum=accum)
            state_shape = jax.eval_shape(
                lambda: init_train_state(
                    jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 params_shape), tcfg))
            # optimizer moments follow the param shardings
            def state_shardings(sub):
                return shd.params_shardings(mesh, sub, rules)
            s_shard = {k: (state_shardings(v) if k in ("m", "v", "master", "ef")
                           else NamedSharding(mesh, P()))
                       for k, v in state_shape["opt"].items()}
            s_shard = {"opt": s_shard}
            b_shard = shd.batch_shardings(mesh, specs["batch"],
                                          batch_axis=baxis)
            step = make_train_step(api.loss_fn(cfg), tcfg)
            fn = jax.jit(step,
                         in_shardings=(p_shard, s_shard, b_shard),
                         out_shardings=(p_shard, s_shard, None),
                         donate_argnums=(0, 1))   # alias state in/out
            lowered = fn.lower(params_shape, state_shape, specs["batch"])
        elif cell.kind in ("prefill", "decode"):
            caches_shape = specs["caches"]
            c_shard = _cache_shardings(mesh, caches_shape)
            tok_shard = shd.batch_shardings(mesh, specs["tokens"])
            sfn = api.serve_fn(cfg, cell)
            fn = jax.jit(sfn,
                         in_shardings=(p_shard, c_shard, tok_shard),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
            lowered = fn.lower(params_shape, caches_shape, specs["tokens"])
        else:  # serve / retrieval
            b_shard = shd.batch_shardings(mesh, specs["batch"],
                                          batch_axis=baxis)
            sfn = api.serve_fn(cfg, cell)
            fn = jax.jit(sfn, in_shardings=(p_shard, b_shard))
            lowered = fn.lower(params_shape, specs["batch"])

        return lowered.compile()


def _costs(compiled, chips):
    ca = compiled.cost_analysis()
    # jaxlib has returned both a dict and a per-device *list* of dicts from
    # cost_analysis() across versions; normalize to one flat dict.
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    txt = compiled.as_text()
    coll, by_kind, counts = rl.collective_bytes(txt)
    # fusion-aware HBM traffic (see roofline.fusion_aware_bytes): XLA's raw
    # "bytes accessed" counts pre-fusion operand bytes and over-states HBM
    # traffic by >10x on a fusing backend; we report both, the roofline
    # memory term uses the fusion-aware estimate.
    return (float(ca.get("flops", 0.0)) * chips,
            float(rl.fusion_aware_bytes(txt)) * chips,
            float(coll), by_kind, counts,
            float(ca.get("bytes accessed", 0.0)) * chips)


def build_cell(arch_id: str, shape_name: str, multi_pod: bool
               ) -> Optional[Dict[str, Any]]:
    """Lower + compile one cell. Returns the roofline row (or skip record).

    XLA's cost_analysis counts a while-loop body ONCE regardless of trip
    count, so a scanned L-layer transformer under-reports by ~L. We
    therefore compile L=1 and L=2 twins of LM cells and extrapolate:
      cost(L) = cost(1) + (L-1) * (cost(2) - cost(1)).
    The FULL config is still compiled — that compile (and its
    memory_analysis) is the deliverable proving the cell fits and shards.
    """
    spec = get_arch(arch_id)
    cell = spec.cell(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if cell.skip:
        return {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": cell.skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = spec.config
    if spec.family == "gnn":
        from ..configs.gat_cora import adapt_config
        cfg = adapt_config(cfg, cell)
    if isinstance(cfg, tr.LMConfig):
        dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        cfg = api.adapt_lm_config(cfg, cell, dp_size=dp)

    t0 = time.time()
    compiled = _compile_for(cfg, spec, cell, mesh)
    t_compile = time.time() - t0

    flops_g, bytes_g, coll, by_kind, counts, bytes_raw = _costs(compiled, chips)
    if isinstance(cfg, tr.LMConfig) and cfg.n_layers > 2:
        # XLA counts a while body once regardless of trip count, so the
        # scanned stack under-reports by ~L. Probe with FULLY-UNROLLED
        # 2- and 4-layer twins: body = (cost(4) - cost(2)) / 2, then
        # cost(L) = cost(2) + (L - 2) * body.
        L = cfg.n_layers
        c2 = _costs(_compile_for(
            dataclasses.replace(cfg, n_layers=2, scan_unroll=2),
            spec, cell, mesh, accum=1), chips)
        c4 = _costs(_compile_for(
            dataclasses.replace(cfg, n_layers=4, scan_unroll=4),
            spec, cell, mesh, accum=1), chips)
        ext = lambda a2, a4: a2 + (L - 2) * max(a4 - a2, 0.0) / 2.0
        flops_g = ext(c2[0], c4[0])
        bytes_g = ext(c2[1], c4[1])
        coll = ext(c2[2], c4[2])
        by_kind = {k: int(ext(c2[3][k], c4[3][k])) for k in c2[3]}
        counts = {k: int(ext(c2[4][k], c4[4][k])) for k in c2[4]}
        bytes_raw = ext(c2[5], c4[5])

    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {"argument_bytes": getattr(ma, "argument_size_in_bytes", None),
               "output_bytes": getattr(ma, "output_size_in_bytes", None),
               "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
               "peak_bytes": getattr(ma, "peak_memory_in_bytes", None)}
    except Exception:
        pass

    pk = device_peaks(MODELED_DEVICE)
    roof = rl.Roofline(
        arch=arch_id, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_global=flops_g,
        bytes_global=api.model_bytes(cfg, cell),   # analytic traffic model
        coll_bytes=coll,
        coll_by_kind=by_kind, coll_counts=counts,
        model_flops=api.model_flops(cfg, cell),
        peak_flops=pk["bf16_flops"], hbm_bw=pk["hbm_bytes_s"],
        link_bw=pk["ici_link_bytes_s"],
        memory_per_device=mem)
    row = roof.row()
    row["hlo_bytes_raw"] = bytes_raw         # diagnostic: pre-fusion metric
    row["hlo_bytes_fusion_est"] = bytes_g    # diagnostic: HLO include-list
    row["status"] = "ok"
    row["compile_s"] = round(t_compile, 1)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in list_archs():
            for c in get_arch(a).shapes:
                cells.append((a, c.name))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    rows = []
    for arch_id, shape_name in cells:
        for mp in meshes:
            tag = f"{arch_id}/{shape_name}/{'2x16x16' if mp else '16x16'}"
            try:
                row = build_cell(arch_id, shape_name, mp)
                rows.append(row)
                if row["status"] == "ok":
                    mem = row.get("memory_per_device") or {}
                    print(f"OK   {tag}: bottleneck={row['bottleneck']} "
                          f"tC={row['t_compute_s']:.2e}s tM={row['t_memory_s']:.2e}s "
                          f"tX={row['t_collective_s']:.2e}s "
                          f"frac={row['roofline_fraction']:.3f} "
                          f"compile={row['compile_s']}s", flush=True)
                else:
                    print(f"SKIP {tag}: {row['reason']}", flush=True)
            except Exception as e:  # noqa: BLE001
                rows.append({"arch": arch_id, "shape": shape_name,
                             "mesh": "2x16x16" if mp else "16x16",
                             "status": "error", "error": str(e)[:2000]})
                print(f"FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print(f"wrote {args.out}")
    n_ok = sum(1 for r in rows if r["status"] == "ok")
    n_skip = sum(1 for r in rows if r["status"] == "skipped")
    n_err = len(rows) - n_ok - n_skip
    print(f"SUMMARY ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
