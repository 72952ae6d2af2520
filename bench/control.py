"""The control: the reference, held in bfloat16, in the program's place.

    python3 bench/run.py --workload svc_hash_c25.replay --seed 7 \
        --seconds 45 --trace 0 2> sound.err
    python3 bench/control.py --workload svc_hash_c25.replay --seed 7 \
        --sound-log sound.err

The configuration states float32 lanes and scores; the control is the
plain reference with every stored lane and every score rounded to the
next precision below, bfloat16. It is judged by the cell's own
comparisons against the float64 reference, at the cell's own size, on
the ticks that a sound run of the same cell and seed fed the program
(its ``counter ticks_fed`` line on standard error), and it has to come
out not correct. Its numbers are the upper readings the limits in
``compare.LIMITS`` were set below. No accelerator is used.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ticks_fed(sound_log: str) -> int:
    """The ticks a sound run fed the program, from its standard error."""
    with open(sound_log) as f:
        found = re.findall(r"^counter ticks_fed = (\d+)$", f.read(), re.M)
    if not found:
        raise ValueError(f"{sound_log} holds no 'counter ticks_fed' line")
    return int(found[-1])


def control_checks(cell, seed: int, n_ticks: int) -> list:
    import ml_dtypes
    import numpy as np

    from bench import feed, harness

    tr = cell.traffic
    mode = harness.load_module("modes", tr["mode"])
    hose = feed.make_hose(cell.config, seed)
    ticks = [hose.tick(t) for t in range(n_ticks)]
    bf16 = ml_dtypes.bfloat16
    run = types.SimpleNamespace(seed=seed, counters={})
    if tr["mode"] == "refresh":
        k = cell.config["engine"]["rank"]["top_k"]
        alpha = 0.7                       # the frontend's blend, rt alone
        cands = harness.make_reference(cell.config, "rt", hose).run(
            ticks).candidates()
        ctrl = harness.make_reference(cell.config, "rt", hose, bf16).run(
            ticks).candidates().table(alpha=1.0)
        text = dict(zip(hose.fps.tolist(), hose.vocab))
        fp = {q: int(f) for q, f in zip(hose.vocab, hose.fps)}

        def answer(q):
            return [(text[d], s * alpha) for d, s in ctrl.get(fp[q], [])]

        requested = {q: answer(q)
                     for q in hose.vocab[:tr["requests_per_cycle"]]}
        return mode.served_checks(run, hose, cands, ctrl, answer,
                                    requested, k, alpha, 0)
    exported = {}
    for name in harness.semantics(cell.config):
        ref = harness.make_reference(cell.config, name, hose, bf16).run(ticks)
        q, c, s = ref.qstore(), ref.cooc_store(), ref.sessions
        win = np.where(s["window"] >= 0, ref.fps[np.maximum(s["window"], 0)],
                       np.uint64(0))
        exported[name] = {
            "drops": 0, "q_fp": q["fp"], "q_w": q["weight"],
            "q_c": q["count"], "c_src": c["src"], "c_dst": c["dst"],
            "c_w": c["weight"], "c_c": c["count"], "s_fp": s["sess_fp"],
            "s_filled": s["filled"], "s_window": win}
    return mode.store_checks(cell.config, hose, ticks, exported)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sound-log", required=True,
                    help="standard error of a sound run of this cell and seed")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.resolve(harness.load_benchmark(ROOT), args.workload)
    n_ticks = ticks_fed(args.sound_log)
    checks = control_checks(cell, args.seed, n_ticks)
    for name, v, lim in checks:
        print(f"control {name} = {v!r} (limit {lim!r})", file=sys.stderr)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "ticks": n_ticks,
                      "correct": all(v <= lim for _, v, lim in checks),
                      "checks": {n: v for n, v, _ in checks}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
