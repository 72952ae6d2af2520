"""The comparisons that decide ``correct``, and their limits.

Every number here is compared with a limit of its own, ``value <= limit``.
Each limit was set between two readings (``PERF.md``, "Limits"): the
largest a sound run of the program gave over a dozen seeds or more, and
the smallest the control gave (the reference with its store lanes and
scores held in bfloat16). A count of disagreements is an exact
comparison: its limit is 0.

A reference entry marked uncertain (``reference.EPS``) may be present or
absent on the program's side, and its score is not compared.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

LIMITS = {
    "refresh": {
        "table_bad_sources": 0,      # sources whose top-k disagree
        "table_score_gap": 3e-3,     # widest |program - reference| score
        "answer_bad": 0,             # frontend answers that disagree
        "answer_score_gap": 3e-3,
        "drops": 0,                  # entries any store dropped
    },
    "replay": {
        "key_diff": 0,               # entries held on one side only
        "weight_gap": 2e-3,          # widest relative weight difference
        "count_gap": 0,              # widest count difference
        "session_diff": 0,           # sessions whose window differs
        "drops": 0,
    },
}

# two scores within this of each other are a tie at the top-k cut
TIE = 1e-4


def unpack(arrays) -> Dict[int, List[Tuple[int, float]]]:
    """A persisted suggestion table (``src``, ``dst``, ``score``,
    ``offsets`` arrays) as {source fp: [(dst fp, score), ...]}."""
    src, dst, score, offs = (np.asarray(arrays[k]) for k in
                             ("src", "dst", "score", "offsets"))
    return {int(s): list(zip(dst[offs[i]:offs[i + 1]].tolist(),
                             score[offs[i]:offs[i + 1]].tolist()))
            for i, s in enumerate(src.tolist())}


def _source(served, ref, alpha: float, k: int) -> Tuple[bool, float]:
    """One source's served list against its reference candidates (sorted
    best first): (disagrees, widest score gap of a certain entry)."""
    bad, gap = len(served) > k, 0.0
    if ref is None:
        return bool(served), max((abs(x) for _, x in served), default=0.0)
    dsts, scores, unc, passing = ref
    cut = min((x for _, x in served), default=0.0) if len(served) >= k \
        else -np.inf
    tie = TIE * (1.0 + (abs(cut) if np.isfinite(cut) else 0.0))
    # only candidates that score above the cut can be missing; served
    # entries beyond that prefix are looked up in the whole list
    top = int(np.searchsorted(-scores * alpha, -(cut - tie), side="right"))
    top = max(top, min(k, scores.size))
    head = {int(d): (float(x) * alpha, bool(u), bool(p)) for d, x, u, p in
            zip(dsts[:top], scores[:top], unc[:top], passing[:top])}
    seen = set()
    for d, x in served:
        if d in seen:
            bad = True
        seen.add(d)
        r = head.get(d)
        if r is None and d >= 0:
            hit = np.nonzero(dsts == np.uint64(d))[0]
            if hit.size:
                i = int(hit[0])
                r = (float(scores[i]) * alpha, bool(unc[i]), bool(passing[i]))
        if r is None:
            bad = True
            gap = max(gap, abs(x))
        elif not r[1]:
            gap = max(gap, abs(x - r[0]))
    for d, (x, u, p) in head.items():
        if p and not u and d not in seen and x > cut + tie:
            bad = True
    return bad, gap


def table(served: Dict[int, list], cands, *, alpha: float, k: int) -> dict:
    """A whole served table against the reference: every source either
    side holds."""
    bad = skipped = compared = 0
    gap = 0.0
    for fp in set(served) | set(cands.sources()):
        if cands.uncertain_source(fp):
            skipped += 1
            continue
        b, g = _source(served.get(fp, []), cands.of(fp), alpha, k)
        bad += b
        gap = max(gap, g)
        compared += 1
    return {"bad": bad, "gap": gap, "compared": compared, "skipped": skipped}


def answers(served: Dict[int, list], cands, *, alpha: float, k: int) -> dict:
    """Answers to the given sources only (an empty answer is an answer)."""
    bad = 0
    gap = 0.0
    for fp, lst in served.items():
        if cands.uncertain_source(fp):
            continue
        b, g = _source(lst, cands.of(fp), alpha, k)
        bad += b
        gap = max(gap, g)
    return {"bad": bad, "gap": gap}


# ---------------------------------------------------------------------------
# replay: the stores themselves
# ---------------------------------------------------------------------------

def _match(prog_keys, ref_keys, ref_unc):
    """(program rows, reference rows) of the certain keys both hold, and
    the number of certain keys only one side holds."""
    order = np.argsort(ref_keys)
    rk = ref_keys[order]
    pos = np.clip(np.searchsorted(rk, prog_keys), 0, max(rk.size - 1, 0))
    hit = (rk.size > 0) & (rk[pos] == prog_keys) if rk.size else \
        np.zeros(prog_keys.shape, bool)
    ri = order[pos]
    certain = ~ref_unc[ri] if rk.size else np.zeros(prog_keys.shape, bool)
    extra = int(np.sum(~hit))
    both_p = np.nonzero(hit & certain)[0]
    both_r = ri[both_p]
    held = np.zeros(ref_keys.size, bool)
    held[ri[hit]] = True
    missing = int(np.sum(~held & ~ref_unc))
    return both_p, both_r, extra + missing


def stores(prog: dict, ref, prune_threshold: float) -> dict:
    """One engine's exported stores against the reference's."""
    q = ref.qstore()
    qi = np.searchsorted(ref.fps, q["fp"])
    p_q = np.searchsorted(ref.fps, prog["q_fp"])
    p_q_known = (p_q < ref.fps.size) & (
        ref.fps[np.minimum(p_q, ref.fps.size - 1)] == prog["q_fp"])
    unknown = int(np.sum(~p_q_known))
    bp, br, kd = _match(p_q[p_q_known], qi, q["uncertain"])
    wp, wr = prog["q_w"][p_q_known][bp], q["weight"][br]
    cp, cr = prog["q_c"][p_q_known][bp], q["count"][br]
    wgap = [np.abs(wp - wr) / np.maximum(wr, prune_threshold)]
    cgap = [np.abs(cp - cr)]
    key_diff = unknown + kd

    c = ref.cooc_store()
    NQ = np.uint64(ref.fps.size)
    ids = lambda f: np.searchsorted(ref.fps, f).astype(np.uint64)
    ck = ids(c["src"]) * NQ + ids(c["dst"])
    ps, pd = prog["c_src"], prog["c_dst"]
    known = np.isin(ps, ref.fps) & np.isin(pd, ref.fps)
    key_diff += int(np.sum(~known))
    pk = ids(ps[known]) * NQ + ids(pd[known])
    bp, br, kd = _match(pk, ck, c["uncertain"])
    key_diff += kd
    wp, wr = prog["c_w"][known][bp], c["weight"][br]
    wgap.append(np.abs(wp - wr) / np.maximum(wr, prune_threshold))
    cgap.append(np.abs(prog["c_c"][known][bp] - c["count"][br]))

    s = ref.sessions
    rwin = np.where(s["window"] >= 0,
                    ref.fps[np.maximum(s["window"], 0)], np.uint64(0))
    order = np.argsort(s["sess_fp"])
    rs = s["sess_fp"][order]
    pos = np.clip(np.searchsorted(rs, prog["s_fp"]), 0, max(rs.size - 1, 0))
    hit = rs[pos] == prog["s_fp"] if rs.size else np.zeros(0, bool)
    ri = order[pos[hit]]
    same = (prog["s_filled"][hit] == s["filled"][ri]) & np.all(
        prog["s_window"][hit] == rwin[ri], axis=1)
    session_diff = int(np.sum(~hit)) + int(np.sum(~same)) \
        + (rs.size - int(hit.sum()))
    return {"key_diff": key_diff,
            "weight_gap": float(max((x.max() for x in wgap if x.size),
                                    default=0.0)),
            "count_gap": float(max((x.max() for x in cgap if x.size),
                                   default=0.0)),
            "session_diff": session_diff}
