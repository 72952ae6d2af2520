"""The plain reference: the engine's semantics, written out in numpy.

It imports nothing of the program and takes nothing the program made:
only the ticks of the hose and the numbers of the configuration file.
Every store is a dense float64 array over ids (queries by fingerprint,
cooccurrences by (source, destination) pair), updated tick by tick in the
order the configuration states:

  1. query events: the query store adds the event's source weight and a
     count of one; each event pairs with the previous ``session_window``
     queries of its session (earliest tick and batch position first),
     except a predecessor equal to it, with the geometric mean of the two
     source weights;
  2. tweets: an n-gram is query-like when the query store, after this
     tick's queries, holds it with a count of at least
     ``min_querylike_count``; query-like n-grams add ``tweet_weight`` to
     the query store, and every ordered pair of distinct query-like
     n-grams of one tweet adds ``tweet_weight`` to the cooccurrence store;
  3. lazy exponential decay: a write first decays the stored weight to
     the current tick, reads decay to the reading tick; every
     ``prune_every`` ticks an entry whose decayed weight is under
     ``prune_threshold`` is removed with its count;
  4. ranking: for every stored pair that passes the evidence gates, a
     linear combination of conditional probability, the sigmoid of PMI,
     log1p of Dunning's G2 and log1p of chi2, then the best ``top_k`` per
     source.

A decision that floating-point rounding could flip (a decayed weight
within ``EPS`` of the prune threshold or of an evidence gate) is marked
*uncertain*, and the comparisons treat the entries it touches as either
way. ``lane_dtype`` rounds every stored lane and every score to a lower
precision: that is the control, which the comparisons must refuse.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

# relative width of the band around a threshold inside which float32
# rounding in the program may decide differently than float64 here
EPS = 1e-4


@dataclasses.dataclass(frozen=True)
class Semantics:
    """The numbers of one engine, as the configuration file states them."""
    session_window: int
    source_weights: tuple
    tweet_weight: float
    min_querylike_count: float
    half_life_ticks: float
    prune_threshold: float
    prune_every: int
    session_ttl: int
    top_k: int
    coefs: tuple               # condprob, pmi, llr, chi2
    min_pair_weight: float
    min_src_weight: float
    min_pair_count: float

    @classmethod
    def from_config(cls, engine: dict, half_life_mult: float = 1.0,
                    threshold_mult: float = 1.0) -> "Semantics":
        r = engine["rank"]
        return cls(
            session_window=engine["session_window"],
            source_weights=tuple(engine["source_weights"]),
            tweet_weight=engine["tweet_weight"],
            min_querylike_count=engine["min_querylike_count"],
            half_life_ticks=engine["half_life_ticks"] * half_life_mult,
            prune_threshold=engine["prune_threshold"] * threshold_mult,
            prune_every=engine["prune_every"],
            session_ttl=engine["session_ttl"],
            top_k=r["top_k"], coefs=tuple(r["coefs"]),
            min_pair_weight=r["min_pair_weight"],
            min_src_weight=r["min_src_weight"],
            min_pair_count=r["min_pair_count"])


def _round(x: np.ndarray, dtype) -> np.ndarray:
    if dtype is None:
        return x
    return np.asarray(x).astype(dtype).astype(np.float64)


class _Store:
    """weight (anchored at ``last``), count, and the uncertain flag, over
    ids ``0..n``; an entry is live while its count is above zero."""

    def __init__(self, n: int, sem: Semantics, dtype):
        self.w = np.zeros(n)
        self.c = np.zeros(n)
        self.last = np.zeros(n, np.int64)
        self.unc = np.zeros(n, bool)
        self.sem, self.dtype = sem, dtype

    def factor(self, dt):
        return np.exp2(-np.asarray(dt, np.float64) / self.sem.half_life_ticks)

    def add(self, ids: np.ndarray, w: np.ndarray, t: int) -> None:
        """Aggregate duplicate ids, decay to ``t``, add."""
        if ids.size == 0:
            return
        u, inv = np.unique(ids, return_inverse=True)
        sw = np.bincount(inv, weights=w, minlength=u.size)
        cnt = np.bincount(inv, minlength=u.size).astype(np.float64)
        self.w[u] = _round(self.w[u] * self.factor(t - self.last[u]) + sw,
                           self.dtype)
        self.c[u] = _round(self.c[u] + cnt, self.dtype)
        self.last[u] = t

    def prune(self, t: int) -> None:
        thr = self.sem.prune_threshold
        live = self.c > 0
        dec = _round(self.w * self.factor(t - self.last), self.dtype)
        self.unc |= live & (np.abs(dec - thr) <= EPS * thr)
        keep = live & (dec >= thr)
        self.w = np.where(keep, dec, 0.0)
        self.c = np.where(keep, self.c, 0.0)
        self.last = np.where(keep, t, 0)

    def decayed(self, now: int) -> np.ndarray:
        return _round(self.w * self.factor(now - self.last), self.dtype)


class Reference:
    """The engine's state after ``ticks``, computed plainly.

    ``universe`` holds every query fingerprint the hose can emit; ids are
    positions in its sorted unique copy."""

    def __init__(self, sem: Semantics, universe: np.ndarray,
                 lane_dtype=None):
        self.sem = sem
        self.fps = np.unique(np.asarray(universe, np.uint64))
        self.dtype = lane_dtype

    def _ids(self, fp: np.ndarray) -> np.ndarray:
        i = np.searchsorted(self.fps, fp)
        ok = (i < self.fps.size) & (self.fps[np.minimum(i, self.fps.size - 1)]
                                    == fp)
        if not ok.all():
            raise ValueError("a fingerprint outside the hose's universe")
        return i.astype(np.int64)

    # -- ingest ---------------------------------------------------------
    def run(self, ticks: Sequence) -> "Reference":
        sem = self.sem
        n_ticks = len(ticks)
        if n_ticks > sem.session_ttl:
            raise ValueError("sessions would expire: the reference keeps "
                             "every session for the whole run")
        NQ = self.fps.size
        sw = np.asarray(sem.source_weights, np.float64)

        # session pairs, all ticks at once: a stable sort by session keeps
        # each session's events in (tick, batch position) order
        sess = np.concatenate([tk.sess_fp[tk.valid] for tk in ticks])
        q = np.concatenate([self._ids(tk.q_fp[tk.valid]) for tk in ticks])
        src = np.concatenate([tk.src[tk.valid] for tk in ticks])
        tick = np.concatenate([np.full(int(tk.valid.sum()), t, np.int64)
                               for t, tk in enumerate(ticks)])
        keep = sess != 0
        sess, q, src, tick = sess[keep], q[keep], src[keep], tick[keep]
        order = np.argsort(sess, kind="stable")
        sess, q, src, tick = sess[order], q[order], src[order], tick[order]
        ev_w = sw[np.clip(src, 0, sw.size - 1)]
        p_src, p_dst, p_w, p_t = [], [], [], []
        for d in range(1, sem.session_window + 1):
            j = np.arange(d, sess.size)
            i = j - d
            ok = (sess[i] == sess[j]) & (q[i] != q[j])
            p_src.append(q[i][ok])
            p_dst.append(q[j][ok])
            p_w.append(np.sqrt(ev_w[i][ok] * ev_w[j][ok]))
            p_t.append(tick[j][ok])
        # the last session_window events of each session, oldest first
        last = np.r_[sess[1:] != sess[:-1], True]
        ends = np.nonzero(last)[0]
        starts = np.r_[0, ends[:-1] + 1]
        W = sem.session_window
        filled = np.minimum(ends - starts + 1, W)
        win = np.full((ends.size, W), -1, np.int64)
        for a in range(W):
            pos = ends - (W - 1 - a)
            ok = pos >= starts
            win[ok, a] = q[pos[ok]]
        self.sessions = {"sess_fp": sess[ends], "filled": filled,
                         "window": win}
        del sess, q, src, tick, ev_w, order

        # the query store, tick by tick; tweets pair their query-like grams
        qs = _Store(NQ, sem, self.dtype)
        q_of = [self._ids(tk.q_fp[tk.valid]) for tk in ticks]
        for t, tk in enumerate(ticks):
            qs.add(q_of[t], sw[np.clip(tk.src[tk.valid], 0, sw.size - 1)], t)
            g = tk.grams[tk.t_valid]
            nz = g != 0
            gid = np.full(g.shape, -1, np.int64)
            gid[nz] = self._ids(g[nz])
            ql = nz.copy()
            ql[nz] = (qs.c[gid[nz]] > 0) \
                & (qs.c[gid[nz]] >= sem.min_querylike_count)
            qs.add(gid[ql], np.full(int(ql.sum()), sem.tweet_weight), t)
            G = g.shape[1]
            a = np.repeat(np.arange(G), G)
            b = np.tile(np.arange(G), G)
            pair_ok = ql[:, a] & ql[:, b] & (gid[:, a] != gid[:, b])
            p_src.append(gid[:, a][pair_ok])
            p_dst.append(gid[:, b][pair_ok])
            p_w.append(np.full(int(pair_ok.sum()), sem.tweet_weight))
            p_t.append(np.full(int(pair_ok.sum()), t, np.int64))
            if sem.prune_every > 0 and t > 0 and t % sem.prune_every == 0:
                qs.prune(t)
        self.q = qs

        # the cooccurrence store over every pair that ever occurs
        p_src, p_dst = np.concatenate(p_src), np.concatenate(p_dst)
        p_w, p_t = np.concatenate(p_w), np.concatenate(p_t)
        keys, inv = np.unique(p_src * NQ + p_dst, return_inverse=True)
        self.pair_src, self.pair_dst = keys // NQ, keys % NQ
        by_t = np.argsort(p_t, kind="stable")
        bounds = np.searchsorted(p_t[by_t], np.arange(n_ticks + 1))
        cs = _Store(keys.size, sem, self.dtype)
        for t in range(n_ticks):
            sl = by_t[bounds[t]:bounds[t + 1]]
            cs.add(inv[sl], p_w[sl], t)
            if sem.prune_every > 0 and t > 0 and t % sem.prune_every == 0:
                cs.prune(t)
        self.cooc = cs
        self.now = n_ticks
        return self

    # -- reads ----------------------------------------------------------
    def qstore(self) -> Dict[str, np.ndarray]:
        live = self.q.c > 0
        return {"fp": self.fps[live], "weight": self.q.decayed(self.now)[live],
                "count": self.q.c[live], "uncertain": self.q.unc[live]}

    def cooc_store(self) -> Dict[str, np.ndarray]:
        live = self.cooc.c > 0
        unc = self.cooc.unc | self.q.unc[self.pair_src] \
            | self.q.unc[self.pair_dst]
        return {"src": self.fps[self.pair_src[live]],
                "dst": self.fps[self.pair_dst[live]],
                "weight": self.cooc.decayed(self.now)[live],
                "count": self.cooc.c[live], "uncertain": unc[live]}

    def candidates(self) -> "Candidates":
        """Every pair that passes the evidence gates, or sits within EPS of
        one, scored as of ``now`` and sorted by (source, -score, dst)."""
        sem, now = self.sem, self.now
        q_live = self.q.c > 0
        q_w = self.q.decayed(now)
        q_c = self.q.c
        total_w = float(np.sum(q_w[q_live]))
        total_c = float(np.sum(q_c[q_live]))
        live = self.cooc.c > 0
        a, b = self.pair_src[live], self.pair_dst[live]
        w_ab = self.cooc.decayed(now)[live]
        c_ab = self.cooc.c[live]
        w_a, c_a, w_b, c_b = q_w[a], q_c[a], q_w[b], q_c[b]
        found = q_live[a] & q_live[b]

        def near(x, thr):
            return np.abs(x - thr) <= EPS * thr

        passing = found & (w_ab >= sem.min_pair_weight) \
            & (c_ab >= sem.min_pair_count) & (w_a >= sem.min_src_weight)
        marginal = found & (near(w_ab, sem.min_pair_weight)
                            | near(w_a, sem.min_src_weight)) \
            & (c_ab >= sem.min_pair_count)
        cand = passing | marginal
        a, b = a[cand], b[cand]
        score = _round(_score(sem, w_ab[cand], c_ab[cand], w_a[cand],
                              w_b[cand], c_a[cand], c_b[cand], total_w,
                              total_c), self.dtype)
        unc = (marginal[cand] | self.cooc.unc[live][cand]
               | self.q.unc[a] | self.q.unc[b])
        order = np.lexsort((b, -score, a))
        return Candidates(self.fps, a[order], b[order], score[order],
                          unc[order], passing[cand][order], sem.top_k,
                          self.q.unc)


def _xlogx(x):
    return np.where(x > 0, x * np.log(np.where(x > 0, x, 1.0)), 0.0)


def _score(sem: Semantics, w_ab, c_ab, w_a, w_b, c_a, c_b, total_w, total_c):
    eps = 1e-9
    condprob = np.where(w_a > 0, w_ab / np.maximum(w_a, eps), 0.0)
    pmi = np.where((w_ab > 0) & (w_a > 0) & (w_b > 0),
                   np.log(np.maximum(w_ab * max(total_w, eps), eps)
                          / np.maximum(w_a * w_b, eps)), 0.0)
    k11 = c_ab
    k12 = np.maximum(c_a - c_ab, 0.0)
    k21 = np.maximum(c_b - c_ab, 0.0)
    k22 = np.maximum(total_c - c_a - c_b + c_ab, 0.0)
    n = np.maximum(k11 + k12 + k21 + k22, eps)
    r1, r2, c1, c2 = k11 + k12, k21 + k22, k11 + k21, k12 + k22
    llr = 2.0 * (_xlogx(k11) + _xlogx(k12) + _xlogx(k21) + _xlogx(k22)
                 - _xlogx(r1) - _xlogx(r2) - _xlogx(c1) - _xlogx(c2)
                 + _xlogx(n))
    llr = np.maximum(llr, 0.0)
    chi2 = n * (k11 * k22 - k12 * k21) ** 2 / np.maximum(r1 * r2 * c1 * c2,
                                                         eps)
    c0, c1_, c2_, c3 = sem.coefs
    return (c0 * condprob + c1_ / (1.0 + np.exp(-pmi))
            + c2_ * np.log1p(llr) + c3 * np.log1p(chi2))


class Candidates:
    """Scored candidates of every source, grouped by source."""

    def __init__(self, fps, src, dst, score, unc, passing, top_k, q_unc):
        self.fps, self.top_k = fps, top_k
        self.src, self.dst, self.score = src, dst, score
        self.unc, self.passing = unc, passing
        self.q_unc = q_unc
        starts = np.r_[0, np.nonzero(src[1:] != src[:-1])[0] + 1]
        self._start = {int(s): int(i) for s, i in zip(src[starts], starts)}
        self._end = dict(zip(self._start, np.r_[starts[1:], src.size]
                             .tolist()))

    def sources(self) -> List[int]:
        """Fingerprints of the sources that have a certain, passing
        candidate: the served table must hold each of them."""
        ok = self.passing & ~self.unc & ~self.q_unc[self.src]
        return [int(f) for f in self.fps[np.unique(self.src[ok])]]

    def uncertain_source(self, fp: int) -> bool:
        i = np.searchsorted(self.fps, np.uint64(fp))
        return bool(i < self.fps.size and self.fps[i] == fp
                    and self.q_unc[i])

    def of(self, fp: int) -> Optional[tuple]:
        """(dst fingerprints, scores, uncertain, passing) of one source,
        best first; None when it has no candidate."""
        i = np.searchsorted(self.fps, np.uint64(fp))
        if i >= self.fps.size or self.fps[i] != fp:
            return None
        lo = self._start.get(int(i))
        if lo is None:
            return None
        hi = self._end[int(i)]
        return (self.fps[self.dst[lo:hi]], self.score[lo:hi],
                self.unc[lo:hi], self.passing[lo:hi])

    def table(self, alpha: float = 1.0) -> Dict[int, list]:
        """The strict top-k table (passing candidates only), scores times
        ``alpha``: what a program that agrees with the reference serves."""
        out: Dict[int, list] = {}
        ok = self.passing
        src, dst, sc = self.src[ok], self.dst[ok], self.score[ok]
        starts = np.r_[0, np.nonzero(src[1:] != src[:-1])[0] + 1]
        ends = np.r_[starts[1:], src.size]
        for lo, hi in zip(starts.tolist(), ends.tolist()):
            hi = min(hi, lo + self.top_k)
            out[int(self.fps[src[lo]])] = [
                (int(d), float(s) * alpha)
                for d, s in zip(self.fps[dst[lo:hi]], sc[lo:hi])]
        return out
