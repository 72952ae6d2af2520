"""The benchmark's traffic generator: the query hose and the firehose.

A copy of the draws of the program's synthetic stream (Zipf popularity
over a two-word vocabulary, topic-sticky user sessions, typos on head
queries, topical tweets as bags of n-grams), kept here so that the
yardstick cannot move when the program's generator changes. It draws
exactly the same numbers, in the same order, from the same seed; the
digest in ``testdata/hose_digest.json`` pins that. Fingerprints go through
the program's tokenizer, because the read path hashes query strings with
it and maps fingerprints back to text through it.

Every parameter comes from a configuration file
(``bench/configs/<name>.json``, key ``hose``); nothing here knows a cell.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import List, NamedTuple, Tuple

import numpy as np

_WORDS = [
    "news", "video", "live", "score", "game", "music", "photo", "trend",
    "world", "tech", "movie", "series", "stream", "update", "launch", "team",
    "play", "final", "award", "storm", "market", "stock", "crypto", "earth",
    "space", "rocket", "phone", "app", "meme", "viral", "dance", "song",
]


@dataclasses.dataclass(frozen=True)
class HoseParams:
    vocab_size: int
    zipf_s: float
    n_topics: int
    n_users: int
    session_ticks: int
    topic_stickiness: float
    typo_rate: float
    n_misspell_targets: int
    queries_per_tick: int
    tweets_per_tick: int
    tweet_words: int
    tweet_grams: int
    source_probs: Tuple[float, float, float]

    @classmethod
    def from_json(cls, d: dict) -> "HoseParams":
        d = dict(d)
        d["source_probs"] = tuple(d["source_probs"])
        return cls(**d)


class Tick(NamedTuple):
    """One tick of both hoses: query events and tweets."""
    sess_fp: np.ndarray   # u64[B]
    q_fp: np.ndarray      # u64[B]
    src: np.ndarray       # i32[B]: 0 typed, 1 hashtag click, 2 related click
    valid: np.ndarray     # bool[B]
    grams: np.ndarray     # u64[T, G], 0 padded
    t_valid: np.ndarray   # bool[T]


def _cdf(p: np.ndarray) -> np.ndarray:
    cdf = p.cumsum()
    cdf /= cdf[-1]
    return cdf


def _draw(rng: np.random.Generator, cdf: np.ndarray, size) -> np.ndarray:
    # the same draws as rng.choice(len(cdf), size, p=p)
    return cdf.searchsorted(rng.random(size), side="right")


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer, output != 0."""
    x = np.asarray(x, np.uint64).copy()
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return np.where(x == 0, np.uint64(1), x)


def _corrupt(q: str, rr) -> str:
    pos = int(rr.integers(1, max(2, len(q) - 1)))
    kind = rr.integers(3)
    if kind == 0 and pos + 1 < len(q):
        return q[:pos] + q[pos + 1] + q[pos] + q[pos + 2:]
    if kind == 1:
        return q[:pos] + q[pos + 1:]
    return q[:pos] + "x" + q[pos + 1:]


class Hose:
    """Ticks of traffic drawn from ``seed``. ``vocab`` is in popularity
    order (Zipf rank 1 first), followed by the misspelt variants."""

    def __init__(self, p: HoseParams, tok, seed: int):
        self.p = p
        self.tok = tok
        self.rng = np.random.default_rng(seed)
        rr = np.random.default_rng(seed + 1)
        vocab: List[str] = []
        seen = set()
        while len(vocab) < p.vocab_size:
            w1 = _WORDS[rr.integers(len(_WORDS))]
            w2 = f"{_WORDS[rr.integers(len(_WORDS))]}{rr.integers(1000)}"
            q = f"{w1} {w2}" if rr.random() < 0.8 else w2
            if q not in seen:
                seen.add(q)
                vocab.append(q)
        fps = [tok.query_fp(q) for q in vocab]
        ranks = np.arange(1, p.vocab_size + 1, dtype=np.float64)
        w = ranks ** (-p.zipf_s)
        base_p = w / w.sum()
        self._base_cdf = _cdf(base_p)
        self.topic = rr.integers(0, p.n_topics, size=p.vocab_size)
        self._topic_cdf = []
        for t in range(p.n_topics):
            m = (self.topic == t).astype(np.float64) * base_p
            s = m.sum()
            self._topic_cdf.append(_cdf(m / s if s > 0 else base_p))
        self._variants: List[int] = []
        for i in range(min(p.n_misspell_targets, len(vocab))):
            q = vocab[i]
            if len(q) < 5:
                continue
            v = _corrupt(q, rr)
            if v == q:
                continue
            vocab.append(v)
            fps.append(tok.query_fp(v))
            self._variants.append(len(vocab) - 1)
        self.vocab = vocab
        self.fps = np.array(fps, np.uint64)

    def tick(self, t: int) -> Tick:
        """The traffic of tick ``t``. Ticks must be drawn in order: each
        draw advances the stream's generator."""
        p, rng = self.p, self.rng
        B = p.queries_per_tick
        rng.random(B)          # the event-share draw (no news events here)
        users = rng.integers(0, p.n_users, size=B)
        epoch = t // p.session_ticks
        with np.errstate(over="ignore"):
            sess_fp = _mix64(
                users.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
                ^ np.uint64((epoch * 0xC2B2AE3D27D4EB4F) % (1 << 64)))
        bt = (users + epoch * 7919) % p.n_topics
        sticky = rng.random(B) < p.topic_stickiness
        q_idx = np.empty(B, np.int64)
        for tpc in np.unique(bt[sticky]):
            m = sticky & (bt == tpc)
            q_idx[m] = _draw(rng, self._topic_cdf[tpc], int(m.sum()))
        if (~sticky).any():
            q_idx[~sticky] = _draw(rng, self._base_cdf, int((~sticky).sum()))
        if self._variants:
            ty = rng.random(B) < p.typo_rate
            if ty.any():
                q_idx[ty] = rng.choice(self._variants, size=int(ty.sum()))
        src = rng.choice(3, size=B, p=p.source_probs).astype(np.int32)

        T, W = p.tweets_per_tick, p.tweet_words
        rng.random(T)          # the tweets' event-share draw
        topics = rng.integers(0, p.n_topics, size=T)
        tw_idx = np.empty((T, W), np.int64)
        for i, tpc in enumerate(topics):
            tw_idx[i] = _draw(rng, self._topic_cdf[tpc], W)
        grams = np.zeros((T, p.tweet_grams), np.uint64)
        g = min(W, p.tweet_grams)
        grams[:, :g] = self.fps[tw_idx[:, :g]]
        return Tick(sess_fp, self.fps[q_idx], src, np.ones(B, bool),
                    grams, np.ones(T, bool))


def digest(ticks) -> str:
    """sha256 over every array of every tick, in order."""
    h = hashlib.sha256()
    for tk in ticks:
        for a in tk:
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()
