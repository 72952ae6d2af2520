"""Traffic in the program's own formats: ticks as its types, stacks on the
device, the durable log."""
from __future__ import annotations

from typing import Sequence

import numpy as np

from bench.hose import Hose, HoseParams


def make_hose(config: dict, seed: int) -> Hose:
    """The hose a configuration states, drawn from ``seed``."""
    from repro.data.tokenizer import NGramTokenizer
    return Hose(HoseParams.from_json(config["hose"]), NGramTokenizer(), seed)


def program_tick(tk):
    """A hose tick as the program's (QueryEvents, TweetBatch)."""
    from repro.data.stream import QueryEvents, TweetBatch
    return (QueryEvents(tk.sess_fp, tk.q_fp, tk.src, tk.valid),
            TweetBatch(tk.grams, tk.t_valid))


def stack(ticks: Sequence, t0: int):
    """Ticks ``t0, t0 + 1, ...`` as one device TickStack, the way the
    catch-up path stacks a logged chunk."""
    from repro.streaming import LogChunk, chunk_to_stack
    col = lambda f: np.stack([f(tk) for tk in ticks])
    return chunk_to_stack(LogChunk(
        ticks=np.arange(t0, t0 + len(ticks)), sess_fp=col(lambda k: k.sess_fp),
        q_fp=col(lambda k: k.q_fp), src=col(lambda k: k.src),
        q_valid=col(lambda k: k.valid), grams=col(lambda k: k.grams),
        t_valid=col(lambda k: k.t_valid)))


def n_events(tk) -> int:
    """Hose events of one tick: valid query events plus valid tweets."""
    return int(tk.valid.sum()) + int(tk.t_valid.sum())
