"""Faults planted under the timed path, for the tests that see
``correct`` come out false: each is one the cells can have."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _state_unchanged():
    from repro.core import engine
    return engine, "ingest_many", jax.jit(
        lambda state, stack, *, cfg: state, static_argnames=("cfg",))


def _half_batch():
    """Half of every tick's query events left out."""
    from repro.core import engine
    orig = engine.ingest_many

    def half(state, stack, *, cfg):
        B = stack.q_valid.shape[1]
        return orig(state, stack._replace(
            q_valid=stack.q_valid.at[:, B // 2:].set(False)), cfg=cfg)
    return engine, "ingest_many", jax.jit(half, static_argnames=("cfg",))


def _answer_altered():
    """Refresh: one destination of the exported table replaced. Replay:
    the heaviest query's stored weight off by 1%."""
    from repro.core import engine, ranking
    orig_export = ranking.suggestions_to_host
    orig_ingest = engine.ingest_many

    def export(table):
        out = orig_export(table)
        if out:
            src = min(out)
            d, s = out[src][0]
            out[src] = [(d ^ 1, s)] + out[src][1:]
        return out

    def ingest(state, stack, *, cfg):
        st = orig_ingest(state, stack, cfg=cfg)
        w = st.qstore.lanes["weight"]
        i = jnp.argmax(w)
        lanes = dict(st.qstore.lanes, weight=w.at[i].multiply(1.01))
        return st._replace(qstore=st.qstore._replace(lanes=lanes))
    return [(ranking, "suggestions_to_host", export),
            (engine, "ingest_many", jax.jit(ingest,
                                            static_argnames=("cfg",)))]


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


def plant(name: str, monkeypatch) -> None:
    patches = FAULTS[name]()
    for mod, attr, fn in (patches if isinstance(patches, list)
                          else [patches]):
        monkeypatch.setattr(mod, attr, fn)
