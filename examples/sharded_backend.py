"""Beyond-paper example: the SHARDED search-assistance backend over every
device the process sees (a power of two of them) — key-sharded stores,
all_to_all pair routing, pair salting, and shard-merged suggestions
(removes the paper's §4.4 memory wall).

  PYTHONPATH=src python examples/sharded_backend.py
On the CPU it asks for 8 virtual devices itself (run as a fresh process);
on a TPU host it shards over the chips.
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np          # noqa: E402
import jax                   # noqa: E402
import jax.numpy as jnp      # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import sharded_engine as se          # noqa: E402
from repro.core.engine import EngineConfig           # noqa: E402
from repro.core.hashing import split_fp              # noqa: E402
from repro.data.stream import StreamConfig, SyntheticStream  # noqa: E402


def main() -> None:
    devs = jax.devices()
    n = 1 << (len(devs).bit_length() - 1)      # largest power of two
    mesh = Mesh(np.array(devs[:n]), ("shard",))
    ecfg = EngineConfig(query_capacity=1 << 13, cooc_capacity=1 << 16,
                        session_capacity=1 << 13, decay_every=4, rank_every=0)
    scfg = se.ShardedConfig(base=ecfg, n_salts=2, route_capacity=2048)
    step = se.make_sharded_step(scfg, mesh)
    decay = se.make_sharded_decay(scfg, mesh)
    rank = se.make_sharded_rank(scfg, mesh)
    state = se.init_sharded_state(scfg, mesh)

    stream = SyntheticStream(StreamConfig(vocab_size=1024,
                                          queries_per_tick=1024), seed=0)
    for t in range(13):
        ev, _ = stream.gen_tick(t)
        s_hi, s_lo = split_fp(ev.sess_fp)
        q_hi, q_lo = split_fp(ev.q_fp)
        state = step(state, jnp.asarray(s_hi), jnp.asarray(s_lo),
                     jnp.asarray(q_hi), jnp.asarray(q_lo),
                     jnp.asarray(ev.src, jnp.int32), jnp.asarray(ev.valid))
        if t > 0 and t % ecfg.decay_every == 0:
            state = decay(state, jnp.int32(ecfg.decay_every))
        state = state._replace(tick=state.tick + 1)

    per_shard = np.asarray(state.cooc.live_mask).reshape(n, -1).sum(axis=1)
    print("per-shard cooccurrence entries:", per_shard.tolist())
    print("route-buffer drops:", np.asarray(state.n_route_drop).tolist())
    sugg = se.merge_sharded_suggestions(rank(state), ecfg.rank.top_k)
    print(f"{len(sugg)} queries with suggestions after shard merge")
    head = stream.tok.query_fp(stream.vocab[0])
    print(f"related({stream.vocab[0]!r}) =",
          [(stream.tok.text(d), round(s, 3)) for d, s in sugg.get(head, [])[:5]])


if __name__ == "__main__":
    main()
