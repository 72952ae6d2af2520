"""The sharded replay program's share of its roofline, in percent.

Least time of one tick on one chip: the bytes it must move at least once
over the chip's peak HBM bandwidth. Every query event reads and writes its
slot of the query store, which every chip holds whole; every gram of a
tweet reads its slot there. A chip reads and writes the session row of
each event it owns (a 1/n share of them) and the cooccurrence slot of
each pair routed to it (the pairs received, a 1/n share of those routed),
and writes and reads each such pair's routed lanes once (the four key
halves and the valid flag). The slot and row bytes are those of the
stores as the program holds them (counted at set-up), so narrower lanes
lower the least time with them. The operations are a few per byte and
never bound it. The share is that least time over the device seconds per
tick that ``ingest_ms_per_tick`` reads: the replay program's, which on
this cell is ``sharded_ingest_many``, per chip.
"""
from bench import peaks

# a routed pair: four u32 key halves and a one-byte valid flag
ROUTED_PAIR_BYTES = 4 * 4 + 1


def least_bytes(query_events: float, tweet_grams: float, pairs: float,
                n_shards: int, qstore_slot: int, cooc_slot: int,
                session_row: int) -> float:
    """Bytes one chip must move for ticks holding ``query_events`` events,
    ``tweet_grams`` grams of tweets and ``pairs`` routed pairs (all
    shards)."""
    return (2 * qstore_slot * query_events + qstore_slot * tweet_grams
            + 2 * session_row * query_events / n_shards
            + 2 * (cooc_slot + ROUTED_PAIR_BYTES) * pairs / n_shards)


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    s, c = run.summary, run.counters
    t = s.program_s("ingest_many") if s is not None else None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    rec = obs.window(*w[-1]) if w else {}
    if not t or "shard.route_pairs" not in rec or "cooc_slot_bytes" not in c:
        return None
    b = least_bytes(c["query_events"], c["tweet_grams"],
                    rec["shard.route_pairs"][0], c["n_shards"],
                    c["qstore_slot_bytes"], c["cooc_slot_bytes"],
                    c["session_row_bytes"])
    return 100.0 * b / peaks.device_peaks(run.device_kind)["hbm_bytes_s"] / t
