"""Cooccurrence pairs routed to their owner shard per tick replayed, all
shards together: the program's counter ``shard.route_pairs`` in the
window, over the ticks. None where the program counts none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    ticks = run.counters.get("ticks", 0)
    rec = obs.window(*w[-1]) if w else {}
    if "shard.route_pairs" not in rec or not ticks:
        return None
    return rec["shard.route_pairs"][0] / ticks
