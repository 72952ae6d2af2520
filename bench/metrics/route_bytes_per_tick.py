"""Bytes the pair routes' ``all_to_all``s send off each chip per tick
replayed: the program's counter ``shard.route_bytes`` in the window, over
the ticks. Interconnect bytes: the peaks table has no interconnect entry,
so no share of a peak is taken. None where the program counts none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    ticks = run.counters.get("ticks", 0)
    rec = obs.window(*w[-1]) if w else {}
    if "shard.route_bytes" not in rec or not ticks:
        return None
    return rec["shard.route_bytes"][0] / ticks
