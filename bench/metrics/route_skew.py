"""How unevenly the pair routes load the shards: the pairs the busiest
shard received over those the mean shard received, summed over the
window's replay calls (the program's counters ``shard.pairs_max`` and
``shard.pairs_mean``); 1.0 is even. None where the program counts none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    rec = obs.window(*w[-1]) if w else {}
    if "shard.pairs_max" not in rec or not rec.get("shard.pairs_mean",
                                                   (0,))[0]:
        return None
    return rec["shard.pairs_max"][0] / rec["shard.pairs_mean"][0]
