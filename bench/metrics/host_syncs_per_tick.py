"""Blocking device-to-host reads of the engines' host loop per tick
replayed: the program's counter ``engine.syncs`` in the window, over the
ticks. None where the program counts none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    ticks = run.counters.get("ticks", 0)
    rec = obs.window(*w[-1]) if w else {}
    if "engine.syncs" not in rec or not ticks:
        return None
    return rec["engine.syncs"][0] / ticks
