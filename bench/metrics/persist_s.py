"""Host seconds per refresh cycle of persisting the table:
``pack_suggestions`` and ``CheckpointManager.save``, the program's spans
``persist.pack`` and ``persist.save`` in the window. None where the
program records no spans."""

SPANS = ("persist.pack", "persist.save")


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    cycles = run.counters.get("cycles", 0)
    rec = obs.window(*w[-1]) if w else {}
    if not any(s in rec for s in SPANS) or not cycles:
        return None
    return sum(rec[s][1] for s in SPANS if s in rec) / cycles
