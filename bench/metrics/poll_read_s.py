"""Host seconds per refresh cycle of a frontend reading the persisted
table (``CheckpointManager.restore_host``: read, verify, decode), the
program's span ``poll.read`` in the window. None where the program
records no spans."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    cycles = run.counters.get("cycles", 0)
    rec = obs.window(*w[-1]) if w else {}
    if "poll.read" not in rec or not cycles:
        return None
    return rec["poll.read"][1] / cycles
