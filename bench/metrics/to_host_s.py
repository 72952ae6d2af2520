"""Host seconds per refresh cycle of ``suggestions_to_host``: the device
table to a host dict, the program's span ``rank.to_host`` in the window.
None where the program records no spans."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    cycles = run.counters.get("cycles", 0)
    rec = obs.window(*w[-1]) if w else {}
    if "rank.to_host" not in rec or not cycles:
        return None
    return rec["rank.to_host"][1] / cycles
