"""Probe rounds each marginal lookup of the rank program ran over its
whole batch, per lookup per refresh cycle: the mean of the program's
counter ``rank.lookup_full_rounds`` records in the window (one record per
lookup). None where the program counts none."""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    w = [(t0, t1) for name, t0, t1 in run.spans.spans if name == "window"]
    if not w:
        return None
    t0, t1 = w[-1]
    rounds = [n for name, t, n in list(obs.RECORDER.counts)
              if name == "rank.lookup_full_rounds" and t0 <= t <= t1]
    if not rounds:
        return None
    return sum(rounds) / len(rounds)
