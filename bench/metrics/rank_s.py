"""Device seconds of the rank program per refresh cycle, from the trace:
every run of a program named ``ranking_cycle``, over the cycles."""


def read(run):
    s = run.summary
    t = s.program_s("ranking_cycle") if s is not None else None
    cycles = run.counters.get("cycles", 0)
    return t / cycles if t is not None and cycles else None
