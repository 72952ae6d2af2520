"""Device milliseconds of the replay programs (``ingest_many``, both
engines) per tick replayed, from the trace."""


def read(run):
    s = run.summary
    t = s.program_s("ingest_many") if s is not None else None
    ticks = run.counters.get("ticks", 0)
    return 1e3 * t / ticks if t is not None and ticks else None
