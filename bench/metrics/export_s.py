"""Host seconds per refresh cycle from the end of the rank program to the
persisted table: ``run_rank_cycle``'s span less the rank program's
device time (``suggestions_to_host``, the counters it reads), plus
``pack_suggestions`` and ``CheckpointManager.save``."""


def read(run):
    s = run.summary
    cycles = run.counters.get("cycles", 0)
    t = s.program_s("ranking_cycle") if s is not None else None
    if t is None or not cycles:
        return None
    host = run.spans.total("rank") + run.spans.total("persist")
    return (host - t) / cycles
