"""The rank program's share of its roofline, in percent.

Least time of one cycle: the bytes it must move at least once over the
chip's peak HBM bandwidth. It must read every lane of the cooccurrence
and query stores (the keys that say which slots are live, the weights,
counts and last ticks that the score and the lazy decay read, the source
and destination lanes that the grouping and the output read; the query
store's totals sum over all of it), and write the table: per source
ranked, its key and ``top_k`` destinations with their scores. The store
bytes are the nbytes of the store's arrays as the program holds them
(``rank_store_bytes``, counted at set-up), so narrower lanes lower the
least time with them. The operations are a few per slot and never bound
it. The share is that least time over the device seconds of the rank
program per cycle; it does not depend on which selection runs.
"""
from bench import peaks

# per source in the table: src key (2 x u32), top_k x (dst key 2 x u32,
# score f32)
SRC_BYTES, PER_RANK_BYTES = 8, 12


def least_bytes(store_bytes: float, sources: float, top_k: int) -> float:
    return store_bytes + sources * (SRC_BYTES + top_k * PER_RANK_BYTES)


def read(run):
    s = run.summary
    cycles = run.counters.get("cycles", 0)
    t = s.program_s("ranking_cycle") if s is not None else None
    if not t or not cycles or "rank_store_bytes" not in run.counters:
        return None
    b = least_bytes(run.counters["rank_store_bytes"],
                    run.counters["sources"], run.counters["top_k"])
    least = b / peaks.device_peaks(run.device_kind)["hbm_bytes_s"]
    return 100.0 * least / (t / cycles)
