"""Median, over the window's chunks, of the time ``fetch`` waited for the
frames of a later chunk than the one being answered: the prefetch of
chunk k+1 inside ``run_chunk(k)`` blocks k's answer until k+1 exists.
Benchmark span around the wait in ``fetch``."""
import statistics


def read(run):
    if not run.cell.live or not run.rec.chunks:
        return None
    return statistics.median(c.prefetch_wait_s for c in run.rec.chunks) * 1e3
