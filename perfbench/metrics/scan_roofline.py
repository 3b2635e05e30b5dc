"""The fused retrieval scans' share of their roofline: the least time the
chip needs for the window's scans (the larger of operations over peak
FLOP/s and bytes over HBM bandwidth, from shapes: every valid float32 row
of both planes read once per call, queries counted as sent) over the
device time of the programs launched inside the benchmark's scan span,
in %."""
import tracereduce
import work


def read(r):
    if r.red is None:
        return None
    secs, n = tracereduce.program_time(r.red, "scan")
    if not n or secs <= 0:
        return None
    dim = r.cfg["fleet"]["dim"]
    least = sum(work.roofline_seconds(*work.scan_work(q, r.rows0, dim),
                                      r.peak)
                for q in r.rec.scan_queries)
    return 100.0 * least / secs
