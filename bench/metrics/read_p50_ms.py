"""Median read latency (ms): every read due in the window, timed from
when it was due, so a late generator counts; a shed, failed or lost read
is missing, i.e. later than any answer."""
from bench.harness import quantile


def read(run):
    if not run.reads:
        return None
    return 1e3 * quantile([r.latency_s for r in run.reads], 0.50)
