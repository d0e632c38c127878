"""95th-percentile read latency (ms) over the same population as
``read_p50_ms``."""
from bench.harness import quantile


def read(run):
    if not run.reads:
        return None
    return 1e3 * quantile([r.latency_s for r in run.reads], 0.95)
