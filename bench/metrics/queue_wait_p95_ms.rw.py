"""95th percentile of the answered reads' ``queue_delay_s`` (ms): from
admission to the start of their batch's engine call (serve layer)."""
from bench.harness import quantile


def read(run):
    waits = [r.stats["queue_delay_s"] for r in run.reads if r.outcome == "ok"]
    return 1e3 * quantile(waits, 0.95) if waits else None
