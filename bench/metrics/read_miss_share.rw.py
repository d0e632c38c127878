"""Share (%) of read batches that were not pure cache hits: each ran a
closure (engine cache layer)."""


def read(run):
    calls = [c for c in run.calls if c.kind == "read"]
    if not calls:
        return None
    return 100.0 * sum(c.cache != "hit" for c in calls) / len(calls)
