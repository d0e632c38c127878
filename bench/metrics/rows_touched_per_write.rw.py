"""Closure rows evicted plus rows repaired, per acknowledged write, from
the ``DeltaStats`` that ``apply_delta`` returns."""


def read(run):
    done = [w.delta for w in run.writes if w.outcome == "ok"]
    if not done:
        return None
    return sum(d["rows_evicted"] + d["rows_repaired"] for d in done) / len(done)
