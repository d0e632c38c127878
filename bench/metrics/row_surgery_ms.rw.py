"""A write's host row surgery (ms): per ``engine.write`` that repaired,
the sum of its ``repair.plan`` (reverse reach), ``repair.base_rows``
(base rows and patch) and ``repair.upload`` (patch transfer and row
scatter, as dispatched) spans, averaged over those writes (delta repair
layer)."""

SURGERY = ("repair.plan", "repair.base_rows", "repair.upload")


def read(run):
    by_id = {s.span_id: s for s in run.spans}
    per_write: dict = {}
    for s in run.spans:
        if s.name not in SURGERY or s.t_end is None:
            continue
        up = by_id.get(s.parent_id)
        while up is not None and up.name != "engine.write":
            up = by_id.get(up.parent_id)
        if up is not None:
            per_write[up.span_id] = per_write.get(up.span_id, 0.0) + (
                s.duration_s)
    if not per_write:
        return None
    return 1e3 * sum(per_write.values()) / len(per_write)
