"""Mean ``closure.execute`` span (ms) of the read path (those not under a
``delta.repair``): one masked closure to its fixpoint, device time
included (the span ends on the overflow flag's read-back)."""


def read(run):
    repairs = {s.span_id for s in run.spans if s.name == "delta.repair"}
    d = [s.duration_s for s in run.spans
         if s.name == "closure.execute" and s.t_end is not None
         and s.parent_id not in repairs]
    return 1e3 * sum(d) / len(d) if d else None
