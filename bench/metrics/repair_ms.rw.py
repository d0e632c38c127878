"""Mean ``delta.repair`` span (ms): one write's row surgery and repair
closure (delta repair layer)."""


def read(run):
    d = [s.duration_s for s in run.spans
         if s.name == "delta.repair" and s.t_end is not None]
    return 1e3 * sum(d) / len(d) if d else None
