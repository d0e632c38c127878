"""Mean host time (ms) of a read batch that ran a closure, outside its
``closure.execute`` and ``planner.decide`` spans: the engine's cache
bookkeeping, the host mirror of the state and the slicing of answers."""


def read(run):
    inside: dict = {}
    for s in run.spans:
        if s.name in ("closure.execute", "planner.decide") and s.t_end:
            inside[s.parent_id] = inside.get(s.parent_id, 0.0) + s.duration_s
    host = [c.t1 - c.t0 - inside.get(c.span_id, 0.0) for c in run.calls
            if c.kind == "read" and c.cache != "hit"]
    return 1e3 * sum(host) / len(host) if host else None
