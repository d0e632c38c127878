"""Mean ``engine.mirror`` span (ms): one device-to-host copy of a cached
closure state, after a closure or a repair (engine host layer)."""


def read(run):
    d = [s.duration_s for s in run.spans
         if s.name == "engine.mirror" and s.t_end is not None]
    return 1e3 * sum(d) / len(d) if d else None
