"""Mean ``planner.decide`` span (ms): the planner's choice of executable
for one closure call."""


def read(run):
    d = [s.duration_s for s in run.spans
         if s.name == "planner.decide" and s.t_end is not None]
    return 1e3 * sum(d) / len(d) if d else None
