"""Mean fixpoint iterations per closure: the ``iterations`` attribute of
each ``closure.execute`` span, the executables' own loop counters summed
over the warm restarts (closures layer)."""


def read(run):
    its = [s.attrs["iterations"] for s in run.spans
           if s.name == "closure.execute" and "iterations" in s.attrs]
    return sum(its) / len(its) if its else None
