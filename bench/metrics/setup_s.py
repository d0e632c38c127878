"""Set-up seconds: from the process's start to the window's, with the
imports, the graph, the engine and every warm-up closure (and, in a run
that compiles, the compiles)."""


def read(run):
    return run.setup_s
