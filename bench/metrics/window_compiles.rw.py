"""Backend compiles inside the window (JAX monitoring events): shapes the
set-up did not warm."""


def read(run):
    return run.compiles_in_window
