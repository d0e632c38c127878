"""Share (%) of the traced window in which no operation ran on the chip."""


def read(run):
    dev = run.device
    if dev is None or dev.window_s <= 0:
        return None
    return 100.0 * dev.idle_share
