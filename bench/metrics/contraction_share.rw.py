"""Share (%) of the device's busy time spent in contractions: XLA dot and
convolution operations (the MXU) and Pallas ``bitmm`` calls."""


def read(run):
    dev = run.device
    if dev is None or dev.busy_s <= 0:
        return None
    return 100.0 * dev.contraction_s / dev.busy_s
