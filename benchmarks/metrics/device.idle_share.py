"""Layer device. Share of the traced window in which no operation ran on
the device: 1 - union of the device's operation intervals / window."""


def read(run):
    return run.trace.idle_share if run.trace else None
