"""Device time per control interval: the union of device operations inside
each interval span, averaged over the cell's devices and intervals."""


def read(run):
    return None if run.trace is None else run.trace["device_ms"]
