"""Device time in collective operations per control interval (the
coordinator's cross-shard ``psum``), averaged over the cell's devices; None
without a trace or where the trace holds no collective."""


def read(run):
    return None if run.trace is None else run.trace["collective_ms"]
