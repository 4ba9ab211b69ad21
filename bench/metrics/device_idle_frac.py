"""Device: the fraction of serving time in which the chip ran no
operation, 1 - busy / serving. Serving time is the union of the
benchmark's serve-call spans; busy time is the union of device operations
inside them. On several chips, the mean over the chips."""


def read(run):
    if run.trace is None or not run.trace.has_device_ops:
        return None
    return run.trace.idle_fraction_while_serving()
