"""Share of the traced window in which no op ran on the device, in %:
1 - (union of device-op intervals / window), mean over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
