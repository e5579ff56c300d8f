"""1 - (union of device-busy intervals / traced window), in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
