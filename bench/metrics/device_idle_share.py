"""Device: 1 minus the union of the device's operation intervals over the
traced stretch, averaged over the chips."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
