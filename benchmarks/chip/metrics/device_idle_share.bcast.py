"""Share of the traced window in which no operation ran on the device:
1 - (union of operation intervals / window), mean over the cell's chips,
in %."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
