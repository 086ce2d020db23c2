"""Payload bytes of every broadcast whose node program completed in the
window over the window's seconds, in GB/s."""


def read(run):
    return run.rate_GBps()
