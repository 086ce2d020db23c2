"""Payload bytes of every broadcast completed in the window over the
window's seconds, in GB/s (nccl-tests ``algbw``; for a broadcast ``busbw``
is the same). The window includes making each fresh payload."""


def read(run):
    return run.rate_GBps()
