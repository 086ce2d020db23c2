"""Median over every broadcast in the window of the time from calling
``run`` with the payload on the root chip to every chip's copy being
ready, in ms."""


def read(run):
    return run.latency_ms(0.5)
