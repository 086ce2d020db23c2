"""Time per broadcast from the host's call into ``ExecutablePlan.run`` (the
start of the benchmark's ``bench.request`` span, on the profiler's host
plane) to the first operation of the program run it launched, on each
chip's plane, mean over the broadcasts and the chips, in ms: dispatch and
the payload's placement on every chip before the program starts. The two
ends lie on two clocks; each chip's gap is corrected by the midpoint of
its clock-offset interval (``phases.Phases.clock_offsets``)."""

import phases


def read(run):
    ph = phases.of(run)
    return None if ph is None else ph.prelaunch_ms()
