"""Device time per broadcast under the program's ``bcast.step`` scope (the
round steps of the cycle: write the received row, read the next row to
send) outside any collective permute, mean over the chips, in ms."""

import phases


def read(run):
    ph = phases.of(run)
    return None if ph is None else ph.phase_ms(phases.STEP)
