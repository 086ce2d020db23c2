"""Device time per broadcast under the program's ``bcast.unstack`` scope
(the stacked packet rows cut back to the payload) outside any collective
permute, mean over the chips, in ms."""

import phases


def read(run):
    ph = phases.of(run)
    return None if ph is None else ph.phase_ms(phases.UNSTACK)
