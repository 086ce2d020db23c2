"""Device time per broadcast under the program's ``bcast.place`` scope
(padding the payload into packet rows, the relay rows, zeroing the non-root
copies) outside any collective permute, mean over the chips, in ms."""

import phases


def read(run):
    ph = phases.of(run)
    return None if ph is None else ph.phase_ms(phases.PLACE)
