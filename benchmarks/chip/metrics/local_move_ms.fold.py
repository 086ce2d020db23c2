"""Device time per broadcast under the folded program's ``bcast.local``
scope (the rows handed between nodes of one chip) outside any collective
permute, mean over the chips, in ms."""

import folded


def read(run):
    ph = folded.phases_of(run)
    return None if ph is None else ph.phase_ms(folded.LOCAL)
