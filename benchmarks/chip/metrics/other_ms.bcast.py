"""Device time per broadcast in the program's local work under none of
``bcast.place``, ``bcast.step`` and ``bcast.unstack``: relayouts the
compiler put in (no scope), loop control and slot arithmetic
(``bcast.cycle``), mean over the chips, in ms. With those three it
partitions ``local_ms.bcast``."""

import phases


def read(run):
    ph = phases.of(run)
    return None if ph is None else ph.phase_ms(phases.OTHER)
