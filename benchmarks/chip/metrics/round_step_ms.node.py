"""Device time per node program (every round step of one node's
broadcast), in ms."""


def read(run):
    tr = run.trace
    if tr is None or not tr.request_count():
        return None
    busy = tr.mean_over_chips(tr.program_busy_ns)
    if not busy:
        return None
    return busy / tr.request_count() * 1e-6
