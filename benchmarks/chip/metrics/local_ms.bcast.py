"""Device time per broadcast in the program's operations that are not
collective permutes (round steps, the payload's replication and padding,
the unstacking), mean over the chips, in ms."""


def read(run):
    tr = run.trace
    if tr is None or not tr.request_count():
        return None
    local = tr.mean_over_chips(
        lambda c: tr.program_busy_ns(c) - tr.permute_ns(c))
    if not local:
        return None
    return local / tr.request_count() * 1e-6
