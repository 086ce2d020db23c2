"""Device time in collective-permute operations per broadcast, mean over
the chips, in ms."""


def read(run):
    tr = run.trace
    if tr is None or not tr.request_count():
        return None
    if not any(tr.permute_count(c) for c in range(tr.chips)):
        return None
    return tr.mean_over_chips(tr.permute_ns) / tr.request_count() * 1e-6
