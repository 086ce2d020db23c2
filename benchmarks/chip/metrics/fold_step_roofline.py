"""The HBM bytes no implementation of the folded cycle can avoid (every
row a chip's nodes receive, written once, and every row they send, read
once) over the chip's published HBM bandwidth, over the device time per
broadcast in which an operation under ``bcast.step`` or ``bcast.local``
runs (permutes in flight or not), mean over the chips, in %."""

import folded
import phases


def read(run):
    ph = folded.phases_of(run)
    need = run.info.get("least_bytes_per_chip")
    if ph is None or run.peaks is None or not need:
        return None
    tr = ph.trace
    n = tr.request_count()
    if not n or not ph.scoped:
        return None
    busy = tr.mean_over_chips(lambda c: folded.scoped_busy_ns(
        ph, c, (phases.STEP, folded.LOCAL)))
    if not busy:
        return None
    least_ns = need / run.peaks["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / (busy / n)
