"""The payload's bytes over the chip's published ICI bandwidth (the least
time in which the root can send the payload out), over the mean time per
broadcast in the traced run, in %. It counts the same work whatever
implements the broadcast."""


def read(run):
    if run.trace is None or run.peaks is None or not run.requests:
        return None
    least_s = run.info["message_bytes"] / (run.peaks["ici_bits_per_s"] / 8)
    mean_s = sum(t1 - t0 for t0, t1, _ in run.requests) / len(run.requests)
    return 100.0 * least_s / mean_s
