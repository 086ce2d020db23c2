"""One packet row's bytes over the chip's published ICI bandwidth (the
least time a permute of one row can take), over the mean duration of a
collective permute, in %."""


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None or "row_bytes" not in run.info:
        return None
    n = sum(tr.permute_count(c) for c in range(tr.chips))
    if not n:
        return None
    mean_ns = sum(tr.permute_ns(c) for c in range(tr.chips)) / n
    least_ns = run.info["row_bytes"] / (run.peaks["ici_bits_per_s"] / 8) * 1e9
    return 100.0 * least_ns / mean_ns
