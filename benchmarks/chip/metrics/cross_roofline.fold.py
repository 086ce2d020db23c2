"""The bytes a chip-level permute of the folded broadcast carries (its
stack of rows, padding included, mean over the permutes of a broadcast)
over the chip's published ICI bandwidth, over the mean time a collective
permute is in flight (each from its ``-start`` to its ``-done``, counted
alone where permutes overlap), in %."""


def read(run):
    tr = run.trace
    carried = run.info.get("bytes_per_permute")
    if tr is None or run.peaks is None or not carried:
        return None
    spans = [p for c in range(tr.chips) for p in tr.permutes(c)]
    if not spans:
        return None
    mean_ns = sum(e - s for s, e in spans) / len(spans)
    least_ns = carried / (run.peaks["ici_bits_per_s"] / 8) * 1e9
    return 100.0 * least_ns / mean_ns
