"""The bytes no implementation of the node program can avoid (each
received row read once and written once, each sent row read once) over the
chip's published HBM bandwidth, over the device time per node program, in
%."""


def read(run):
    tr = run.trace
    need = run.info.get("least_bytes_per_request")
    if tr is None or run.peaks is None or not need or not tr.request_count():
        return None
    busy = tr.mean_over_chips(tr.program_busy_ns)
    if not busy:
        return None
    least_ns = need / run.peaks["hbm_bytes_per_s"] * 1e9
    return 100.0 * least_ns / (busy / tr.request_count())
