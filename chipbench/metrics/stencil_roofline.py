"""stencil_roofline: the Himeno sweep program's (``jit_sweep``) share of
its roofline. Device time per call from the trace, against the least time
for its bytes and operations (``chipbench/roofline.py``) at the chip's
published peaks (``chipbench/peaks.json``); the sweep is bound by HBM
bandwidth."""
import roofline


def read(cell):
    tr = cell.device_trace
    if tr is None:
        return None
    calls, seconds = tr.kernel("sweep")
    if not calls or seconds <= 0:
        return None
    peak = roofline.peak_for(cell.peaks, cell.device["kind"])
    least, _ = roofline.least_time_s(
        roofline.himeno_sweep(tuple(cell.config["grid"])), peak)
    return 100.0 * least / (seconds / calls)
