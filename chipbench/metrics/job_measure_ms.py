"""job_measure_ms: milliseconds per traced job spent running the
program, the summed ``offload.measure`` spans of the device trace (one
per wall-clocked repeat of a candidate and per run of the PCAST pair, in
every stage): the floor under the time to answer."""
import spans


def read(cell):
    trace = cell.device_trace
    found = spans.spans(trace, "offload", "measure") \
        if trace is not None else []
    if not found:
        return None
    return sum(e - s for s, e in found) / 1e6 / len(trace.requests)
