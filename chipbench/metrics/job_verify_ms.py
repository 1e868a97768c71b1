"""job_verify_ms: milliseconds per traced job in the verify stage
(``offload.verify``: the winner re-measured, then the PCAST pair of the
host path and the winner's placed run), from the offload pipeline's
spans in the device trace."""
import spans


def read(cell):
    trace = cell.device_trace
    found = spans.spans(trace, "offload", "verify") \
        if trace is not None else []
    if not found:
        return None
    return sum(e - s for s, e in found) / 1e6 / len(trace.requests)
