"""job_analyze_ms: milliseconds per traced job in the analyze stage
(``offload.analyze``: the adapter, the device read and the all-host
baseline wall-clocked), from the offload pipeline's spans in the device
trace."""
import spans


def read(cell):
    trace = cell.device_trace
    found = spans.spans(trace, "offload", "analyze") \
        if trace is not None else []
    if not found:
        return None
    return sum(e - s for s, e in found) / 1e6 / len(trace.requests)
