"""job_search_ms: milliseconds per traced job in the search stage
(``offload.search``: the GA, each unique candidate wall-clocked on the
host or the chip), from the offload pipeline's spans in the device
trace."""
import spans


def read(cell):
    trace = cell.device_trace
    found = spans.spans(trace, "offload", "search") \
        if trace is not None else []
    if not found:
        return None
    return sum(e - s for s, e in found) / 1e6 / len(trace.requests)
