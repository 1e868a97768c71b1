"""job_measurements: runs of the program per traced job, the count of the
offload pipeline's ``offload.measure`` spans in the device trace (each
wall-clocked repeat of a candidate, and each run of the PCAST pair)."""
import spans


def read(cell):
    trace = cell.device_trace
    found = spans.spans(trace, "offload", "measure") \
        if trace is not None else []
    if not found:
        return None
    return len(found) / len(trace.requests)
