"""Median device time of one execution of the decode program, from the
trace's `XLA Modules` line. Beside decode_step_ms_p50 (the host's clock around
the same call) it says how much of a decode step is the host: dispatch, table
upload, the blocking fetch."""
import statistics


def read(record, trace):
    if trace is None:
        return None
    runs = [d for name, ds in trace["module_s"].items()
            if "decode_fn" in name for d in ds]
    return 1e3 * statistics.median(runs) if runs else None
