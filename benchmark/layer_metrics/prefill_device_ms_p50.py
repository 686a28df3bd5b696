"""Median device time of one execution of a prefill program (every bucket's
`prefill_fn` module on the trace's `XLA Modules` line, the traced window's
mix of buckets). Beside prefill_ms_p50 (the host's clock around the blocking
call) it says how much of a prefill is the host."""
import statistics


def read(record, trace):
    if trace is None:
        return None
    runs = [d for name, ds in trace.get("module_s", {}).items()
            if "prefill_fn" in name for d in ds]
    return 1e3 * statistics.median(runs) if runs else None
