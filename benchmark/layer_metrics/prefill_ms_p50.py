"""Median engine.prefill() call (it blocks on the first token), harness
wrapper."""
import statistics


def read(record, trace):
    d = record.get("samples", {}).get("prefill_s")
    return 1e3 * statistics.median(d) if d else None
