"""Median engine.decode() call (it blocks on the tokens), harness wrapper."""
import statistics


def read(record, trace):
    d = record.get("samples", {}).get("decode_s")
    return 1e3 * statistics.median(d) if d else None
