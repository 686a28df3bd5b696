"""Mean of submit -> first token over every request submitted in the window
that got one: the same stamps as ttft_p50_ms. Steadier than the median, which
sits between the clusters the prefill buckets make."""
import statistics


def read(record, trace):
    t = record.get("samples", {}).get("ttft_s")
    return 1e3 * statistics.fmean(t) if t else None
