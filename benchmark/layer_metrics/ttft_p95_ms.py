"""95th percentile of submit -> first token over every request submitted in
the window: the same stamps as ttft_p50_ms. A per-layer metric because a
window's requests leave too few samples beyond it to decide a PR."""
import statistics


def read(record, trace):
    t = record.get("samples", {}).get("ttft_s")
    if not t or len(t) < 20:
        return None
    return 1e3 * statistics.quantiles(t, n=20)[-1]
