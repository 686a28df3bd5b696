"""Prefill's share of the scheduler's time: the window's `serving::prefill`
spans over its `serving::step` spans. Every prefill stalls every decoding
slot for its length, so this is the share of the window in which no client
got a token."""
from benchmark.harness import program_spans


def read(record, trace):
    rows = program_spans.read(record)
    if not rows or not rows["steps"]:
        return None
    steps = sum(s["dur_ns"] for s in rows["steps"])
    prefill = sum(s["total_ns"].get("prefill", 0) for s in rows["steps"])
    return 100.0 * prefill / steps if steps else None
