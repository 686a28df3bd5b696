"""Share of the measured window in which the engine had nothing in flight
(no executable being enqueued, enqueued, or on its way back: between the
start of a `serving::decode.dispatch` / `serving::prefill.dispatch` and the
end of that call's `.wait`): a lower bound on the device's idle share, read
off the span log in the untraced window (harness/host_gaps.py)."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.starved_pct(record)
