"""Starved time a step of the measured window (nothing of the engine's in
flight: harness/host_gaps.py) while `serving::prefill.admit`, the self time
of `serving::prefill`, `serving::prefill.upload` or
`serving::prefill.publish` was the innermost span open: the host around a
prefill's executable, over all steps of the window."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.starved_ms_per_step(record, "prefill_call")
