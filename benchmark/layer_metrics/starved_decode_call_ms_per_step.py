"""Starved time a step of the measured window (nothing of the engine's in
flight: harness/host_gaps.py) while `serving::decode.prepare`, the self time
of `serving::decode_step`, `serving::decode.upload` or `serving::decode.commit`
was the innermost span open: the hooks and the capacity check ahead of a
decode call, its arguments put on the device, and the host's state advanced
once the tokens are back."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.starved_ms_per_step(record, "decode_call")
