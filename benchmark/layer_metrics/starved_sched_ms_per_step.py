"""Starved time a step of the measured window (nothing of the engine's in
flight: harness/host_gaps.py) while any other span under `serving::step` was
the innermost open (`retire`, `refill`, `grow`, `emit`, `bookkeeping`,
`step.counts`, the step's own time): the scheduler's Python."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.starved_ms_per_step(record, "sched")
