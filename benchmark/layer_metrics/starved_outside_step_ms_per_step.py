"""Starved time a step of the measured window (nothing of the engine's in
flight: harness/host_gaps.py) while no `serving::step` was open: the caller
between two `step()` calls (the benchmark's loop here, a server's in a
deployment). It has to be small before the other three are believed."""
from benchmark.harness import host_gaps


def read(record, trace):
    return host_gaps.starved_ms_per_step(record, "outside_step")
