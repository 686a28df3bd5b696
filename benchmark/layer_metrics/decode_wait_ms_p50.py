"""Median `serving::decode.wait`: the host blocked on one decode call's
positions and tokens (the device's time for the step, less what the dispatch
overlapped, plus the copy back)."""
from benchmark.harness import program_spans


def read(record, trace):
    return program_spans.median_ms(record, "decode.wait")
