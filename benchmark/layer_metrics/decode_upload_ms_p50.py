"""Median `serving::decode.upload`: the host building one decode call's
arguments (block tables, positions, last tokens and keys put on the
device)."""
from benchmark.harness import program_spans


def read(record, trace):
    return program_spans.median_ms(record, "decode.upload")
