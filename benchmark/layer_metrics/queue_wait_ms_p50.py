"""Median time a request waits in the scheduler's queue: submit until its
first placement opens, over the requests submitted in the window. Read from
the program's `serving::queue` spans (one per request, from the stamps its
PhaseTrail already holds). In a closed loop it is the rest of the `step()`
that was running when the client submitted, plus the placements ahead."""
import statistics

from benchmark.harness import program_spans


def read(record, trace):
    rows = program_spans.read(record)
    waits = [r["queue_ns"] for r in rows["requests"]] if rows else []
    return statistics.median(waits) / 1e6 if waits else None
