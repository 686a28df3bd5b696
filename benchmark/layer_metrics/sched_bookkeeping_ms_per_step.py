"""Mean `serving::bookkeeping` a step: what the scheduler does as a step
closes (the step counter, two gauges, the KV ledger's reconcile against the
pool, the JSONL records when a metrics path is set), over the steps of the
window. Every first token is a whole `step()`, this phase included."""
import statistics

from benchmark.harness import program_spans


def read(record, trace):
    rows = program_spans.read(record)
    durs = rows["spans"].get("bookkeeping") if rows else None
    return statistics.mean(durs) / 1e6 if durs else None
