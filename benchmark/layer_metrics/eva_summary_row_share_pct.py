"""Share of the rows a decode step's attention sees that are chunk summaries:
`summary_rows_held / (window_rows_held + summary_rows_held)`, summed over
the window's `serving::step` spans (one layer's worth, the active slots).
It says how much of what attention sees is compressed memory: 0 would mean
the traffic never leaves its first window. None from a program that does not
count the rows."""
from benchmark.harness import program_spans

KEYS = ("window_rows_held", "summary_rows_held")


def read(record, trace):
    rows = program_spans.read(record)
    steps = [s["attrs"] for s in (rows or {}).get("steps", ())
             if all(k in s["attrs"] for k in KEYS)]
    window, summary = (sum(a[k] for a in steps) for k in KEYS)
    if not window + summary:
        return None
    return 100.0 * summary / (window + summary)
