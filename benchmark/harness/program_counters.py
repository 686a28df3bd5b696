"""Counters the program leaves on its spans, summed over the measured
window: `serving::decode.wait` and `serving::prefill` carry the expert
layers' counts of the executable they waited for (docs/observability.md
§12). Reads the same span log and the same window as program_spans.py;
None where there is no log, no window, or a log that has wrapped."""
from . import program_spans

PREFIX = program_spans.PREFIX


def window_spans(record, name):
    """The closed spans called `serving::<name>` that started in the
    window, or None."""
    log, window = program_spans._log(), program_spans.window_ns(record)
    if log is None or window is None:
        return None
    spans = log.window(window[0], window[1] + 60 * 10**9)
    if spans is None:
        return None
    return [s for s in spans if s["name"] == PREFIX + name
            and window[0] <= s["ts"] < window[1]]


def attr_sums(record, name, keys):
    """{"spans": how many carried every key, key: sum} or None."""
    spans = window_spans(record, name)
    if spans is None:
        return None
    rows = [s["attrs"] for s in spans
            if s["attrs"] and all(k in s["attrs"] for k in keys)]
    if not rows:
        return None
    return {"spans": len(rows),
            **{k: sum(int(r[k]) for r in rows) for k in keys}}
