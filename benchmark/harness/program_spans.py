"""The serve step read from inside: the program's own span log, cut to the
measured window.

The program keeps every closed `serving::*` span in a bounded, process-wide
log (`paddle_tpu.profiler.span_log()`, on by default since PR 26) on the
`time.perf_counter_ns` clock: `serving::step` with a child per scheduler
phase, the engine's `prefill` and `decode_step` with `decode.upload`,
`decode.dispatch` and `decode.wait` under it, and one `serving::queue` per
request. This file reads that log after the loop has returned (the
scheduler and engine are gone by then; the log is the process's) and hands
the `layer_metrics/` readers rows:

    steps     one per `serving::step` that started in the window:
              {"dur_ns", "attrs", "self_ns": {name: ns}, "total_ns": {name: ns}}
              names without the `serving::` prefix; `self_ns` charges each
              span its duration minus what its children cover, so the values
              of one step add up to its `dur_ns`; `total_ns` is each name's
              whole duration (children included), summed over the step
    spans     {name: [dur_ns, ...]} of every span under those steps
    requests  one per `serving::queue` span that started in the window
              (a request submitted in it): {"request_id", "queue_ns"}

The window is the harness's own: it opens `setup_s` after `run.py` started
(`_T0`, the same clock) and lasts `window_s`. None comes back where there
is nothing to read: a program without the log (the parent of PR 26), a
record without a window, or a log that has already overwritten part of the
window: never a number from a partial window.
"""
import statistics
import sys

PREFIX = "serving::"
_cache = (None, None)


def _harness_t0():
    """`run.py`'s `_T0`: its module is `__main__` under the command and
    `benchmark.run` under the tests."""
    for name in ("__main__", "benchmark.run"):
        t0 = getattr(sys.modules.get(name), "_T0", None)
        if isinstance(t0, float):
            return t0
    return None


def window_ns(record):
    t0 = _harness_t0()
    try:
        start = t0 + record["end_to_end"]["setup_s"]
        return int(start * 1e9), int((start + record["window_s"]) * 1e9)
    except (KeyError, TypeError):
        return None


def _log():
    profiler = sys.modules.get("paddle_tpu.profiler")
    span_log = getattr(profiler, "span_log", None)
    return span_log() if span_log else None


def _short(name):
    return name[len(PREFIX):] if name.startswith(PREFIX) else name


def rows_of(spans, start_ns, end_ns):
    """The rows described above from closed spans (dicts with `name`, `ts`,
    `dur`, `span_id`, `parent`, `attrs`)."""
    children = {}
    for s in spans:
        children.setdefault(s["parent"], []).append(s)
    steps, by_name, requests = [], {}, []

    def walk(span, row):
        kids = children.get(span["span_id"], ())
        name = _short(span["name"])
        own = span["dur"] - sum(k["dur"] for k in kids)
        row["self_ns"][name] = row["self_ns"].get(name, 0) + max(own, 0)
        row["total_ns"][name] = row["total_ns"].get(name, 0) + span["dur"]
        by_name.setdefault(name, []).append(span["dur"])
        for k in kids:
            walk(k, row)

    for s in sorted(spans, key=lambda s: s["ts"]):
        if not start_ns <= s["ts"] < end_ns:
            continue
        if s["name"] == PREFIX + "step":
            row = {"dur_ns": s["dur"], "attrs": s["attrs"] or {},
                   "self_ns": {}, "total_ns": {}}
            walk(s, row)
            steps.append(row)
        elif s["name"] == PREFIX + "queue":
            requests.append({"request_id": (s["attrs"] or {}).get(
                "request_id"), "queue_ns": s["dur"]})
    return {"steps": steps, "spans": by_name, "requests": requests}


def read(record):
    """The measured window's rows, or None (see the module's text)."""
    global _cache
    log, window = _log(), window_ns(record)
    if log is None or window is None:
        return None
    key = (id(record), log.appended)
    if _cache[0] != key:
        # a step that began in the window may close a little after it, and
        # its children with it: read a minute beyond, select by start
        spans = log.window(window[0], window[1] + 60 * 10**9)
        _cache = (key, None if spans is None
                  else rows_of(spans, *window))
    return _cache[1]


def median_ms(record, name):
    """Median duration in ms of the window's spans called `name`."""
    rows = read(record)
    durs = rows["spans"].get(name) if rows else None
    return statistics.median(durs) / 1e6 if durs else None


def step_mean_pct(record, share):
    """100 x the mean of `share(step row)` over the window's steps for
    which it is not None."""
    rows = read(record)
    if not rows:
        return None
    shares = [v for v in map(share, rows["steps"]) if v is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None
