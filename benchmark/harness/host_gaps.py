"""When the device had nothing of the engine's to run, and what the host was
doing then: read from the program's span log alone, in the measured window.

*In flight* is the union of the intervals [start of `X.dispatch`, end of
`X.wait`], X in `decode`, `prefill`: the engine is enqueuing an executable,
has one enqueued, or its result is on the way back. *Starved* is the rest of
the window, which here runs from the start of the first `serving::step` that
began in the harness's window to the end of the last: the device cannot be
working for this engine then, so starved time is a LOWER bound on the
device's idle time (the launch after the dispatch began and the fetch after
the module ended are in flight and idle), read with no profiler attached.

Each starved stretch is charged to the INNERMOST span open at the time (a
slice of that span's self time), in four groups:

    decode_call   decode.prepare, decode_step (self), decode.upload,
                  decode.commit
    prefill_call  prefill.admit, prefill (self), prefill.upload,
                  prefill.publish
    sched         anything else under a `serving::step`
    outside_step  no `serving::step` open: the caller between two `step()`s

The four and the in-flight time add up to the window to the nanosecond.

Takes the raw spans through `program_spans._log()` / `window_ns()`. None
comes back, never a partial number, where the log is missing or has wrapped,
the record has no window, no step began in it, or a `serving::prefill` or
`serving::decode_step` of the window lacks its `dispatch` / `wait` children
(the parent of PR 36; an engine that runs its prefill another way).
"""
import statistics

from . import program_spans

GROUPS = ("decode_call", "prefill_call", "sched", "outside_step")
GROUP_OF = {"decode.prepare": "decode_call", "decode_step": "decode_call",
            "decode.upload": "decode_call", "decode.commit": "decode_call",
            "prefill.admit": "prefill_call", "prefill": "prefill_call",
            "prefill.upload": "prefill_call",
            "prefill.publish": "prefill_call"}
CALLS = {"decode_step": "decode", "prefill": "prefill"}
_cache = (None, None)


def _end(span):
    return span["ts"] + span["dur"]


def _merged(intervals, lo, hi):
    """Sorted, disjoint [(a, b)] of `intervals` cut to [lo, hi)."""
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def split(spans, start_ns, end_ns):
    """The window's split from closed spans (dicts with `name`, `ts`, `dur`,
    `span_id`, `parent`), or None (see the module's text):

        window_ns        (first step's start, last step's end)
        steps            the `serving::step`s that began in [start_ns, end_ns)
        in_flight_ns     the union of the calls' in-flight intervals
        starved_ns       {group: ns}, every group present
        starved_by_span  {innermost span, `serving::` dropped, or
                          "outside_step": ns}
        prefill_host_ns  per prefill that has its `admit` and `publish`:
                         the three spans minus the call's in-flight interval
    """
    name_of = program_spans._short
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    for group in kids.values():
        group.sort(key=lambda s: s["ts"])
    steps = sorted((s for s in spans
                    if s["name"] == program_spans.PREFIX + "step"
                    and start_ns <= s["ts"] < end_ns),
                   key=lambda s: s["ts"])
    if not steps:
        return None
    lo, hi = steps[0]["ts"], max(map(_end, steps))

    flight_of = {}
    for s in spans:
        call = CALLS.get(name_of(s["name"]))
        if call is None or not lo <= s["ts"] < hi:
            continue
        mine = {name_of(k["name"]): k for k in kids.get(s["span_id"], ())}
        dispatch, wait = mine.get(call + ".dispatch"), mine.get(call + ".wait")
        if dispatch is None or wait is None:
            return None
        flight_of[s["span_id"]] = (dispatch["ts"], _end(wait))
    # a prefill between its `admit` and its `publish`, siblings in turn
    prefill_host = []
    for around in kids.values():
        for admit, call, publish in zip(around, around[1:], around[2:]):
            if call["span_id"] in flight_of and [
                    name_of(k["name"]) for k in (admit, call, publish)] \
                    == ["prefill.admit", "prefill", "prefill.publish"]:
                f0, f1 = flight_of[call["span_id"]]
                prefill_host.append(admit["dur"] + call["dur"]
                                    + publish["dur"] - (f1 - f0))
    flights = _merged(flight_of.values(), lo, hi)

    # the window cut into slices of (from, to, innermost span open)
    slices = []

    def walk(span, a, b):
        name, t = name_of(span["name"]), a
        for k in kids.get(span["span_id"], ()):
            k0, k1 = max(k["ts"], t), min(_end(k), b)
            if k1 <= k0:
                continue
            if k0 > t:
                slices.append((t, k0, name))
            walk(k, k0, k1)
            t = k1
        if b > t:
            slices.append((t, b, name))

    t = lo
    for s in steps:
        s0, s1 = max(s["ts"], t), _end(s)
        if s1 <= s0:
            continue
        if s0 > t:
            slices.append((t, s0, "outside_step"))
        walk(s, s0, s1)
        t = s1

    # what of each slice no in-flight interval covers
    by_span, i = {}, 0
    for a, b, name in slices:
        starved = b - a
        while i < len(flights) and flights[i][1] <= a:
            i += 1
        j = i
        while j < len(flights) and flights[j][0] < b:
            starved -= min(flights[j][1], b) - max(flights[j][0], a)
            j += 1
        if starved:
            by_span[name] = by_span.get(name, 0) + starved
    by_group = dict.fromkeys(GROUPS, 0)
    for name, ns in by_span.items():
        group = name if name == "outside_step" else GROUP_OF.get(name, "sched")
        by_group[group] += ns
    return {"window_ns": (lo, hi), "steps": len(steps),
            "in_flight_ns": sum(b - a for a, b in flights),
            "starved_ns": by_group, "starved_by_span": by_span,
            "prefill_host_ns": prefill_host}


def read(record):
    """The measured window's split, or None."""
    global _cache
    log, window = program_spans._log(), program_spans.window_ns(record)
    if log is None or window is None:
        return None
    key = (id(record), log.appended)
    if _cache[0] != key:
        # as program_spans.read: a step that began in the window may close
        # a little after it, and its children with it
        spans = log.window(window[0], window[1] + 60 * 10**9)
        _cache = (key, None if spans is None else split(spans, *window))
    return _cache[1]


def starved_pct(record):
    """100 x starved time over the window."""
    got = read(record)
    if not got:
        return None
    lo, hi = got["window_ns"]
    return 100.0 * sum(got["starved_ns"].values()) / (hi - lo)


def starved_ms_per_step(record, group):
    """A group's starved time in ms over the steps that began in the
    window."""
    got = read(record)
    return got["starved_ns"][group] / got["steps"] / 1e6 if got else None


def prefill_host_ms_p50(record):
    """Median over the window's prefills of what the host adds to one:
    `prefill.admit` + `serving::prefill` + `prefill.publish` minus the
    call's in-flight interval."""
    got = read(record)
    host = got["prefill_host_ns"] if got else None
    return statistics.median(host) / 1e6 if host else None
