#!/usr/bin/env python
"""Where a serving trace's device idle time went, by what the host was doing.

    python tools/serve_gaps.py <trace dir or .xplane.pb>

Reads a `jax.profiler` trace of a few `Scheduler.step()` calls (the
benchmark's `--keep-trace DIR`, or any `jax.profiler.start_trace` session
around a warm loop) with `jax.profiler.ProfileData` only. Every
`serving::*` span is a `TraceAnnotation` on `/host:CPU`, on the clock of the
device planes (docs/observability.md §12), so each idle gap of the device's
`XLA Ops` line can be charged to the INNERMOST `serving::*` span open in it
(else the innermost `bench:*` span of the benchmark's loop, else `none`).

Printed, over the window of the whole `serving::step`s the trace holds:

- the device's idle time by group (`decode_call`, `prefill_call`, `sched`,
  `outside_step`: the levers of ROADMAP A4) and by span, beside the split the
  spans' own stamps give with no device trace: *in flight* from the start of
  `X.dispatch` to the end of `X.wait`, *starved* the rest, each starved
  stretch charged to the innermost span (what the benchmark's
  `host_starved_pct` and `starved_*_ms_per_step` read off the span log in
  the untraced window);
- what that lower bound leaves out, as medians per call: the launch (module
  start minus the START of `X.dispatch`) and the fetch tail (end of `X.wait`
  minus module end), and the device time outside every in-flight interval
  (the uploads' own small programs). The profiler's two planes agree to
  within a millisecond or so only: where a module begins BEFORE the start of
  its own dispatch, the device plane is first moved later by the least that
  restores the order (printed; the launch is then at least what is printed,
  the tail at most, and their sum needs no common clock);
- the longest `serving::step` of the trace with its phases.
"""
import bisect
import glob
import os
import statistics
import sys

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SERVING, BENCH = "serving::", "bench:"
STEP = SERVING + "step"
GROUPS = ("decode_call", "prefill_call", "sched", "outside_step")
GROUP_OF = {"decode.prepare": "decode_call", "decode_step": "decode_call",
            "decode.upload": "decode_call", "decode.commit": "decode_call",
            "prefill.admit": "prefill_call", "prefill": "prefill_call",
            "prefill.upload": "prefill_call",
            "prefill.publish": "prefill_call"}
CALLS = {SERVING + "decode_step": "decode", SERVING + "prefill": "prefill"}
IN_CALL = {f"{call}.{part}" for call in CALLS.values()
           for part in ("dispatch", "wait")}


def group_of(name):
    """The group a slice charged to span `name` falls in; `in_call` for a
    `dispatch` or a `wait`, which the stamps count as in flight."""
    if not name.startswith(SERVING):
        return "outside_step"
    short = name[len(SERVING):]
    return "in_call" if short in IN_CALL else GROUP_OF.get(short, "sched")


def find_xplane(path):
    if os.path.isfile(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def device_lines(profile):
    """([(start, end)] of `XLA Ops`, [(name, start, end)] of `XLA Modules`)
    of the first device plane."""
    for plane in sorted(profile.planes, key=lambda p: p.name):
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        ops = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
               for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
        modules = [(e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns))
                   for e in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        if ops:
            return ops, sorted(modules, key=lambda m: m[1])
    return [], []


def host_spans(profile):
    """[(name, start, end)] of the `serving::*` and `bench:*` spans of the
    host thread that ran the steps (the line with the most
    `serving::step`s), by start."""
    best, steps = [], -1
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith((SERVING, BENCH)):
                    start = float(e.start_ns)
                    spans.append((name, start,
                                  start + float(e.duration_ns)))
            n = sum(s[0] == STEP for s in spans)
            if n > steps:
                best, steps = spans, n
    return sorted(best, key=lambda s: (s[1], -s[2]))


def merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def cut(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def inverse(intervals, lo, hi):
    """What of [lo, hi) the sorted disjoint `intervals` leave."""
    out, t = [], lo
    for a, b in cut(intervals, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def slices_of(spans, lo, hi):
    """[lo, hi) cut into [(from, to, innermost span open or "none")] from
    spans that nest by time on one thread, sorted by (start, -end)."""
    out, stack, t = [], [], lo

    def upto(to, name):
        nonlocal t
        to = min(to, hi)
        if to > t:
            out.append((t, to, name))
            t = to

    for name, a, b in spans:
        if b <= lo or a >= hi:
            continue
        while stack and stack[-1][1] <= a:
            top = stack.pop()
            upto(top[1], top[0])
        upto(a, stack[-1][0] if stack else "none")
        stack.append((name, min(b, stack[-1][1]) if stack else b))
    while stack:
        top = stack.pop()
        upto(top[1], top[0])
    upto(hi, "none")
    return out


def charge(intervals, slices):
    """{span name: ns of the sorted disjoint `intervals` under its slices}."""
    out, i = {}, 0
    for a, b, name in slices:
        while i < len(intervals) and intervals[i][1] <= a:
            i += 1
        j = i
        while j < len(intervals) and intervals[j][0] < b:
            got = min(intervals[j][1], b) - max(intervals[j][0], a)
            if got > 0:
                out[name] = out.get(name, 0.0) + got
            j += 1
    return out


def calls_of(spans, modules):
    """One row per `serving::decode_step` / `serving::prefill`: the
    in-flight interval by the stamps (None where the call lacks its
    `dispatch` or its `wait`) and the module that ran in it (the one that
    overlaps it longest: the call's own executable; None where none does)."""
    starts = [s[1] for s in spans]
    rows, m = [], 0
    for name, a, b in spans:
        call = CALLS.get(name)
        if call is None:
            continue
        inside = spans[bisect.bisect_left(starts, a):
                       bisect.bisect_right(starts, b)]
        dispatch = [s for s in inside
                    if s[0] == f"{SERVING}{call}.dispatch" and s[2] <= b]
        wait = [s for s in inside
                if s[0] == f"{SERVING}{call}.wait" and s[2] <= b]
        if not dispatch or not wait:
            rows.append({"call": call, "span": (a, b), "in_flight": None,
                         "module": None})
            continue
        f0, f1 = dispatch[0][1], wait[-1][2]
        while m < len(modules) and modules[m][2] <= a:
            m += 1
        over = []
        for _, m0, m1 in modules[m:]:
            if m0 >= f1:
                break
            over.append((min(m1, f1) - max(m0, f0), m0, m1))
        rows.append({"call": call, "span": (a, b), "in_flight": (f0, f1),
                     "module": max(over)[1:] if over else None})
    return rows


def clock_shift(calls):
    """The least the device plane's clock must be moved later for no module
    to begin before the START of its own dispatch. The profiler puts the
    two planes on one clock to within a millisecond or so; a module that
    begins before it was enqueued says by how much at least they are apart.
    0.0 where causality already holds."""
    early = [c["in_flight"][0] - c["module"][0] for c in calls if c["module"]]
    return max([0.0] + early)


def report(profile):
    """The numbers `main` prints, or None where the trace holds no device
    operation or no whole `serving::step`."""
    ops, modules = device_lines(profile)
    spans = host_spans(profile)
    steps = [s for s in spans if s[0] == STEP]
    if not ops or not steps:
        return None
    lo, hi = steps[0][1], max(s[2] for s in steps)
    slices = slices_of(spans, lo, hi)
    calls = calls_of(spans, modules)
    shift = clock_shift(calls)
    busy = cut(merged((a + shift, b + shift) for a, b in ops), lo, hi)
    idle = inverse(busy, lo, hi)
    whole = all(c["in_flight"] for c in calls if lo <= c["span"][0] < hi)
    flights = cut(merged(c["in_flight"] for c in calls if c["in_flight"]),
                  lo, hi)
    starved = inverse(flights, lo, hi)

    def by_group(by_span):
        out = dict.fromkeys(GROUPS + ("in_call",), 0.0)
        for name, ns in by_span.items():
            out[group_of(name)] += ns
        return out

    def total(intervals, under):
        return sum(charge(intervals, [(a, b, "x") for a, b in under])
                   .values())

    idle_by_span = charge(idle, slices)
    starved_by_span = charge(starved, slices)
    longest = max(steps, key=lambda s: s[2] - s[1])
    ran = [c for c in calls if c["module"]]
    return {
        "window_ns": (lo, hi), "steps": len(steps), "clock_shift_ns": shift,
        "busy_ns": sum(b - a for a, b in busy),
        "idle_ns": sum(b - a for a, b in idle),
        "idle_by_span": idle_by_span, "idle_by_group": by_group(idle_by_span),
        # the stamps' split is whole only if every call has its children
        "in_flight_ns": sum(b - a for a, b in flights) if whole else None,
        "starved_by_span": starved_by_span if whole else None,
        "starved_by_group": by_group(starved_by_span) if whole else None,
        "busy_while_starved_ns": total(busy, starved) if whole else None,
        # on the shifted clock: at least 0, and a lower bound each; their
        # sum a call needs no common clock
        "launch_ns": {k: [c["module"][0] + shift - c["in_flight"][0]
                          for c in ran if c["call"] == k]
                      for k in CALLS.values()},
        "tail_ns": {k: [c["in_flight"][1] - c["module"][1] - shift
                        for c in ran if c["call"] == k]
                    for k in CALLS.values()},
        "longest_step": {"dur_ns": longest[2] - longest[1],
                         "at_ns": longest[1] - lo,
                         "phases": charge([longest[1:]], slices),
                         "idle_ns": total(idle, [longest[1:]])},
    }


def _ms(ns):
    return f"{ns / 1e6:9.3f}"


def render(got):
    lo, hi = got["window_ns"]
    steps, window = got["steps"], hi - lo
    lines = [
        f"window {window / 1e9:.4f} s of {steps} whole serving::step(s); "
        f"device busy {got['busy_ns'] / 1e9:.4f} s, idle "
        f"{got['idle_ns'] / 1e9:.4f} s = "
        f"{100 * got['idle_ns'] / window:.2f} %",
        f"device plane moved {got['clock_shift_ns'] / 1e6:.3f} ms later: the "
        f"least that lets no module begin before the start of its dispatch"]
    whole = got["in_flight_ns"] is not None
    if whole:
        starved = window - got["in_flight_ns"]
        lines.append(
            f"by the spans' own stamps: in flight "
            f"{got['in_flight_ns'] / 1e9:.4f} s, starved "
            f"{starved / 1e9:.4f} s = {100 * starved / window:.2f} % (a "
            f"lower bound on the idle share); device busy while starved "
            f"{got['busy_while_starved_ns'] / 1e6:.3f} ms")
    else:
        lines.append("by the spans' own stamps: a serving::prefill or "
                     "serving::decode_step lacks its dispatch / wait "
                     "children: no in-flight split")
    lines += ["", "ms a step        device idle    starved (stamps)"]
    for group in GROUPS:
        stamp = _ms(got["starved_by_group"][group] / steps) if whole else \
            "        -"
        lines.append(f"{group:<14} {_ms(got['idle_by_group'][group] / steps)}"
                     f"       {stamp}")
    lines.append(f"{'dispatch, wait':<14} "
                 f"{_ms(got['idle_by_group']['in_call'] / steps)}"
                 f"       {'(in flight)':>9}")
    lines += ["", "by innermost span, ms a step: device idle | starved"]
    names = sorted(got["idle_by_span"],
                   key=lambda n: -got["idle_by_span"][n])
    for name in names:
        stamp = _ms((got["starved_by_span"] or {}).get(name, 0.0) / steps) \
            if whole else "        -"
        lines.append(f"  {name:<28} {_ms(got['idle_by_span'][name] / steps)}"
                     f" | {stamp}")
    lines.append("")
    for call in CALLS.values():
        launch, tail = got["launch_ns"][call], got["tail_ns"][call]
        if launch:
            both = [a + b for a, b in zip(launch, tail)]
            lines.append(
                f"{call}: {len(launch)} calls; launch (module start - "
                f"start of {call}.dispatch) median "
                f"{statistics.median(launch) / 1e6:.3f} ms; fetch tail "
                f"(end of {call}.wait - module end) median "
                f"{statistics.median(tail) / 1e6:.3f} ms; the two together "
                f"(no common clock needed) "
                f"{statistics.median(both) / 1e6:.3f} ms")
    step = got["longest_step"]
    lines += ["", f"longest serving::step: {step['dur_ns'] / 1e6:.3f} ms, "
                  f"{step['at_ns'] / 1e6:.1f} ms into the window, device "
                  f"idle {step['idle_ns'] / 1e6:.3f} ms of it; self time "
                  f"by span:"]
    for name, ns in sorted(step["phases"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  {name:<28} {_ms(ns)}")
    return "\n".join(lines)


def main(argv):
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    from jax.profiler import ProfileData
    got = report(ProfileData.from_file(find_xplane(argv[0])))
    if got is None:
        print("serve_gaps: the trace holds no device operation or no whole "
              "serving::step", file=sys.stderr)
        return 1
    print(render(got))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
