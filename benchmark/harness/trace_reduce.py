"""From a profiler trace to numbers: device busy and idle time, time by
operation, the longest idle gaps and what the host was doing in them.

Reads the `.xplane.pb` the JAX profiler writes with nothing but
`jax.profiler.ProfileData`. What a TPU trace looks like (looked at by hand,
PR 25): one plane per chip named `/device:TPU:<n>`; its line `XLA Ops` holds
one event per executed HLO operation (nested: a `while` spans the operations
of its body), `XLA Modules` one per executed program, `Steps` one per step.
Host threads are lines of the plane `/host:CPU`; `TraceAnnotation`s of the
benchmark appear there by name.
"""
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def events_of(profile, plane_prefix=DEVICE_PLANE, line_name=OPS_LINE):
    """{plane name: [(name, start_ns, end_ns)]} of one line of each plane
    whose name starts with `plane_prefix`."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name != line_name:
                continue
            evs = out.setdefault(plane.name, [])
            for e in line.events:
                start = float(e.start_ns)
                evs.append((e.name, start, start + float(e.duration_ns)))
    return out


def host_spans(profile, prefix=SPAN_PREFIX):
    """[(name, start_ns, end_ns)] of the benchmark's own host spans."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    start = float(e.start_ns)
                    out.append((e.name[len(prefix):], start,
                                start + float(e.duration_ns)))
    return sorted(out, key=lambda s: s[1])


def union_intervals(intervals):
    """Merged [(start, end)] of possibly nested or overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def self_times(events):
    """{name: seconds} where a nesting event (a `while`, a call) is charged
    only the time none of its children cover: sums to the busy time."""
    out = {}
    stack = []                       # [name, end, child_time, start]
    def close(item):
        name, end, child, start = item
        out[name] = out.get(name, 0.0) + max(end - start - child, 0.0) / 1e9
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(end, stack[-1][1]) - start
        stack.append([name, end, 0.0, start])
    while stack:
        close(stack.pop())
    return out


_OPCODE = re.compile(r"[\s)]([a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
PALLAS = 'custom_call_target="tpu_custom_call"'


def short_name(name, width=96):
    """An XLA Ops event is named by its whole HLO instruction. For a
    breakdown a reader can take in: `%name opcode result-type`, layouts
    dropped, Pallas kernels marked."""
    if " = " not in name:
        return name[:width]
    instr, rest = name.split(" = ", 1)
    m = _OPCODE.search(rest)
    # a result type too long for the opcode to be in reach: the instruction's
    # own name says what it is ("%while.6")
    opcode = m.group(1) if m else instr.lstrip("%").split(".")[0]
    result = _LAYOUT.sub("", rest[:m.start() + 1] if m else "").strip()
    if PALLAS in name:
        opcode = "pallas"
    return f"{instr} {opcode} {result}"[:width]


def attribute_gap(gap, spans):
    """The host span that covers most of an idle gap, or "none"."""
    best, best_cover = "none", 0.0
    for name, start, end in spans:
        cover = min(end, gap[1]) - max(start, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def summarize(profile, n_devices=1, top=10):
    """The reduced trace: what every per-layer reader gets.

    window_s  first device operation's start to the last one's end
    busy_s    union of the device operations' intervals, averaged over chips
    op_s      {operation name: self seconds} summed over chips
    module_s  {program name: [seconds of each execution]} (`XLA Modules`,
              the name without its fingerprint: `jit__decode_fn`)
    gaps      the longest idle gaps, by what the host was doing in them
    """
    per_plane = events_of(profile)
    if not per_plane:
        return None
    planes = sorted(per_plane)[:n_devices]
    spans = host_spans(profile)
    start = min(e[1] for p in planes for e in per_plane[p])
    end = max(e[2] for p in planes for e in per_plane[p])
    busy, op_s, gap_by = [], {}, {}
    for p in planes:
        merged = union_intervals([(s, e) for _, s, e in per_plane[p]])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, sec in self_times(per_plane[p]).items():
            op_s[name] = op_s.get(name, 0.0) + sec
        for (_, a), (b, _) in zip(merged, merged[1:]):
            who = attribute_gap((a, b), spans)
            gap_by[who] = gap_by.get(who, 0.0) + (b - a) / 1e9
    module_s = {}
    modules = events_of(profile, line_name=MODULES_LINE)
    for p in planes:
        for name, a, b in modules.get(p, []):
            module_s.setdefault(name.split("(")[0], []).append((b - a) / 1e9)
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {"window_s": (end - start) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "n_devices": len(planes),
            "op_s": op_s,
            "module_s": module_s,
            "device_ops": [[short_name(k), v] for k, v in ranked[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                gap_by.items(), key=lambda kv: -kv[1])[:top]],
            "events": sum(len(per_plane[p]) for p in planes)}


def time_of(summary, *needles):
    """Seconds of the operations whose name holds any of `needles`; None
    where no such operation ran."""
    hit = [v for k, v in summary["op_s"].items()
           if any(n in k for n in needles)]
    return sum(hit) if hit else None
