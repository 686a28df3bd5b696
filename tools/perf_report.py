"""Render a perf attribution report from saved step-timeline artifacts.

The profiler's JSONL step timeline (paddle_tpu.profiler Profiler(timeline=…))
is the durable perf evidence: one record per train
step with phase durations, a per-op digest, eager-cache stats, and the
memory peak. This tool re-renders the attribution report from those files
alone — no live backend needed — so a run's decomposition survives the TPU
grant that produced it.

Usage:
  python tools/perf_report.py RUN.jsonl [--compare OTHER.jsonl] [--top 10]
  python tools/perf_report.py DIR          # uses DIR/step_timeline.jsonl

Schema validation is exported as `validate_record` / `load_timeline` so the
CI smoke test can assert the pipeline never rots.
"""
import argparse
import json
import os
import sys

SCHEMA = "paddle_tpu.step_timeline.v1"

# field -> (types, required)
_FIELDS = {
    "schema": (str, True),
    "step": (int, True),
    "step_ms": ((int, float, type(None)), True),
    "phases": (dict, True),
    "ops": (list, True),
    "num_samples": ((int, float, type(None)), False),
    "cache": (dict, False),
    "mem_peak_bytes": ((int, type(None)), False),
}
_OP_FIELDS = ("name", "calls", "total_ms")


def validate_record(rec):
    """Return a list of schema violations ([] == valid)."""
    errs = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    if rec.get("schema") != SCHEMA:
        errs.append(f"schema={rec.get('schema')!r}, want {SCHEMA!r}")
    for field, (types, required) in _FIELDS.items():
        if field not in rec:
            if required:
                errs.append(f"missing field {field!r}")
            continue
        if not isinstance(rec[field], types):
            errs.append(f"{field}={rec[field]!r} has type "
                        f"{type(rec[field]).__name__}")
    for ph, ms in (rec.get("phases") or {}).items():
        if not isinstance(ms, (int, float)) or ms < 0:
            errs.append(f"phase {ph!r} duration {ms!r} invalid")
    for op in rec.get("ops") or []:
        missing = [k for k in _OP_FIELDS if k not in op]
        if missing:
            errs.append(f"op row {op!r} missing {missing}")
    return errs


def load_timeline(path):
    """Parse + validate a JSONL timeline; raises ValueError on any invalid
    record (the CI guard against pipeline rot)."""
    if os.path.isdir(path):
        path = os.path.join(path, "step_timeline.jsonl")
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}") from None
            errs = validate_record(rec)
            if errs:
                raise ValueError(f"{path}:{i + 1}: " + "; ".join(errs))
            records.append(rec)
    if not records:
        raise ValueError(f"{path}: empty timeline")
    return records


# ------------------------------------------------------------- aggregation

def _agg(records):
    steps = [r for r in records if r.get("step_ms") is not None]
    step_ms = sorted(r["step_ms"] for r in steps)
    phases = {}
    for r in steps:
        for ph, ms in r["phases"].items():
            phases.setdefault(ph, []).append(ms)
    ops = {}
    for r in records:
        for op in r["ops"]:
            key = (op["name"], op.get("shapes", ""))
            b = ops.setdefault(key, {"name": op["name"],
                                     "shapes": op.get("shapes", ""),
                                     "calls": 0, "total_ms": 0.0,
                                     "cache_hits": 0, "cache_misses": 0})
            b["calls"] += op["calls"]
            b["total_ms"] += op["total_ms"]
            b["cache_hits"] += op.get("cache_hits", 0)
            b["cache_misses"] += op.get("cache_misses", 0)
    cache = {"hits": 0, "misses": 0, "bypass": 0}
    for r in records:
        for k in cache:
            cache[k] += (r.get("cache") or {}).get(k, 0)
    mem = [r["mem_peak_bytes"] for r in records
           if r.get("mem_peak_bytes") is not None]
    return {
        "n_steps": len(steps),
        "avg_step_ms": sum(step_ms) / len(step_ms) if step_ms else None,
        "p50_step_ms": step_ms[len(step_ms) // 2] if step_ms else None,
        "phases_avg_ms": {ph: sum(v) / len(v) for ph, v in phases.items()},
        "ops": sorted(ops.values(), key=lambda b: -b["total_ms"]),
        "cache": cache,
        "mem_peak_bytes": max(mem) if mem else None,
    }


def _fmt_ms(v):
    return "-" if v is None else f"{v:.3f}"


def render(records, top=10, title="perf report"):
    a = _agg(records)
    lines = [f"# {title}", "",
             f"steps: {a['n_steps']}  ·  avg step "
             f"{_fmt_ms(a['avg_step_ms'])} ms  ·  p50 "
             f"{_fmt_ms(a['p50_step_ms'])} ms"]
    if a["mem_peak_bytes"] is not None:
        lines.append(f"live-memory peak: {a['mem_peak_bytes'] / 1e6:.2f} MB")
    c = a["cache"]
    disp = c["hits"] + c["misses"]
    if disp:
        lines.append(f"eager-cache: {c['hits']}/{disp} hits "
                     f"({100.0 * c['hits'] / disp:.1f}%), "
                     f"{c['bypass']} bypassed")
    if a["phases_avg_ms"]:
        lines += ["", "## phase breakdown (avg ms/step)", "",
                  "| phase | avg ms | % of step |", "|---|---|---|"]
        denom = a["avg_step_ms"] or \
            sum(a["phases_avg_ms"].values()) or 1.0
        for ph, ms in sorted(a["phases_avg_ms"].items(),
                             key=lambda kv: -kv[1]):
            lines.append(f"| {ph} | {ms:.3f} | {100.0 * ms / denom:.1f}% |")
    if a["ops"]:
        lines += ["", f"## top ops (host span time, top {top})", "",
                  "| op | shapes | calls | total ms | cache |",
                  "|---|---|---|---|---|"]
        for b in a["ops"][:top]:
            hits = b["cache_hits"] + b["cache_misses"]
            cache = f"{b['cache_hits']}/{hits}" if hits else "-"
            lines.append(f"| {b['name']} | {b['shapes'] or '-'} | "
                         f"{b['calls']} | {b['total_ms']:.3f} | {cache} |")
    return "\n".join(lines)


def render_compare(a_recs, b_recs, a_name, b_name):
    a, b = _agg(a_recs), _agg(b_recs)
    lines = [f"# comparison: {a_name} vs {b_name}", "",
             "| metric | A | B | delta |", "|---|---|---|---|"]

    def row(name, va, vb, fmt=_fmt_ms):
        delta = "-"
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)) \
                and va:
            delta = f"{100.0 * (vb - va) / va:+.1f}%"
        lines.append(f"| {name} | {fmt(va)} | {fmt(vb)} | {delta} |")

    row("avg step ms", a["avg_step_ms"], b["avg_step_ms"])
    row("p50 step ms", a["p50_step_ms"], b["p50_step_ms"])
    for ph in sorted(set(a["phases_avg_ms"]) | set(b["phases_avg_ms"])):
        row(f"{ph} avg ms", a["phases_avg_ms"].get(ph),
            b["phases_avg_ms"].get(ph))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("run", help="step-timeline .jsonl (or its directory)")
    p.add_argument("--compare", default=None,
                   help="second timeline to diff against")
    p.add_argument("--top", type=int, default=10)
    args = p.parse_args(argv)
    records = load_timeline(args.run)
    if args.compare:
        other = load_timeline(args.compare)
        print(render_compare(records, other, args.run, args.compare))
    else:
        print(render(records, top=args.top, title=f"perf report: {args.run}"))
        # an attribution.md written beside the timeline (the profiler's
        # analyze().render()) rides along; point the reader at it rather
        # than re-deriving roofline joins here
        run_dir = args.run if os.path.isdir(args.run) \
            else os.path.dirname(args.run)
        attrib = os.path.join(run_dir, "attribution.md")
        if os.path.exists(attrib):
            print(f"\n(roofline attribution: {attrib})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
