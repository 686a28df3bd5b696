"""Device-profile closed loop: XPlane capture -> typed parse -> host join.

The stack so far is host-clock observability: PR 1's spans and PR 4's
metrics/trace substrate time *dispatches*, while the device half (the
CUPTI/XPlane role of the reference's cupti_data_process.cc) only existed
as a manual runbook step whose parser had never seen real output
(VERDICT weak #21). This module is the validated device half:

  capture   — `DeviceProfiler` context / one-shot `capture()` wrapping
              `jax.profiler.trace`. Works identically on the CPU
              backend, so tier-1 CI exercises the WHOLE pipeline against
              a real `.xplane.pb` (the XLA CPU runtime emits per-HLO-op
              events with `hlo_op`/`hlo_module` stat lanes, same as the
              TPU device planes).
  parse     — typed parser over the capture: plane/line normalization
              (the pick-one-line rule lifted out of xplane_summary.py
              and HARDENED — the old "largest total" fallback picks the
              python tracer lane on CPU captures, whose events include
              the multi-second trace context itself), per-op device-time
              aggregation, HLO-op -> framework-primitive attribution via
              the metadata/stat lanes. Output: one schema'd
              `paddle_tpu.deviceprof.v1` JSONL record.
  join      — aligns device op timings with host span boundaries (the
              capture's host window / the scheduler's decode-step wall
              times) and `cost_model/analytical.py` per-op predictions:
              measured-device-vs-predicted efficiency per op — PR 1's
              roofline attribution, now on device time — exported as
              `deviceprof_*` registry gauges and a bench `extra` block.
  orchestrate — `OneShotCapture`: an armed capture that fires once in a
              healthy window (bench.py --xplane). Every state transition
              is annotated into the flight recorder, so a run that wedges
              BEFORE the capture fires leaves "armed, never fired" in its
              postmortem instead of losing the evidence.

Decoder resolution: `jax.profiler.ProfileData` in a process that already
has jax (through `_jax_compat.profile_data`, found via sys.modules), else
the stdlib XSpace wire decoder (`xplane.py`). Parse/validate/render are
stdlib-only and standalone-loadable (importlib by file path) so the
offline tools never import the backend.
"""
import json
import os
import re
import sys
import time

__all__ = ["SCHEMA", "CaptureError", "DeviceProfiler", "OneShotCapture",
           "capture", "find_xplane", "parse_xplane", "join_cost_model",
           "validate_record", "write_record", "load_records",
           "render_record", "export_gauges", "device_planes", "pick_line"]

SCHEMA = "paddle_tpu.deviceprof.v1"


class CaptureError(RuntimeError):
    """The capture produced no parseable device profile (and why)."""


# --------------------------------------------------------------- decoding

def _xplane_mod():
    """The stdlib XSpace decoder, whether this module lives in the package
    or was standalone-loaded by an offline tool."""
    mod = sys.modules.get("paddle_tpu.observability.xplane")
    if mod is not None:
        return mod
    try:
        from . import xplane as mod
        return mod
    except ImportError:
        import importlib.util
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "xplane.py")
        spec = importlib.util.spec_from_file_location(
            "_deviceprof_xplane", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _load_planes(path):
    """(planes, decoder_name). Uses the typed jax binding when the
    process already has jax; falls back to the stdlib wire decoder.
    Never triggers a jax import itself: the offline tools run beside the
    process that holds the chip."""
    compat = sys.modules.get("paddle_tpu._jax_compat")
    native_err = None
    if compat is not None:
        try:
            return list(compat.profile_data()(path).planes), "native"
        except Exception as e:                               # noqa: BLE001
            # a *parse* failure from the native binding is worth retrying
            # with the wire decoder, but keep the reason if both fail
            native_err = e
    try:
        return list(_xplane_mod().XSpace.from_file(path).planes), "purepy"
    except Exception as e:                                   # noqa: BLE001
        msg = f"{path}: not a parseable XSpace: {e}"
        if native_err is not None:
            msg += f" (native ProfileData also failed: {native_err})"
        raise CaptureError(msg) from None


def find_xplane(root):
    """Newest .xplane.pb under a trace directory (jax writes
    plugins/profile/<ts>/<host>.xplane.pb)."""
    import glob
    cands = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if not cands:
        raise CaptureError(f"no .xplane.pb under {root} "
                           "(capture never ran, or trace dir is wrong)")
    return max(cands, key=os.path.getmtime)


# --------------------------------------- plane/line normalization (hardened)

def _event_stats(ev):
    s = getattr(ev, "stats", None)
    if isinstance(s, dict):
        return s
    if s is None:
        return {}
    try:
        return dict(s)
    except Exception:                                        # noqa: BLE001
        return {}


def _dur_ns(ev):
    try:
        return max(int(getattr(ev, "duration_ns", 0) or 0), 0)
    except Exception:                                        # noqa: BLE001
        return 0


def _occurrences(ev):
    try:
        return max(int(getattr(ev, "occurrences", 1) or 1), 1)
    except Exception:                                        # noqa: BLE001
        return 1


def _offset_ns(ev):
    """Event start within its line: our decoder spells it `offset_ns`,
    the native jax ProfileData binding spells it `start_ns` (absolute —
    fine, containment analysis only needs line-consistent values). A
    decoder exposing neither degrades _self_times to raw durations."""
    for attr in ("offset_ns", "start_ns"):
        v = getattr(ev, attr, None)
        if v is not None:
            try:
                return int(v)
            except (TypeError, ValueError):
                continue
    return 0


def _line_total_ns(line):
    return sum(_dur_ns(ev) for ev in line.events)


def _line_hlo_total_ns(line):
    return sum(_dur_ns(ev) for ev in line.events
               if "hlo_op" in _event_stats(ev))


def pick_lines(plane):
    """Normalize a device plane's lines to the lanes that may be SUMMED
    without multi-counting, returning [(line, rule), ...].

    TPU device planes carry PARALLEL hierarchy lines over the same
    nanoseconds (Steps / XLA Modules / XLA Ops / Framework Ops /
    Framework Name Scope) — summing across those multi-counts time, so
    exactly ONE is picked. CPU-backend planes instead carry a python
    tracer lane plus per-THREAD XLA runtime lanes whose events are
    disjoint work — dropping all but one understates device time.
    Rule, in order:

      1. a line literally named 'XLA Ops' (the TPU per-op lane; the
         other hierarchy lanes are views of the same nanoseconds),
      2. EVERY line whose events carry `hlo_op` stats (the CPU runtime
         thread lanes; this is what the old inline rule got wrong twice
         — its "largest total duration" fallback picks the PYTHON
         tracer lane, whose top event is the multi-second
         `profiler.trace` context itself, and keeping a single lane
         drops the executor threads running e.g. the optimizer while
         loop),
      3. the largest-total line (host-only traces; flagged by rule name).
    """
    lines = [ln for ln in plane.lines if _line_total_ns(ln) > 0]
    if not lines:
        return []
    for ln in lines:
        if (ln.name or "").strip().lower() == "xla ops":
            return [(ln, "xla_ops")]
    hlo = sorted((ln for ln in lines if _line_hlo_total_ns(ln) > 0),
                 key=_line_hlo_total_ns, reverse=True)
    if hlo:
        return [(ln, "hlo_stats") for ln in hlo]
    return [(max(lines, key=_line_total_ns), "largest_total")]


def pick_line(plane):
    """The PRIMARY normalized lane of a plane: (line, rule) — the
    largest lane pick_lines keeps, (None, None) when the plane has no
    timed events."""
    picked = pick_lines(plane)
    return picked[0] if picked else (None, None)


def device_planes(planes):
    """The planes that carry device-side execution. TPU/GPU captures name
    them explicitly; on the CPU backend the host plane IS the device
    plane — but only when it actually carries XLA op lanes (a host-only
    trace must fail loudly, not summarize the python tracer)."""
    planes = [p for p in planes if getattr(p, "lines", None)]

    def named_device(p):
        name = (p.name or "").lower()
        return "/device" in name or "tpu" in name or "gpu" in name

    dev = [p for p in planes if named_device(p)]
    if dev:
        return dev
    out = []
    for p in planes:
        if "cpu" not in (p.name or "").lower():
            continue
        line, rule = pick_line(p)
        if line is not None and rule in ("xla_ops", "hlo_stats"):
            out.append(p)
    return out


# ------------------------------------------- HLO -> framework attribution

# HLO opcode -> the jaxpr primitive name the analytical cost model prices
# (cost_model/analytical.py). Fusions stay None: one fused loop has no
# single-primitive attribution (its members are priced individually by
# the model's fusion heuristic).
_HLO_TO_PRIM = {
    "dot": "dot_general", "convolution": "conv_general_dilated",
    "add": "add", "subtract": "sub", "multiply": "mul", "divide": "div",
    "maximum": "max", "minimum": "min", "negate": "neg", "abs": "abs",
    "exponential": "exp", "log": "log", "tanh": "tanh",
    "logistic": "logistic", "rsqrt": "rsqrt", "sqrt": "sqrt",
    "power": "pow", "sign": "sign", "floor": "floor", "ceil": "ceil",
    "round-nearest-afz": "round", "cosine": "cos", "sine": "sin",
    "select": "select_n", "clamp": "clamp", "compare": "eq",
    "and": "and", "or": "or", "not": "not", "xor": "xor",
    "broadcast": "broadcast_in_dim", "transpose": "transpose",
    "reshape": "reshape", "convert": "convert_element_type",
    "bitcast-convert": "convert_element_type", "copy": "copy",
    "iota": "iota", "concatenate": "concatenate", "reverse": "rev",
    "pad": "pad", "slice": "slice", "gather": "gather",
    "scatter": "scatter", "dynamic-slice": "dynamic_slice",
    "dynamic-update-slice": "dynamic_update_slice",
    "reduce": "reduce", "reduce-window": "reduce_window",
    "sort": "sort", "while": "while", "conditional": "cond",
    "all-reduce": "psum", "all-gather": "all_gather",
    "reduce-scatter": "psum_scatter", "all-to-all": "all_to_all",
    "collective-permute": "ppermute", "rng-bit-generator": "random_bits",
    "cholesky": "cholesky", "triangular-solve": "triangular_solve",
}

_OP_SUFFIX = re.compile(r"(\.(?:\d+|clone|remat\d*))+$")


def hlo_base_name(name):
    """'%loop_fusion.3' -> 'loop_fusion'; 'dot.4' -> 'dot';
    'divide_subtract_fusion.5.clone' -> 'divide_subtract_fusion'."""
    return _OP_SUFFIX.sub("", (name or "").strip().lstrip("%")) or "?"


def hlo_to_prim(base):
    return _HLO_TO_PRIM.get(base)


def _self_times(events):
    """[(event, self_ns)]: each event's duration minus its DIRECT
    children's — the runtime lanes record container ops (`while`, `call`)
    whose span encloses every body op's span on the SAME line (measured:
    1161 of 1501 events nested on a real CPU train-step capture), so
    summing raw durations multi-counts the same nanoseconds. Self time
    is the chrome-trace/pprof model: a container keeps only its own
    scheduling overhead. Falls back to raw durations when the line
    carries no usable offsets (pre-aggregated captures)."""
    timed = [(_offset_ns(ev), _dur_ns(ev), ev) for ev in events]
    if len({t[0] for t in timed}) <= 1 and len(timed) > 1:
        return [(ev, dur) for _, dur, ev in timed]
    timed.sort(key=lambda t: (t[0], -t[1]))
    stack = []                       # [start, end, child_ns]
    out = []

    def close(top):
        out.append((top[3], max(top[1] - top[0] - top[2], 0)))

    for start, dur, ev in timed:
        end = start + dur
        while stack and start >= stack[-1][1]:
            close(stack.pop())
        if stack and end <= stack[-1][1]:
            stack[-1][2] += dur      # direct child: parent loses its span
        elif stack:
            # straddles the open parent's end: treat as a sibling
            while stack:
                close(stack.pop())
        stack.append([start, end, 0, ev])
    while stack:
        close(stack.pop())
    return out


def _aggregate(line, rule):
    """Per-op aggregation over ONE normalized line. For hlo-stat lanes,
    only events that carry an `hlo_op` stat count — the runtime lane also
    interleaves executor/threadpool wrapper events. Containers that nest
    over their body (`while`/`call`) contribute SELF time only."""
    ops = {}
    modules = {}
    n_events = 0
    picked = []
    for ev in line.events:
        if _dur_ns(ev) <= 0:
            continue
        if rule == "hlo_stats" and "hlo_op" not in _event_stats(ev):
            continue
        picked.append(ev)
    for ev, self_ns in _self_times(picked):
        if self_ns <= 0:
            continue
        stats = _event_stats(ev)
        n_events += _occurrences(ev)
        base = hlo_base_name(getattr(ev, "name", ""))
        row = ops.setdefault(base, {"op": base, "prim": hlo_to_prim(base),
                                    "calls": 0, "device_ns": 0,
                                    "_modules": {}})
        row["calls"] += _occurrences(ev)
        row["device_ns"] += self_ns
        module = stats.get("hlo_module")
        if isinstance(module, str) and module:
            row["_modules"][module] = row["_modules"].get(module, 0) \
                + self_ns
            modules[module] = modules.get(module, 0) + self_ns
    return ops, modules, n_events


def parse_xplane(path, top=None):
    """Parse one `.xplane.pb` into a `paddle_tpu.deviceprof.v1` record:
    normalized plane/line choice, per-op device time, HLO->primitive
    attribution. Raises CaptureError (with the reason) when the capture
    carries no timed device events — never a silent empty table."""
    path = os.path.abspath(path)
    planes, decoder = _load_planes(path)
    devs = device_planes(planes)
    if not devs:
        names = [p.name for p in planes]
        raise CaptureError(
            f"no device-side XLA events in {path} (planes: {names}; "
            "host-only trace? the capture must span real executions)")
    ops = {}
    modules = {}
    plane_rows = []
    n_events = 0
    for plane in devs:
        for line, rule in pick_lines(plane):
            p_ops, p_modules, p_n = _aggregate(line, rule)
            p_total = sum(r["device_ns"] for r in p_ops.values())
            if p_total <= 0:
                continue
            plane_rows.append({"plane": plane.name, "line": line.name,
                               "rule": rule,
                               "device_ms": round(p_total / 1e6, 6),
                               "n_events": p_n})
            n_events += p_n
            for base, row in p_ops.items():
                agg = ops.setdefault(base, {"op": base, "prim": row["prim"],
                                            "calls": 0, "device_ns": 0,
                                            "_modules": {}})
                agg["calls"] += row["calls"]
                agg["device_ns"] += row["device_ns"]
                for m, ns in row["_modules"].items():
                    agg["_modules"][m] = agg["_modules"].get(m, 0) + ns
            for m, ns in p_modules.items():
                modules[m] = modules.get(m, 0) + ns
    total_ns = sum(r["device_ns"] for r in ops.values())
    if total_ns <= 0:
        raise CaptureError(
            f"device planes present but no timed device events in {path} "
            f"(planes: {[r['plane'] for r in plane_rows]}; lines: "
            f"{[(r['line'], r['rule']) for r in plane_rows]})")
    rows = sorted(ops.values(), key=lambda r: -r["device_ns"])
    if top:
        rows = rows[:top]
    out_ops = []
    for r in rows:
        mods = r.pop("_modules")
        main_mod = max(mods, key=mods.get) if mods else None
        out_ops.append({"op": r["op"], "prim": r["prim"],
                        "calls": int(r["calls"]),
                        "device_ms": round(r["device_ns"] / 1e6, 6),
                        "frac": round(r["device_ns"] / total_ns, 6),
                        "hlo_module": main_mod})
    def _uniq(values):
        seen = []
        for v in values:
            if v not in seen:
                seen.append(v)
        return ";".join(seen)

    return {
        "schema": SCHEMA, "ts": time.time(), "pid": os.getpid(),
        "xplane": path, "decoder": decoder,
        "plane": _uniq(r["plane"] for r in plane_rows),
        "line": _uniq(r["line"] for r in plane_rows),
        "line_rule": _uniq(r["rule"] for r in plane_rows),
        "planes": plane_rows,
        "total_device_ms": round(total_ns / 1e6, 6),
        "n_events": int(n_events),
        "modules": {m: round(ns / 1e6, 6) for m, ns in sorted(
            modules.items(), key=lambda kv: -kv[1])},
        "ops": out_ops,
    }


# -------------------------------------------------------------- the join

def _pred_value(v):
    if isinstance(v, dict):
        v = v.get("predicted_ms")
    return None if v is None else float(v)


def _predicted_ms(prim, per_op):
    """Predicted roofline ms for one measured op: exact primitive match,
    with the `reduce` HLO opcode joining the sum of the model's reduce_*
    family (XLA collapses all reduce kinds into one opcode)."""
    if not prim or not per_op:
        return None
    if prim in per_op:
        return _pred_value(per_op[prim])
    if prim == "reduce":
        vals = [_pred_value(v) for k, v in per_op.items()
                if k.startswith("reduce_")]
        vals = [v for v in vals if v is not None]
        return sum(vals) if vals else None
    return None


def join_cost_model(record, per_op_predicted=None, steps=1,
                    host_window_ms=None, wall_step_ms=None):
    """Attach the join block: device time per step vs the host wall
    window it was captured in (reconciliation: device <= wall) and
    per-op measured-vs-predicted efficiency against the analytical
    roofline (`bench` passes `cost_model['per_op']`). Mutates and
    returns `record`."""
    steps = max(int(steps), 1)
    if host_window_ms is None:
        host_window_ms = record.get("host_window_ms")
    total = float(record["total_device_ms"])
    dev_per_step = total / steps
    wall = wall_step_ms if wall_step_ms is not None else (
        host_window_ms / steps if host_window_ms else None)
    ratio = (dev_per_step / wall) if wall else None
    rows = []
    joined_ms = 0.0
    for op in record["ops"]:
        measured = op["device_ms"] / steps
        pred = _predicted_ms(op.get("prim"), per_op_predicted)
        eff = (pred / measured) if (pred is not None and measured > 0) \
            else None
        if pred is not None:
            joined_ms += op["device_ms"]
        rows.append({"op": op["op"], "prim": op.get("prim"),
                     "measured_ms_per_step": round(measured, 6),
                     "predicted_ms": None if pred is None
                     else round(pred, 6),
                     "efficiency": None if eff is None else round(eff, 6),
                     "device_frac": op["frac"]})
    record["join"] = {
        "steps": steps,
        "host_window_ms": None if host_window_ms is None
        else round(float(host_window_ms), 4),
        "wall_ms_per_step": None if wall is None else round(float(wall), 6),
        "device_ms_per_step": round(dev_per_step, 6),
        "device_wall_ratio": None if ratio is None else round(ratio, 6),
        "reconciles": bool(ratio is not None and ratio <= 1.0),
        "coverage": round(joined_ms / total, 6) if total else 0.0,
        "per_op": rows,
    }
    return record


# ---------------------------------------------------------------- schema

_OP_FIELDS = {"op": str, "calls": int, "device_ms": (int, float),
              "frac": (int, float)}
_JOIN_FIELDS = {"steps": int, "device_ms_per_step": (int, float),
                "reconciles": bool, "coverage": (int, float),
                "per_op": list}
_JOIN_OP_FIELDS = ("op", "measured_ms_per_step", "predicted_ms",
                   "efficiency")


def validate_record(rec):
    """Return a list of schema violations ([] == valid)."""
    errs = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    if rec.get("schema") != SCHEMA:
        errs.append(f"schema={rec.get('schema')!r}, want {SCHEMA!r}")
    for field in ("xplane", "decoder", "plane", "line", "line_rule"):
        if not isinstance(rec.get(field), str) or not rec.get(field):
            errs.append(f"{field}={rec.get(field)!r} invalid")
    if not isinstance(rec.get("total_device_ms"), (int, float)) \
            or rec.get("total_device_ms", -1) < 0:
        errs.append(f"total_device_ms={rec.get('total_device_ms')!r} invalid")
    if not isinstance(rec.get("n_events"), int) or rec.get("n_events", -1) < 0:
        errs.append(f"n_events={rec.get('n_events')!r} invalid")
    if not isinstance(rec.get("ops"), list) or not rec.get("ops"):
        errs.append("ops missing or empty")
    for op in rec.get("ops") or []:
        if not isinstance(op, dict):
            errs.append(f"op row {op!r} not a dict")
            continue
        for k, types in _OP_FIELDS.items():
            if not isinstance(op.get(k), types):
                errs.append(f"op {op.get('op')!r}: {k}={op.get(k)!r} invalid")
        if isinstance(op.get("frac"), (int, float)) \
                and not 0 <= op["frac"] <= 1.000001:
            errs.append(f"op {op.get('op')!r}: frac {op['frac']} out of "
                        "[0,1]")
    join = rec.get("join")
    if join is not None:
        if not isinstance(join, dict):
            errs.append(f"join={join!r} not a dict")
        else:
            for k, types in _JOIN_FIELDS.items():
                if not isinstance(join.get(k), types):
                    errs.append(f"join.{k}={join.get(k)!r} invalid")
            for row in join.get("per_op") or []:
                missing = [k for k in _JOIN_OP_FIELDS if k not in row]
                if missing:
                    errs.append(f"join row {row!r} missing {missing}")
    return errs


def write_record(rec, path):
    """Validate + append one record to a deviceprof JSONL stream."""
    errs = validate_record(rec)
    if errs:
        raise ValueError(f"invalid {SCHEMA} record: " + "; ".join(errs))
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return path


def load_records(path):
    """Parse + validate a deviceprof JSONL; ValueError on any rot."""
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
        raise ValueError(f"{path}: empty deviceprof stream")
    return records


# -------------------------------------------------------------- rendering

def _fmt(v, spec=".3f"):
    return "-" if v is None else format(v, spec)


def render_record(rec, top=20):
    """Markdown: the per-op device-time table plus (when joined) the
    measured-vs-predicted efficiency table."""
    lines = [f"## device profile: {rec['plane']} — "
             f"{rec['total_device_ms']:.3f} ms total device time",
             f"(decoder {rec['decoder']}, line {rec['line']!r}, "
             f"rule {rec['line_rule']}, {rec['n_events']} events)", "",
             "| op | prim | calls | ms | % |", "|---|---|---|---|---|"]
    total = rec["total_device_ms"] or 1.0
    for op in rec["ops"][:top]:
        lines.append(
            f"| {op['op'][:60]} | {op.get('prim') or '-'} | {op['calls']} | "
            f"{op['device_ms']:.3f} | {100 * op['device_ms'] / total:.1f} |")
    join = rec.get("join")
    if join:
        ratio = join.get("device_wall_ratio")
        lines += ["", f"### join over {join['steps']} step(s): device "
                  f"{join['device_ms_per_step']:.3f} ms/step vs wall "
                  f"{_fmt(join.get('wall_ms_per_step'))} ms/step "
                  f"(ratio {_fmt(ratio)}, "
                  f"{'reconciles' if join['reconciles'] else 'DOES NOT reconcile'})",
                  "",
                  "| op | measured ms/step | predicted ms | efficiency | "
                  "% device |", "|---|---|---|---|---|"]
        for row in join["per_op"][:top]:
            lines.append(
                f"| {row['op'][:60]} | {row['measured_ms_per_step']:.4f} | "
                f"{_fmt(row['predicted_ms'], '.4f')} | "
                f"{_fmt(row['efficiency'])} | "
                f"{100 * row['device_frac']:.1f} |")
        lines.append("")
        lines.append(f"predicted-row coverage of device time: "
                     f"{100 * join['coverage']:.1f}%")
    return "\n".join(lines)


# ----------------------------------------------------------------- gauges

def export_gauges(record):
    """Publish the joined capture as `deviceprof_*` registry gauges — the
    families tools/metrics_report.py --compare gates as failure classes
    (total device ms/step GROWTH, per-op efficiency DROP)."""
    try:
        from . import metrics
    except ImportError:                     # standalone tool load: no-op
        return
    join = record.get("join") or {}
    if join.get("device_ms_per_step") is not None:
        metrics.gauge(
            "deviceprof_total_device_ms_per_step",
            "Device-side op time per step from the last XPlane capture "
            "(growth past the --compare threshold is failure-class)"
        ).set(join["device_ms_per_step"])
    if join.get("device_wall_ratio") is not None:
        metrics.gauge(
            "deviceprof_device_wall_ratio",
            "Device op time / host wall window of the capture (<=1.0 "
            "reconciles)").set(join["device_wall_ratio"])
    if join.get("coverage") is not None:
        metrics.gauge(
            "deviceprof_join_coverage",
            "Fraction of captured device time carrying a cost-model "
            "predicted row").set(join["coverage"])
    effs = []
    eff_gauge = metrics.gauge(
        "deviceprof_op_efficiency",
        "Per-op predicted-roofline / measured-device time from the last "
        "capture (a drop past the --compare threshold is failure-class)",
        labelnames=("op",))
    for row in join.get("per_op") or []:
        if row.get("efficiency") is not None:
            eff_gauge.labels(op=row["op"]).set(row["efficiency"])
            effs.append(row["efficiency"])
    if effs:
        metrics.gauge(
            "deviceprof_min_op_efficiency",
            "Worst per-op device efficiency among joined ops (drop = "
            "failure-class)").set(min(effs))


# ---------------------------------------------------------------- capture

def _fr_annotate(label, value):
    """Record capture state in the flight recorder, so a postmortem of a
    wedged run carries the armed/in-flight capture instead of losing it.
    Best-effort: the capture must not depend on the recorder."""
    fr = sys.modules.get("paddle_tpu.observability.flight_recorder")
    if fr is None:
        try:
            from . import flight_recorder as fr
        except Exception:                                    # noqa: BLE001
            return
    try:
        fr.get().annotate(f"deviceprof.{label}", value)
    except Exception:                                        # noqa: BLE001
        pass


def _glob_xplanes(root):
    import glob
    return set(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                         recursive=True))


class DeviceProfiler:
    """Context manager over `jax.profiler.trace`: capture the device
    timeline of the enclosed executions into `out_dir`, then `parse()`
    the fresh `.xplane.pb`. Works identically on the CPU backend (the
    XLA CPU runtime emits per-HLO-op events), which is what lets tier-1
    CI validate the whole pipeline against real output.

    The caller must SYNC the enclosed work before exiting (a host fetch
    / block_until_ready), or the device half of the last dispatch lands
    outside the window."""

    def __init__(self, out_dir, label="deviceprof"):
        self.out_dir = os.path.abspath(out_dir)
        self.label = label
        self.xplane_path = None
        self.host_window_ms = None
        self._pre = set()
        self._t0 = None

    def __enter__(self):
        import jax
        os.makedirs(self.out_dir, exist_ok=True)
        self._pre = _glob_xplanes(self.out_dir)
        _fr_annotate(self.label, {"state": "capturing",
                                  "dir": self.out_dir})
        try:
            jax.profiler.start_trace(self.out_dir)
        except Exception as e:                               # noqa: BLE001
            _fr_annotate(self.label, {"state": "failed",
                                      "dir": self.out_dir,
                                      "error": str(e)[:300]})
            raise CaptureError(
                f"device trace failed to start ({e}); is another capture "
                "already active?") from e
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as e:                               # noqa: BLE001
            _fr_annotate(self.label, {"state": "failed",
                                      "dir": self.out_dir,
                                      "error": str(e)[:300]})
            if exc_type is None:
                raise CaptureError(f"device trace failed to stop: {e}") \
                    from e
            return False
        self.host_window_ms = 1000.0 * (t1 - self._t0)
        if exc_type is not None:
            _fr_annotate(self.label, {"state": "failed",
                                      "dir": self.out_dir,
                                      "error": f"{exc_type.__name__}: "
                                               f"{str(exc)[:200]}"})
            return False
        fresh = _glob_xplanes(self.out_dir) - self._pre
        if not fresh:
            _fr_annotate(self.label, {"state": "failed",
                                      "dir": self.out_dir,
                                      "error": "no .xplane.pb written"})
            raise CaptureError(
                f"capture wrote no .xplane.pb under {self.out_dir}")
        self.xplane_path = max(fresh, key=os.path.getmtime)
        _fr_annotate(self.label, {"state": "captured",
                                  "dir": self.out_dir,
                                  "xplane": self.xplane_path})
        return False

    def parse(self, top=None):
        if self.xplane_path is None:
            raise CaptureError("nothing captured yet (use as a context "
                               "manager around real executions)")
        rec = parse_xplane(self.xplane_path, top=top)
        rec["host_window_ms"] = round(self.host_window_ms, 4)
        return rec


def capture(fn, out_dir, iters=1, label="deviceprof", top=None):
    """One-shot capture: run `fn()` `iters` times under a device trace
    (final result synced before the window closes) and return
    (last_result, parsed deviceprof record)."""
    import jax
    out = None
    with DeviceProfiler(out_dir, label=label) as dp:
        for _ in range(iters):
            out = fn()
        if out is not None:
            jax.block_until_ready(out)
    return out, dp.parse(top=top)


# ----------------------------------------------- one-shot orchestration

class OneShotCapture:
    """An ARMED capture that fires at most once, in a healthy window the
    caller picks (bench: past warmup with the watchdog quiet; serving:
    after a successful decode step). States:

        armed -> capturing -> captured -> reported
                    `-> failed (reason kept)      `-> failed

    Every transition lands in the flight recorder's annotations, so a
    run that wedges with the capture still armed (or mid-flight) leaves
    that fact in its postmortem — the acceptance rule of ISSUE 9: an
    armed-but-unfired capture is evidence, not silence."""

    def __init__(self, out_dir, label="capture"):
        self.out_dir = os.path.abspath(out_dir)
        self.label = label
        self.state = "armed"
        self.error = None
        self.record = None
        self.profiler = None
        self._annotate()

    def _annotate(self):
        note = {"state": self.state, "dir": self.out_dir}
        if self.error:
            note["error"] = self.error
        _fr_annotate(self.label, note)

    @property
    def armed(self):
        return self.state == "armed"

    @property
    def captured(self):
        return self.state == "captured"

    def start(self):
        """Open the device trace window (once). False if not armed or the
        trace cannot start — never raises into the caller's hot loop."""
        if self.state != "armed":
            return False
        try:
            self.profiler = DeviceProfiler(self.out_dir, label=self.label)
            self.profiler.__enter__()
        except Exception as e:                               # noqa: BLE001
            self.state, self.error = "failed", str(e)[:300]
            self._annotate()
            return False
        self.state = "capturing"
        self._annotate()
        return True

    def stop(self):
        """Close the window. The caller synced the captured work first."""
        if self.state != "capturing":
            return False
        try:
            self.profiler.__exit__(None, None, None)
        except Exception as e:                               # noqa: BLE001
            self.state, self.error = "failed", str(e)[:300]
            self._annotate()
            return False
        self.state = "captured"
        self._annotate()
        return True

    def abort(self, why):
        """The captured work itself failed (e.g. an OOM on a ladder
        rung): close the trace window so it cannot poison later work,
        and record why. Safe in any state."""
        if self.state == "capturing" and self.profiler is not None:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:                                # noqa: BLE001
                pass
        if self.state in ("armed", "capturing"):
            self.state = "failed"
            self.error = str(why)[:300]
            self._annotate()

    def finalize(self, cost_model_per_op=None, steps=1, wall_step_ms=None,
                 top=8, aborted_by=None):
        """Parse + join + write the artifact set (deviceprof.jsonl +
        deviceprof_join.md next to the raw trace) + export the
        deviceprof_* gauges. Returns the summary block for a bench
        `extra`; on failure returns {"state": "failed", "error": ...}
        instead of raising — the capture is evidence, not a dependency.

        `aborted_by`: the window closed early because the captured work
        failed. The parse/join artifacts are still written (evidence of
        the sick window, marked `aborted_by` in the persisted record),
        but the deviceprof_* gauges are NOT exported — --compare must
        never gate regression thresholds against a known-sick window."""
        if self.state != "captured":
            out = {"state": self.state}
            if self.error:
                out["error"] = self.error
            return out
        try:
            rec = self.profiler.parse()
            join_cost_model(rec, cost_model_per_op, steps=steps,
                            wall_step_ms=wall_step_ms)
            if aborted_by:
                rec["aborted_by"] = str(aborted_by)[:300]
            jsonl = os.path.join(self.out_dir, "deviceprof.jsonl")
            write_record(rec, jsonl)
            report = os.path.join(self.out_dir, "deviceprof_join.md")
            with open(report, "w") as f:
                f.write(render_record(rec) + "\n")
            if not aborted_by:
                export_gauges(rec)
            self.record = rec
            self.state = "reported"
            self._annotate()
            join = rec["join"]
            return {"state": "reported",
                    **({"aborted_by": rec["aborted_by"]} if aborted_by
                       else {}),
                    "xplane": rec["xplane"], "jsonl": jsonl,
                    "report": report, "decoder": rec["decoder"],
                    "plane": rec["plane"], "line": rec["line"],
                    "line_rule": rec["line_rule"],
                    "total_device_ms": rec["total_device_ms"],
                    "device_ms_per_step": join["device_ms_per_step"],
                    "wall_ms_per_step": join["wall_ms_per_step"],
                    "device_wall_ratio": join["device_wall_ratio"],
                    "reconciles": join["reconciles"],
                    "join_coverage": join["coverage"],
                    "top_ops": join["per_op"][:top]}
        except Exception as e:                               # noqa: BLE001
            self.state = "failed"
            self.error = f"{type(e).__name__}: {str(e)[:300]}"
            self._annotate()
            return {"state": "failed", "error": self.error}
