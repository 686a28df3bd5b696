"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json's `workloads`, loads its configuration
(`configs/<config>.json`) and its traffic mix (`traffic/<traffic>.json`),
picks the loop by the mix's `kind` (`loops/<kind>.py`) and, in a traced run,
calls every `layer_metrics/<name>.py` whose metric lists the cell. A later PR
adds a configuration, a mix, a cell, a per-layer metric or a kind of loop by
adding files and entries; it edits none.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. `--cpu-tiny` (rehearsal and tests only) runs
the same code at the `tiny` sizes of the same files on the CPU, and says that
it is not a chip run before its last line.
"""
import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time

_T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(directory, name):
    path = os.path.join(HERE, directory, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_of(spec):
    """The same file at its `tiny` sizes: top-level keys overridden, nested
    groups merged one level deep."""
    out = dict(spec)
    for k, v in spec.get("tiny", {}).items():
        out[k] = {**spec[k], **v} if isinstance(v, dict) \
            and isinstance(spec.get(k), dict) else v
    return out


def load_cell(workload, tiny=False):
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic
    mix), the two files at their `tiny` sizes if asked."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        sys.exit(f"benchmark: no workload {workload!r} in BENCHMARK.json "
                 f"(have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    if tiny:
        config, traffic = tiny_of(config), tiny_of(traffic)
    return bench, cell, config, traffic


def make_ctx(cell, config, traffic, seed, seconds, tiny, **extra):
    """What a loop's `run(ctx)` gets. `extra` overrides: `trace`, `capture`,
    `mark`, and the hooks tests and tools/limits.py plant (`wrap_step`,
    `wrap_engine`, `control_mode`)."""
    from benchmark.harness import tracing
    return {"cell": cell, "config": config, "traffic": traffic,
            "seed": seed, "seconds": seconds, "tiny": tiny, "trace": False,
            "t0": time.perf_counter(), "root": ROOT,
            "compile_counter": tracing.CompileCounter(),
            "span": tracing.span, "mark": lambda name: None,
            "trace_steps": 6, "trace_seconds": 2.0, **extra}


def lists_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None, hooks=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR, to look "
                         "at by hand (benchmark/tools/look_trace.py)")
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="rehearsal on the CPU at tiny sizes; NOT a chip run")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload, args.cpu_tiny)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # libtpu's logs go under the run's own TMPDIR, not a fixed /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    devices = jax.devices()
    if not args.cpu_tiny and (devices[0].platform != "tpu"
                              or len(devices) < cell["chips"]):
        sys.exit(f"benchmark: cell {cell['name']} needs {cell['chips']} TPU "
                 f"chip(s); jax reports {len(devices)} x "
                 f"{devices[0].platform!r}. Only --cpu-tiny runs without a "
                 f"chip, and that is not a chip run.")

    from benchmark.harness import lastline, tracing
    marks = [["jax_up", time.perf_counter() - _T0]]
    ctx = make_ctx(
        cell, config, traffic, args.seed, seconds, args.cpu_tiny,
        trace=bool(args.trace), t0=_T0,
        mark=lambda name: marks.append([name, time.perf_counter() - _T0]),
        capture=lambda fn: tracing.capture(
            fn, os.path.join(ROOT, ".bench_tmp", cell["name"], "trace"),
            cell["chips"], keep_copy=args.keep_trace),
        **(hooks or {}))
    record = load_module("loops", traffic["kind"]).run(ctx)
    record["device_kind"] = devices[0].device_kind
    record["config"], record["traffic"] = config, traffic

    trace = record.get("trace")
    if args.trace:
        declared = [m for m in bench["per_layer"]
                    if lists_cell(m, cell["name"])]
        values = {}
        for m in declared:
            try:
                value = load_module("layer_metrics",
                                    m["name"]).read(record, trace)
            except KeyError:
                if not args.cpu_tiny:
                    raise
                value = None        # the CPU has no row in the peaks table
            if value is not None:       # a reader with nothing to read
                values[m["name"]] = value
    else:
        declared = [m for m in bench["end_to_end"]
                    if lists_cell(m, cell["name"])]
        values = {m["name"]: record["end_to_end"][m["name"]]
                  for m in declared}
    units = {m["name"]: m["unit"] for m in declared}
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}

    device = lastline.device_block(
        record["memory_peak_bytes"],
        busy_s=trace["busy_s"] if args.trace and trace else None,
        window_s=trace["window_s"] if args.trace and trace else None)
    breakdown = {"device_ops": trace["device_ops"],
                 "idle_gaps": trace["idle_gaps"]} \
        if args.trace and trace else None
    checks = record["checks"]
    if args.cpu_tiny:
        print("benchmark --cpu-tiny: NOT A CHIP RUN - CPU backend, tiny "
              "sizes. No number below is a device result.", flush=True)
    print(json.dumps({"note": "details of this run (not the result line)",
                      "setup_s": record["end_to_end"]["setup_s"],
                      "reference_s": record.get("reference_s"),
                      "window_s": record.get("window_s"),
                      "readings": record.get("readings"),
                      "counters": record.get("counters"),
                      "spans": record.get("spans"),
                      "setup_marks": marks,
                      "total_s": time.perf_counter() - _T0}), flush=True)
    lastline.emit(all(c["ok"] for c in checks), record["attempted"],
                  record["failed"], metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
