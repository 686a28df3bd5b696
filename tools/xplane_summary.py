"""Summarize a jax.profiler trace capture: top ops by device time.

Usage:
  python tools/xplane_summary.py TRACE_DIR_OR_FILE [top_n]
         [--jsonl OUT.jsonl] [--join-steps K]

Thin CLI over the typed parser in
`paddle_tpu/observability/deviceprof.py` (ISSUE 9): finds the newest
`.xplane.pb` under a trace dir, parses it through the hardened
plane/line normalization (never the python tracer lane), prints the
per-op markdown table, and optionally appends the schema-validated
`paddle_tpu.deviceprof.v1` record to a JSONL stream.

The parser modules are loaded STANDALONE by file path (they are
stdlib-only by contract) — this tool never imports jax or paddle_tpu,
so it runs beside the process that holds the chip (one process per
chip).

Exit is NONZERO with the reason on any failure — an empty or host-only
capture cannot produce a silently empty table.
"""
import argparse
import importlib.util
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_standalone(name, *relpath):
    path = os.path.join(_ROOT, *relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_deviceprof():
    """The parser, without importing paddle_tpu (or jax)."""
    mod = sys.modules.get("paddle_tpu.observability.deviceprof")
    if mod is not None:
        return mod
    return _load_standalone("_xplane_summary_deviceprof",
                            "paddle_tpu", "observability", "deviceprof.py")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", nargs="?", default="/tmp/xplane_gpt",
                   help="trace dir (newest .xplane.pb wins) or a .pb file")
    p.add_argument("top_n", nargs="?", type=int, default=20)
    p.add_argument("--jsonl", default=None, metavar="OUT",
                   help="also append the schema-validated deviceprof.v1 "
                        "record here")
    p.add_argument("--join-steps", type=int, default=None, metavar="K",
                   help="the capture spans K steps: adds per-step device "
                        "time to the record (cost-model predictions need "
                        "the in-process pipeline, bench.py --xplane)")
    args = p.parse_args(argv)

    dp = load_deviceprof()
    try:
        if os.path.isdir(args.path):
            path = dp.find_xplane(args.path)
        elif os.path.isfile(args.path):
            path = args.path
        else:
            raise dp.CaptureError(
                f"no trace at {args.path} (capture never ran?)")
        rec = dp.parse_xplane(path)
        if args.join_steps:
            dp.join_cost_model(rec, None, steps=args.join_steps)
        print(dp.render_record(rec, top=args.top_n))
        if args.jsonl:
            dp.write_record(rec, args.jsonl)
            print(f"\n(record appended to {args.jsonl})")
    except dp.CaptureError as e:
        print(f"xplane_summary FAILED: {e}", file=sys.stderr)
        return 1
    except ValueError as e:
        print(f"xplane_summary FAILED (schema): {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
