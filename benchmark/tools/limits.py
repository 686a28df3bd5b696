"""Read, on the chip and at the cell's own size, what the limits of a cell's
comparison are set from: the program's numbers over a dozen seeds (the lower
reading), the control's (the reference put in the program's place at the
next precision down) and, for a training cell, each fault's.

    python3 benchmark/tools/limits.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --fault-seeds 3 [--seconds 8] [--out FILE]

One process for all seeds, because set-up is most of a run. Prints one JSON
line per (seed, what) and a summary; PERF.md has the table.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import run as bench_run                      # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu-tiny", action="store_true")
    args = ap.parse_args(argv)

    _, cell, config, traffic = bench_run.load_cell(args.workload,
                                                   args.cpu_tiny)
    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if not args.cpu_tiny and jax.devices()[0].platform != "tpu":
        sys.exit("limits: needs the chip (or --cpu-tiny to rehearse)")

    from benchmark.harness import correctness, faults
    control = config["dtype"]["control"]
    rows = []

    def emit(seed, what, readings):
        row = {"cell": cell["name"], "seed": seed, "what": what, **readings}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def ctx_for(seed, **extra):
        return bench_run.make_ctx(cell, config, traffic, seed, args.seconds,
                                  args.cpu_tiny, **extra)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        if traffic["kind"] == "train_job":
            from paddle_tpu.framework import compile_cache
            compile_cache.place()
            loop = bench_run.load_module("loops", "train_job")
            ctx = ctx_for(seed)
            prog = loop.Program(ctx)
            got = prog.first_steps(traffic["first_steps"])
            batches = prog.batches
            prog.free()
            want = loop.reference_readings(ctx, batches)
            emit(seed, "program", correctness.train_readings_gap(got, want))
            if i < args.control_seeds:
                low = loop.reference_readings(ctx, batches, control)
                emit(seed, f"control:{control}",
                     correctness.train_readings_gap(low, want))
            if i < args.fault_seeds:
                for fault in ("half_batch", "state_unchanged"):
                    bad = loop.Program(ctx_for(
                        seed, wrap_step=getattr(faults, fault)))
                    got_bad = bad.first_steps(traffic["first_steps"])
                    bad.free()
                    emit(seed, f"fault:{fault}",
                         correctness.train_readings_gap(got_bad, want))
        else:
            loop = bench_run.load_module("loops", traffic["kind"])
            record = loop.run(ctx_for(
                seed, control_mode=control if i < args.control_seeds
                else None))
            emit(seed, "program", {**record["readings"],
                                   **record["end_to_end"],
                                   "attempted": record["attempted"]})
            if "control_readings" in record:
                emit(seed, f"control:{control}", record["control_readings"])

    numeric = {}
    for row in rows:
        for k, v in row.items():
            if isinstance(v, (int, float)) and k != "seed":
                numeric.setdefault((row["what"], k), []).append(v)
    summary = {f"{what} {k}": {"min": min(v), "max": max(v), "n": len(v)}
               for (what, k), v in sorted(numeric.items())}
    print(json.dumps({"summary": summary}, indent=1), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
            f.write(json.dumps({"summary": summary}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
