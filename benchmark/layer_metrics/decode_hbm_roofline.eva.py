"""The decode program's share of its memory roofline where the cache is a
window of token rows and a summary row a chunk: the least bytes one decode
step has to move (`harness.flops`' `decode_step_bytes`: weights once, the
ring rows and the summary rows the slots' queries see, the rows written, the
first head's slice) at the means of `window_rows_held` and
`summary_rows_held` on the window's `serving::step` spans that decoded, over
the chip's memory bandwidth, over the median device time of the decode
program in the trace. The rows are the program's own count from its
positions, so the share cannot pass 100. Under the gather arm the step
builds the dense view of every slot's whole table: expect a third or less.
Moves ttft_p50_ms (see serve_mfu.eva). None from a program that does not
count the rows."""
import importlib
import statistics

from benchmark.harness import peaks, program_spans

KEYS = ("window_rows_held", "summary_rows_held")


def mean_rows(record):
    """(mean window rows, mean summary rows) over the window's steps that
    decoded and carry both, or None."""
    rows = program_spans.read(record)
    steps = [s["attrs"] for s in (rows or {}).get("steps", ())
             if "decode_step" in s["total_ns"]
             and all(k in s["attrs"] for k in KEYS)]
    if not steps:
        return None
    return tuple(statistics.mean(a[k] for a in steps) for k in KEYS)


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    module = importlib.import_module(
        f"benchmark.harness.{flops_of}") if flops_of else None
    held = mean_rows(record) if hasattr(module, "visible_rows") else None
    if trace is None or held is None:
        return None
    runs = [d for name, ds in trace["module_s"].items()
            if "decode_fn" in name for d in ds]
    if not runs:
        return None
    least = module.decode_step_bytes(record["config"], *held,
                                     slots=record["counters"]["slots"])
    bandwidth = peaks.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / bandwidth) / statistics.median(runs)
