"""Share of the least bytes of a decode step that are EVA's cached rows: the
ring rows and the summary rows the slots' queries see, over the whole of
`harness.flops`' `decode_step_bytes_by_part` (weights, window, summary,
written, other), at the means of `window_rows_held` and `summary_rows_held`
on the window's `serving::step` spans that decoded. It says whether the
mechanism leads in the cell that was added for it: under 50 the weights do.
None from a configuration whose arithmetic has no such split, or from a
program that does not count the rows."""
import importlib

from benchmark.run import load_module


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    module = importlib.import_module(
        f"benchmark.harness.{flops_of}") if flops_of else None
    if not hasattr(module, "visible_rows"):
        return None
    held = load_module("layer_metrics",
                       "decode_hbm_roofline.eva").mean_rows(record)
    if held is None:
        return None
    parts = module.decode_step_bytes_by_part(
        record["config"], *held, slots=record["counters"]["slots"])
    return 100.0 * (parts["window"] + parts["summary"]) / sum(parts.values())
