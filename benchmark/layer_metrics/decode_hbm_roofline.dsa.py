"""The decode program's share of its memory roofline where attention reads
only the positions a learned indexer selects: the least bytes one decode step
has to move (`harness.flops`' `decode_step_bytes`: non-expert weights once,
each held expert some token chose once, each indexer key scored, each
selected K/V row, the rows and keys written, the head's slice) at the means
of `dsa_rows_indexed` and `dsa_rows_selected` over the window's
`serving::decode_step` spans and of `moe_experts_hit` over its
`serving::decode.wait` spans, over the chip's memory bandwidth, over the
median device time of the decode program in the trace. The rows are the
program's own count from its positions, so the share cannot pass 100. Moves
ttft_p50_ms (see serve_mfu.dsa). None from a program that does not count
the rows."""
import importlib
import statistics

from benchmark.harness import peaks, program_counters

KEYS = ("dsa_rows_indexed", "dsa_rows_selected")


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    module = importlib.import_module(
        f"benchmark.harness.{flops_of}") if flops_of else None
    moe = (record["counters"].get("moe") or {}).get("decode")
    if trace is None or not hasattr(module, "index_flops_per_pair") \
            or not moe:
        return None
    rows = program_counters.attr_sums(record, "decode_step", KEYS)
    runs = [d for name, ds in trace["module_s"].items()
            if "decode_fn" in name for d in ds]
    if not rows or not runs:
        return None
    least = module.decode_step_bytes(
        record["config"], record["counters"]["slots"],
        moe["moe_experts_hit"] / moe["spans"],
        rows["dsa_rows_indexed"] / rows["spans"],
        rows["dsa_rows_selected"] / rows["spans"])
    bandwidth = peaks.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / bandwidth) / statistics.median(runs)
