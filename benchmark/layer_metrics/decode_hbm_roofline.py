"""The decode program's share of its memory roofline: the least bytes one
decode step has to move (non-expert weights once, each held expert that some
token chose once, every slot's state read and written, the resident latent
rows, the head's slice; `harness.flops`' `decode_step_bytes` from the means
of the window's steps) over the chip's memory bandwidth, over the median
device time of the decode program in the trace. Memory-bound at this batch:
the bytes bound it, not the operations. Moves ttft_p50_ms: the cell's
first token comes out of a step that also decodes (see serve_mfu.hybrid)."""
import importlib
import statistics

from benchmark.harness import peaks, program_spans


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    moe = (record["counters"].get("moe") or {}).get("decode")
    rows = program_spans.read(record)
    if trace is None or not flops_of or not moe or not rows:
        return None
    runs = [d for name, ds in trace["module_s"].items()
            if "decode_fn" in name for d in ds]
    held = [s["attrs"]["kv_tokens_held"] for s in rows["steps"]
            if "decode_step" in s["total_ns"]
            and "kv_tokens_held" in s["attrs"]]
    if not runs or not held:
        return None
    least = importlib.import_module(
        f"benchmark.harness.{flops_of}").decode_step_bytes(
        record["config"], record["counters"]["slots"],
        moe["moe_experts_hit"] / moe["spans"], statistics.mean(held))
    bandwidth = peaks.peaks_for(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / bandwidth) / statistics.median(runs)
