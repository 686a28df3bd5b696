"""Whole serve loop's share of the chip's bf16 peak for a model the
configuration file describes with its own arithmetic (`harness.flops`): 2 per
parameter a token really multiplies here (non-expert parameters, the shared
expert, and each token-expert pick that fell on a held expert, counted by
the program on its decode and prefill spans), the head's slice per output
token, the recurrent state's update and attention over the context, over
window x peak. Bucket padding and experts applied to tokens that did not
choose them are not counted. Moves ttft_p50_ms: the cell is judged on the
step that gives a request its first token (a prefill and a decode of every
client), because its tokens per second and its gap percentile spread wider
from run to run than half their bounds (PERF.md section 6, PR 28)."""
import importlib

from benchmark.harness import peaks


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    moe = record["counters"].get("moe") or {}
    if not flops_of or not moe.get("decode"):
        return None
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    c = record["counters"]
    local = moe["decode"]["moe_pairs_local"] \
        + (moe.get("prefill") or {}).get("moe_pairs_local", 0)
    flops = importlib.import_module(
        f"benchmark.harness.{flops_of}").serve_flops(
        record["config"], c["prompt_tokens"] + c["output_tokens_processed"],
        c["output_tokens"], c["context_pairs"], local)
    return 100.0 * flops / (record["window_s"] * peak)
