"""Whole serve loop's share of the chip's bf16 peak for a model whose
attention reads only the positions a learned indexer selects (`harness.flops`
with `serve_flops(config, processed_tokens, output_tokens, local_pairs,
index_pairs, selected_pairs)`): 2 per non-expert parameter a processed
token, 2 per parameter of each token-expert pick that fell on a held expert,
the vocabulary slice's row of logits an output token, and the indexer's and
attention's pairs the program counted from its positions: `dsa_index_pairs`
and `dsa_selected_pairs` of the window's `serving::prefill` spans,
`dsa_rows_indexed` and `dsa_rows_selected` of its `serving::decode_step`
spans. Useful work only: the masked pairs of a dense pass over the selection
and bucket padding are not counted. Over window x peak. Moves ttft_p50_ms,
as serve_mfu.hybrid does. None from a program that does not count the
pairs."""
import importlib

from benchmark.harness import peaks, program_counters

PREFILL = ("dsa_index_pairs", "dsa_selected_pairs")
DECODE = ("dsa_rows_indexed", "dsa_rows_selected")


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    module = importlib.import_module(
        f"benchmark.harness.{flops_of}") if flops_of else None
    moe = record["counters"].get("moe") or {}
    if not hasattr(module, "index_flops_per_pair") or not moe.get("decode"):
        return None
    prefill = program_counters.attr_sums(record, "prefill", PREFILL)
    decode = program_counters.attr_sums(record, "decode_step", DECODE)
    if not prefill or not decode:
        return None
    c = record["counters"]
    local = moe["decode"]["moe_pairs_local"] \
        + (moe.get("prefill") or {}).get("moe_pairs_local", 0)
    flops = module.serve_flops(
        record["config"], c["prompt_tokens"] + c["output_tokens_processed"],
        c["output_tokens"], local,
        prefill["dsa_index_pairs"] + decode["dsa_rows_indexed"],
        prefill["dsa_selected_pairs"] + decode["dsa_rows_selected"])
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    return 100.0 * flops / (record["window_s"] * peak)
