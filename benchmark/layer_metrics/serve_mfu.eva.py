"""Whole serve loop's share of the chip's bf16 peak for a model whose
attention scores a window of tokens and a summary row a chunk (`harness.flops`
with `serve_flops(config, processed_tokens, output_tokens, visible_pairs)`):
2 per layer parameter a processed token, the first prediction head's row of
logits an output token, and attention over the (query, visible row) pairs the
program itself counted from its positions: `eva_pairs` on the window's
`serving::prefill` spans, `latent_rows_held` / layers on its
`serving::decode.wait` spans. Bucket padding, the heads the served step does
not run and the dense view's unseen rows are not counted. Over window x
peak. Moves ttft_p50_ms, as serve_mfu.hybrid does: the cell is judged on the
step that gives a request its first token. None from a program that does not
count the pairs."""
import importlib

from benchmark.harness import peaks, program_counters


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    module = importlib.import_module(
        f"benchmark.harness.{flops_of}") if flops_of else None
    if not hasattr(module, "visible_rows"):
        return None
    prefill = program_counters.attr_sums(record, "prefill", ("eva_pairs",))
    decode = program_counters.attr_sums(record, "decode.wait",
                                        ("latent_rows_held",))
    if not prefill or not decode:
        return None
    c = record["counters"]
    pairs = prefill["eva_pairs"] + decode["latent_rows_held"] \
        // record["config"]["num_hidden_layers"]
    flops = module.serve_flops(
        record["config"], c["prompt_tokens"] + c["output_tokens_processed"],
        c["output_tokens"], pairs)
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    return 100.0 * flops / (record["window_s"] * peak)
