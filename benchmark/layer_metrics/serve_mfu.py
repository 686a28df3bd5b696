"""Whole serve loop's share of the chip's bf16 peak: model FLOPs of every
prompt and output token processed in the window (2 per block parameter per
token, a row of logits per output token, attention over the context behind
each token; padding not counted) over window x peak. The weights are stored
in float32 but XLA's default f32 matmul on the TPU is one bf16 pass, so the
bf16 peak is the honest denominator. Moves serve_tokens_per_s."""
from benchmark.harness import model_flops, peaks


def read(record, trace):
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    c = record["counters"]
    flops = model_flops.serve_flops(record["config"], c["prompt_tokens"],
                                    c["output_tokens_processed"],
                                    c["context_pairs"])
    return 100.0 * flops / (record["window_s"] * peak)
