"""Whole serve loop's share of the chip's bf16 peak in a cell that is judged
on the time to the first token and not on tokens per second: the same model
FLOPs as `serve_mfu` (`model_flops.serve_flops`: 2 per block parameter per
prompt and output token processed in the window, a row of logits per output
token, attention over the context behind each token; padding not counted)
over window x peak. Where nearly every processed token is a prompt token,
the share says how close the queue of prefills that a request waits behind
runs to the chip's peak, and that queue is the time to the first token.
Moves ttft_p50_ms."""
from benchmark.harness import model_flops, peaks


def read(record, trace):
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    c = record["counters"]
    flops = model_flops.serve_flops(record["config"], c["prompt_tokens"],
                                    c["output_tokens_processed"],
                                    c["context_pairs"])
    return 100.0 * flops / (record["window_s"] * peak)
