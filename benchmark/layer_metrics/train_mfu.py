"""Whole train step's share of the chip's bf16 peak: tokens per second of
the measured window x model FLOPs per token (6N + 6LSH, recompute not
counted) over chips x peak. Host clock; moves train_tokens_per_s."""
from benchmark.harness import model_flops, peaks


def read(record, trace):
    peak = peaks.peaks_for(record["device_kind"])["flops_bf16"]
    per_token = model_flops.train_flops_per_token(record["config"],
                                                  record["shapes"]["seq"])
    return 100.0 * record["end_to_end"]["train_tokens_per_s"] * per_token \
        / peak
