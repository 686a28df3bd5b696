"""model_flops.py, the flash FLOP/byte functions and the traffic generator
against hand-worked numbers at the cells' shapes."""
import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT

from benchmark.harness import model_flops, peaks, traffic_gen


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_gpt2_medium_parameters_and_train_flops():
    cfg = config("gpt2_medium")
    # per layer: qkv 1024*3072+3072, proj 1024*1024+1024, fc1 1024*4096+4096,
    # fc2 4096*1024+1024, two layer norms 4*1024
    per_layer = 3148800 + 1049600 + 4198400 + 4195328 + 4096
    assert per_layer == 12596224
    assert model_flops.block_params(cfg) == 24 * per_layer == 302309376
    # + wte 50304*1024 + wpe 1024*1024 + final layer norm
    assert model_flops.total_params(cfg) == 354871296
    # 6*(blocks + vocab matrix) + 6*L*S*H at S=1024
    assert model_flops.train_flops_per_token(cfg, 1024) == \
        6 * (302309376 + 51511296) + 6 * 24 * 1024 * 1024 == 2273918976


def test_cerebras_1p3b_parameters_and_serve_flops():
    cfg = config("cerebras_gpt_1p3b")
    per_layer = 12589056 + 4196352 + 16785408 + 16779264 + 8192
    assert model_flops.block_params(cfg) == 24 * per_layer == 1208598528
    # one request: a 100-token prompt (prefill gives the first token), then
    # two decode steps at contexts 101 and 102
    pairs = model_flops.causal_pairs(100) + 101 + 102
    assert model_flops.causal_pairs(100) == 5050
    flops = model_flops.serve_flops(cfg, prompt_tokens=100, output_tokens=3,
                                    context_sum=pairs)
    # 102 processed tokens (the third output token is not fed back)
    by_hand = 2 * 1208598528 * 103 + 2 * 50304 * 2048 * 3 \
        + 4 * 24 * 2048 * pairs
    assert flops == by_hand
    assert model_flops.causal_pairs(3, start=2) == 3 + 4 + 5


def test_flash_flops_and_bytes_at_the_train_shape():
    w = model_flops.flash_flops_bytes(8, 16, 1024, 64, itemsize=2)
    # one matmul: 2*S*S*D per head, half of it under the causal mask
    assert w["fwd_flops"] == 2 * (2 * 1024 * 1024 * 64 // 2) * 128 \
        == 17179869184
    assert w["bwd_flops"] == 2 * w["fwd_flops"]
    tensor = 128 * 1024 * 64 * 2
    assert w["fwd_bytes"] == 4 * tensor and w["bwd_bytes"] == 8 * tensor
    # on a v5e the forward is compute-bound, barely: 87 us against 82 us
    p = peaks.peaks_for("TPU v5 lite")
    assert w["fwd_flops"] / p["flops_bf16"] > \
        w["fwd_bytes"] / p["hbm_bytes_per_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_every_seed_gets_the_same_sizes_in_another_order():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "closed8_mixed.json")) as f:
        mix = json.load(f)
    pairs = traffic_gen.size_pairs(mix)
    prompts = sorted(p for p, _ in pairs)
    assert len(pairs) == 64 and prompts[0] >= 32 and prompts[-1] <= 768
    assert all(16 <= o <= 64 for _, o in pairs)
    # log-uniform: the median prompt is the geometric mean of the ends
    assert abs(np.median(prompts) - (32 * 768) ** 0.5) < 8
    seqs = []
    for seed in (1, 3000000001):
        t = traffic_gen.ClosedLoopTraffic(mix, 50257, seed)
        reqs = [t.next_request() for _ in range(64)]
        assert all(max(p) < 50257 for p, _ in reqs)
        seqs.append([(len(p), o) for p, o in reqs])
    assert sorted(seqs[0]) == sorted(seqs[1]) == sorted(pairs)
    assert seqs[0] != seqs[1]
    # shuffled in strata: every round of 8 requests holds one prompt from
    # each eighth of the lengths, so a cut-off cycle is still balanced
    edges = [prompts[i] for i in range(0, 64, 8)] + [10 ** 9]
    for seq in seqs:
        for r in range(0, 64, 8):
            bands = sorted(sum(n >= e for e in edges) for n, _ in seq[r:r + 8])
            assert bands == list(range(1, 9)), (r, bands)
