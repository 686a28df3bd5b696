"""The DeepSeek-V3 configuration's arithmetic against hand counts (ISSUE 32's
parameter counts), its configuration file against the catalog row, and the
two readers PR 32 added on a recorded record and with nothing to read."""
import json
import math
import os

import pytest

from bench_helpers import ROOT, bench_json

from benchmark import run as bench_run
from benchmark.harness import model_flops_dsv3 as mf
from benchmark.harness import weights_dsv3 as wd

CELL = "dsv3_serve_closed64_ctx4k"
PREFILL_CELL = "cgpt1p3b_serve_prefill8"
H = 7168
MLA = H * 1536 + 1536 * 128 * 192 + H * 576 + 512 * 128 * 256 + 16384 * H
EXPERT = 3 * H * 2048


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek_v3_ep16_share.json")) as f:
        return json.load(f)


def test_layer_list_is_one_dense_layer_and_four_expert_layers(config):
    assert wd.layer_kinds(config) == [("mla", "swiglu")] + \
        [("mla", "moe")] * 4


def test_parameter_counts_match_hand_counts(config):
    """ISSUE 32's reckoning: MLA 187.1 M, shared expert 44.0 M, router
    1.8 M: 233.0 M a layer outside the routed experts; an expert 44.0 M; an
    expert layer with 16 held 937.6 M; the dense layer 583.5 M; the
    vocabulary slice 231.7 M; 4.566 G in all = 9.13 GB in bfloat16."""
    assert round(MLA / 1e6, 1) == 187.1
    dense = mf.layer_counts(config, ("mla", "swiglu"))
    assert dense["matmul"] == MLA + 3 * H * 18432
    assert round(dense["matmul"] / 1e6, 1) == 583.5
    assert dense["expert"] == 0
    moe = mf.layer_counts(config, ("mla", "moe"))
    assert moe["matmul"] == MLA + EXPERT + H * 256
    assert round(moe["matmul"] / 1e6, 1) == 233.0
    assert moe["expert"] == EXPERT == 44040192
    assert round((moe["matmul"] + 16 * EXPERT) / 1e6, 1) == 937.6
    # stored: bf16 matmul weights; f32 norms, router and router bias
    norms = 2 * H + 1536 + 512
    assert moe["bytes"] == 2 * (MLA + EXPERT) + 4 * (H * 256 + 256 + norms)
    assert dense["bytes"] == 2 * dense["matmul"] + 4 * norms
    vocab = 2 * 16160 * H
    assert round(vocab / 1e6, 1) == 231.7
    total = dense["matmul"] + 4 * (moe["matmul"] + 16 * EXPERT) + vocab
    assert round(total / 1e9, 3) == 4.566
    stored = dense["bytes"] + 4 * (moe["bytes"] + 16 * 2 * EXPERT) \
        + 2 * vocab + 4 * H
    assert abs(stored - 9.146e9) < 2e6      # what the engine's arrays hold
    shapes = {f"layers.{i}.{leaf}": s
              for i, k in enumerate(wd.layer_kinds(config))
              for leaf, s in wd.layer_shapes(config, k).items()}
    shapes.update({f"top.{leaf}": s
                   for leaf, s in wd.global_shapes(config).items()})
    counted = sum(mf._count(s) for s in shapes.values())
    assert counted == total + 5 * norms + 4 * 256 + H


def test_serve_flops_and_decode_bytes_match_hand_counts(config):
    matmul = sum(mf.layer_counts(config, k)["matmul"]
                 for k in wd.layer_kinds(config))
    assert mf.mla_flops_per_pair(config) == 2 * 128 * (128 + 64 + 128)
    got = mf.serve_flops(config, processed_tokens=10, output_tokens=3,
                         context_pairs=100, local_pairs=7)
    assert got == 2 * matmul * 10 + 2 * EXPERT * 7 + 2 * H * 16160 * 3 \
        + 5 * 81920 * 100
    # ISSUE 32: 8.7 GFLOP a prompt token as the dense expert form runs it
    dense_form = 2 * (matmul + 4 * 16 * EXPERT)
    assert round(dense_form / 1e9, 1) == 8.7
    assert mf.latent_bytes_per_token(config) == 5 * 2 * 576
    plain = mf.decode_step_bytes(config, slots=64, experts_hit=0,
                                 latent_tokens=0)
    assert mf.decode_step_bytes(config, 64, 10, 1000) - plain == \
        10 * 2 * EXPERT + 1000 * 5760
    assert 2 * EXPERT == 88080384           # an expert hit is 88 MB
    stored = sum(mf.layer_counts(config, k)["bytes"]
                 for k in wd.layer_kinds(config))
    assert plain == stored + 2 * H * 16160 + 2 * H * 64 + 4 * H
    # the query bottleneck's two matrices are among the bytes
    assert stored > 5 * 2 * (H * 1536 + 1536 * 24576)


def test_configuration_file_keeps_the_catalog_numbers(config):
    """Every number of the catalog row under the same key, unless the key
    is in `reduced`; no width among the reduced keys; the file states the
    deployment, what was assumed and what is not built."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert row["name"] == "DeepSeek-V3"
    entry = {c["name"]: c for c in bench_json()["configs"]}[config["name"]]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/deepseek_v3_ep16_share.json"
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("deployment", "departures", "assumed", "tiny", "published"):
        assert config[key]
    assert config["deployment"]["chips_per_layer"] == 16
    assert any("not built" in d and "MTP" in d for d in config["departures"])
    # the floors: four expert layers, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_experts"] == config["n_routed_experts"] == 16 >= 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["router_width"] == config["published"]["n_routed_experts"]
    mc = config["program"]["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["q_lora_rank"],
            mc["kv_lora_rank"], mc["moe_intermediate_size"]) == \
        (7168, 128, 1536, 512, 2048)
    assert (mc["num_experts"], mc["n_routed_experts"],
            mc["experts_per_tok"]) == (16, 256, 8)
    assert mc["rope_scaling"] == config["rope_scaling"]
    assert mc["mixers"] == ["mla"] * 5 and mc["mla_gate"] is False
    pe = config["program"]["paged_engine_config"]
    assert pe["max_len"] == config["max_position_embeddings"] == 4096 + 512
    # the ladder straddles the median prompt and the 75th percentile, and
    # its top bucket holds about half of the prompts: the cluster of its
    # requests admitted alone is then wide enough to hold the median TIME
    # to first token from seed to seed (the file's `departures` say why)
    traffic = bench_run.load_json(ROOT, "benchmark", "traffic",
                                  "closed64_ctx4k.json")
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    ladder = pe["prefill_buckets"]
    for q in (0.5, 0.75):
        length = lo * (hi / lo) ** q
        bucket = min(b for b in ladder if b >= length)
        below = max([b for b in ladder if b < length])
        assert below * 1.05 < length < bucket / 1.05
    assert ladder[-1] == hi
    top_share = 1 - math.log(ladder[-2] / lo) / math.log(hi / lo)
    assert 0.45 <= top_share <= 0.55


def test_accepted_readers_find_their_keys_in_the_file(config):
    """`moe_experts_hit_pct` and its neighbours read `num_experts`,
    `num_hidden_layers` and `first_k_dense_replace` from the file."""
    moe = {"spans": 10, "moe_pairs_total": 10 * 64 * 8 * 4,
           "moe_pairs_local": 1280, "moe_experts_hit": 10 * 48,
           "moe_expert_max": 60}
    record = {"config": config, "counters": {"moe": {"decode": moe}}}
    assert reader("moe_experts_hit_pct")(record, None) == \
        pytest.approx(100 * 48 / 64)
    assert reader("moe_local_pair_share_pct")(record, None) == \
        pytest.approx(100 * 1280 / 20480)
    assert reader("moe_expert_load_max_over_mean")(record, None) == \
        pytest.approx(60 / (1280 / 64))


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def test_new_entries_are_there_and_list_their_cells():
    """What PR 32 added is present, after what was there; nothing is pinned
    to the end of a list, so a later PR appends without editing this."""
    bench = bench_json()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert configs.index("deepseek_v3_ep16_share") == 3
    assert cells[3:5] == [CELL, PREFILL_CELL]
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert metrics[27:30] == ["latent_read_share_pct",
                              "prefill_device_ms_p50", "serve_mfu.prefill"]
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        for cell in (CELL, PREFILL_CELL):
            if cell in m.get("workloads", []):
                assert cell in reports[m["moves"]], (m["name"], cell)
    listing = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert {"serve_mfu.hybrid", "decode_hbm_roofline", "moe_experts_hit_pct",
            "latent_read_share_pct", "prefill_device_ms_p50",
            "prefill_ms_p50"} <= listing
    assert "cache_state_share_pct" not in listing


def test_the_prefill_cell_is_judged_on_first_token_only():
    """Four equal outputs quantise tokens per second, and the driver's sets
    of six spread `gap_p95_ms` past half its bound (PR 32, PERF.md section
    7): the cell is listed under neither, nor by a reader that moves either,
    and the share of the peak it reports is the one that moves
    `ttft_p50_ms`."""
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == PREFILL_CELL)
    assert (cell["config"], cell["traffic"]) == \
        ("cerebras_gpt_1p3b", "closed8_longprompt")
    reported = {m["name"] for m in bench["end_to_end"]
                if PREFILL_CELL in m.get("workloads", [PREFILL_CELL])}
    assert reported == {"setup_s", "ttft_p50_ms"}
    listing = {m["name"]: m["moves"] for m in bench["per_layer"]
               if PREFILL_CELL in m["workloads"]}
    assert set(listing.values()) == {"ttft_p50_ms"}
    assert [n for n in listing if "mfu" in n] == ["serve_mfu.prefill"]
    assert {"prefill_ms_p50", "prefill_device_ms_p50", "queue_wait_ms_p50",
            "ttft_mean_ms", "ttft_p95_ms"} <= set(listing)


def test_serve_mfu_prefill_is_serve_mfu_on_a_recorded_record():
    """The twin reads the same counters with the same arithmetic; by hand:
    2 per block parameter a processed token, a row of logits per output
    token, 4 L H per (token, context position) pair, over 30 s of peak."""
    config = bench_run.load_json(ROOT, "benchmark", "configs",
                                 "cerebras_gpt_1p3b.json")
    record = {"device_kind": "TPU v5 lite", "window_s": 30.0,
              "config": config,
              "counters": {"prompt_tokens": 440_000,
                           "output_tokens_processed": 1_800,
                           "context_pairs": 170_000_000}}
    got = reader("serve_mfu.prefill")(record, None)
    assert got == reader("serve_mfu")(record, None)
    h, layers, f = 2048, 24, 8192
    block = layers * (h * 3 * h + 3 * h + h * h + h + h * f + f + f * h + h
                      + 4 * h)
    flops = 2 * block * 441_800 + 2 * config["vocab_size"] * h * 1_800 \
        + 4 * layers * h * 170_000_000
    assert got == pytest.approx(100 * flops / (30 * 197e12))
    assert 10 < got < 30


def spans_with(attrs_list):
    return [{"name": "serving::decode.wait", "ts": 10 + i, "dur": 1,
             "span_id": str(i), "parent": None, "attrs": a}
            for i, a in enumerate(attrs_list)]


def test_latent_read_share_on_recorded_spans(monkeypatch):
    from benchmark.harness import program_counters
    spans = spans_with([
        {"latent_rows_read": 5 * 64 * 4608, "latent_rows_held": 5 * 140000},
        {"latent_rows_read": 5 * 64 * 4608, "latent_rows_held": 5 * 150000},
        {"pool_donated": 1}])                    # a span without the keys
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: spans)
    assert reader("latent_read_share_pct")({}, None) == \
        pytest.approx(100 * 290000 / (2 * 64 * 4608))


@pytest.mark.parametrize("spans", [None, [], spans_with([{"pool_donated": 1}])])
def test_latent_read_share_with_nothing_to_read(monkeypatch, spans):
    """No span log, no span in the window, or the parent's spans without
    the two counters: no number, no exception."""
    from benchmark.harness import program_counters
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: spans)
    assert reader("latent_read_share_pct")({}, None) is None


def test_prefill_device_ms_from_a_recorded_trace():
    trace = {"module_s": {"jit_prefill_fn": [0.150, 0.310, 0.090],
                          "jit_prefill_fn.1": [0.200],
                          "jit__decode_fn": [0.030] * 9}}
    assert reader("prefill_device_ms_p50")({}, trace) == \
        pytest.approx(175.0)
    assert reader("prefill_device_ms_p50")({}, None) is None
    assert reader("prefill_device_ms_p50")(
        {}, {"module_s": {"jit__decode_fn": [0.03]}}) is None
    assert reader("prefill_device_ms_p50")({}, {}) is None
