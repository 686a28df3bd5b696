"""The Nemotron-3-Nano configuration's arithmetic against hand counts (ISSUE
34's parameter counts), its configuration file against the catalog row, and
the two readers PR 34 added on a recorded record and with nothing to read."""
import json
import os

import pytest

from bench_helpers import ROOT, bench_json

from benchmark import run as bench_run
from benchmark.harness import model_flops_nemotron_h as mf
from benchmark.harness import weights_nemotron_h as wn

CELL = "nemotron3_nano_serve_closed128"
H = 2688
MAMBA = H * 10304 + 4096 * H             # in_proj [z, xBC, dt] + out_proj
MAMBA_SMALL = 4 * 6144 + 6144 + 3 * 64 + 4096   # conv, its bias, dt/A/D, norm
GQA = H * 4096 + 2 * H * 256 + 4096 * H
EXPERT = 2 * H * 1856
SHARED = 2 * H * 3712
ROUTER = H * 128
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_ep8_share.json")) as f:
        return json.load(f)


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def test_block_list_is_the_patterns_first_three_segments(config):
    kinds = wn.layer_kinds(config)
    assert config["hybrid_override_pattern"] == PATTERN[:20]
    assert len(kinds) == 20
    assert [kinds.count(k) for k in ("mamba2", "moe", "gqa")] == [9, 8, 3]
    assert [PATTERN.count(c) for c in "ME*"] == [23, 23, 6]
    assert config["program"]["model_config"]["blocks"] == kinds


def test_parameter_counts_match_hand_counts(config):
    """ISSUE 34's reckoning: a Mamba-2 block 38.74 M, an attention block
    23.40 M, an expert 9.98 M, the shared expert 19.96 M, the router 0.34 M,
    an expert block with 16 held 179.9 M, the vocabulary's eighth 88.1 M;
    blocks 0-19 + the slice 1.947 G = 3.89 GB in bfloat16; the whole model
    31.58 B."""
    mamba = mf.layer_counts(config, "mamba2")
    assert mamba["matmul"] == MAMBA and mamba["expert"] == 0
    assert round((MAMBA + MAMBA_SMALL + H) / 1e6, 2) == 38.74
    gqa = mf.layer_counts(config, "gqa")
    assert gqa["matmul"] == GQA
    assert round((GQA + H) / 1e6, 2) == 23.40
    moe = mf.layer_counts(config, "moe")
    assert moe["matmul"] == ROUTER + SHARED and moe["expert"] == EXPERT
    assert (round(EXPERT / 1e6, 2), round(SHARED / 1e6, 2),
            round(ROUTER / 1e6, 2)) == (9.98, 19.96, 0.34)
    block16 = ROUTER + 128 + SHARED + 16 * EXPERT + H
    assert round(block16 / 1e6, 1) == 179.9
    vocab = 2 * 16384 * H
    assert round(vocab / 1e6, 1) == 88.1
    total = 9 * (MAMBA + MAMBA_SMALL + H) + 3 * (GQA + H) + 8 * block16 \
        + vocab + H
    assert round(total / 1e9, 3) == 1.947
    # every leaf of every block and the globals, counted from the shapes
    shapes = [s for k in wn.layer_kinds(config)
              for s in wn.layer_shapes(config, k).values()]
    shapes += list(wn.global_shapes(config).values())
    assert sum(mf._count(s) for s in shapes) == total
    # stored: bfloat16 but the float32 leaves
    f32_mamba = H + 3 * 64 + 4096
    assert mamba["bytes"] == 2 * (MAMBA + MAMBA_SMALL - 3 * 64 - 4096) \
        + 4 * f32_mamba
    assert gqa["bytes"] == 2 * GQA + 4 * H
    assert moe["bytes"] == 2 * SHARED + 4 * (ROUTER + 128 + H)
    stored = 9 * mamba["bytes"] + 3 * gqa["bytes"] \
        + 8 * (moe["bytes"] + 16 * 2 * EXPERT) + 2 * vocab + 4 * H
    assert round(stored / 1e9, 2) == 3.90
    # the uncut model: 23 + 6 + 23 blocks of 128 experts, the whole vocabulary
    whole = 23 * (MAMBA + MAMBA_SMALL + H) + 6 * (GQA + H) \
        + 23 * (ROUTER + 128 + SHARED + 128 * EXPERT + H) \
        + 2 * 131072 * H + H
    assert round(whole / 1e9, 2) == 31.58


def test_serve_flops_and_decode_bytes_match_hand_counts(config):
    matmul = 9 * MAMBA + 3 * GQA + 8 * (ROUTER + SHARED)
    ssm = 5 * 64 * 64 * 128 + 2 * 4 * 6144
    assert mf.ssm_flops_per_token(config) == ssm
    assert mf.gqa_flops_per_pair(config) == 4 * 32 * 128
    got = mf.serve_flops(config, processed_tokens=10, output_tokens=3,
                         context_pairs=100, local_pairs=7)
    assert got == 2 * matmul * 10 + 9 * ssm * 10 + 2 * EXPERT * 7 \
        + 2 * H * 16384 * 3 + 3 * 16384 * 100
    # ISSUE 34: 3.75 GFLOP a prompt token as the dense expert form runs it
    dense_form = 2 * (matmul + 8 * 16 * EXPERT) + 9 * ssm
    assert round(dense_form / 1e9, 2) == 3.74
    # a slot's state: 9 blocks of [64, 64, 128] float32 + a bfloat16 tail
    per_slot = 9 * (4 * 64 * 64 * 128 + 2 * 3 * 6144)
    assert mf.state_bytes_per_slot(config) == per_slot
    assert round(per_slot / 9 / 1e6, 3) == 2.134
    assert mf.kv_bytes_per_token(config) == 3 * 2 * 2 * 2 * 128 == 3072
    parts = mf.decode_step_bytes_by_part(config, slots=128, experts_hit=100,
                                         latent_tokens=60000)
    assert set(parts) == {"state", "expert", "attention", "other"}
    mamba, gqa, moe = (mf.layer_counts(config, k)["bytes"]
                       for k in ("mamba2", "gqa", "moe"))
    assert parts["state"] == 9 * mamba + 2 * per_slot * 128
    assert parts["expert"] == 8 * moe + 100 * 2 * EXPERT
    assert parts["attention"] == 3 * gqa + 3072 * 60000
    assert parts["other"] == 2 * H * 16384 + 2 * H * 128 + 4 * H
    assert mf.decode_step_bytes(config, 128, 100, 60000) == \
        sum(parts.values())
    # ISSUE 34 reckoned the least bytes at 8.9 GB, the state-space blocks
    # 63 % of it; with every expert hit and half of the positions resident
    # (0.30 GB of rows beside 0.14 GB of attention weights) it is 9.0 and 62
    full = mf.decode_step_bytes_by_part(config, 128, 8 * 16, 128 * 768)
    assert round(sum(full.values()) / 1e9, 1) == 9.0
    assert round(100 * full["state"] / sum(full.values())) == 62
    assert 2 * EXPERT == 19955712          # an expert hit is 20 MB


def test_configuration_file_keeps_the_catalog_numbers(config):
    """Every number of the catalog row under the same key, unless the key
    is in `reduced`; no width among the reduced keys; the file states the
    deployment, what the slots stand for, what was assumed."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    entry = {c["name"]: c for c in bench_json()["configs"]}[config["name"]]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == "benchmark/configs/nemotron3_nano_ep8_share.json"
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["published"]["hybrid_override_pattern"] == PATTERN
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("deployment", "departures", "assumed", "tiny", "published"):
        assert config[key]
    assert config["deployment"]["chips_per_layer"] == 8
    assert "sixth" in config["deployment"]["batch"]
    # the floors: a whole period and four blocks more, 8 experts, an eighth
    assert config["num_experts"] == config["n_routed_experts"] == 16 >= 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["router_width"] == config["published"]["n_routed_experts"]
    mc = config["program"]["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["num_kv_heads"],
            mc["head_dim"], mc["moe_intermediate_size"],
            mc["shared_intermediate_size"]) == (2688, 32, 2, 128, 1856, 3712)
    assert (mc["ssm_heads"], mc["ssm_head_dim"], mc["ssm_groups"],
            mc["ssm_state_size"], mc["ssm_chunk"], mc["conv_kernel"]) == \
        (64, 64, 8, 128, 128, 4)
    assert (mc["num_experts"], mc["n_routed_experts"], mc["experts_per_tok"],
            mc["n_group"], mc["topk_group"], mc["moe_act"]) == \
        (16, 128, 6, 1, 1, "relu2")
    assert mc["rms_norm_eps"] == config["layer_norm_epsilon"] == 1e-5
    pe = config["program"]["paged_engine_config"]
    assert pe["slots"] == 128
    assert pe["max_len"] == config["max_position_embeddings"] == 1024 + 512
    # the ladder keeps the prompts' median and upper quartile off bucket
    # edges (the lower quartile, 128, is the first bucket's edge)
    traffic = bench_run.load_json(ROOT, "benchmark", "traffic",
                                  "closed128_longout.json")
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    ladder = pe["prefill_buckets"]
    for q in (0.5, 0.75):
        length = lo * (hi / lo) ** q
        bucket = min(b for b in ladder if b >= length)
        below = max(b for b in ladder if b < length)
        assert below * 1.05 < length < bucket / 1.05, (q, length)
    assert ladder[-1] == hi and traffic["clients"] == pe["slots"]
    old = bench_run.load_json(ROOT, "benchmark", "traffic",
                              "closed64_longout.json")
    assert (traffic["prompt_len"], traffic["output_len"]) == \
        (old["prompt_len"], old["output_len"])


def test_new_entries_are_there_and_list_their_cells():
    """What PR 34 added is present, after what was there; nothing is pinned
    to the end of a list, so a later PR appends without editing this."""
    bench = bench_json()
    configs = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert configs.index("nemotron3_nano_ep8_share") == 4
    assert cells.index(CELL) == 5
    cell = bench["workloads"][5]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron3_nano_ep8_share", "closed128_longout", 1)
    assert "sixth" in cell["why"] and len(cell["why"]) <= 200
    assert metrics[30:32] == ["decode_state_byte_share_pct",
                              "ssm_scan_fill_pct"]
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"setup_s", "ttft_p50_ms"}
    listing = {m["name"]: m for m in bench["per_layer"]
               if CELL in m.get("workloads", [])}
    assert {m["moves"] for m in listing.values()} == {"ttft_p50_ms"}
    assert set(listing) == {
        "ttft_p95_ms", "ttft_mean_ms", "prefill_ms_p50",
        "prefill_device_ms_p50", "queue_wait_ms_p50", "serve_mfu.hybrid",
        "decode_hbm_roofline", "moe_local_pair_share_pct",
        "cache_state_share_pct", "latent_read_share_pct",
        "decode_state_byte_share_pct", "ssm_scan_fill_pct"}
    # the two readers that count expert layers as num_hidden_layers -
    # first_k_dense_replace cannot read a pattern of single blocks
    assert "moe_experts_hit_pct" not in listing
    assert "moe_expert_load_max_over_mean" not in listing
    for name in ("decode_state_byte_share_pct", "ssm_scan_fill_pct"):
        m = listing[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["workloads"]) == ("%", "higher", "program_counter",
                                    "state-space blocks", [CELL])


def step_rows(attrs_list):
    return {"steps": [{"dur_ns": 1, "attrs": a, "self_ns": {},
                       "total_ns": {"decode_step": 1}} for a in attrs_list],
            "spans": {}, "requests": []}


def test_decode_state_byte_share_on_a_recorded_record(config, monkeypatch):
    from benchmark.harness import program_spans
    rows = step_rows([
        {"state_slots_in_use": 128, "kv_tokens_held": 60000},
        {"state_slots_in_use": 120, "kv_tokens_held": 70000},
        {"active_slots": 3}])                   # a step without the gauges
    rows["steps"].append({"dur_ns": 1, "total_ns": {"prefill": 1},
                          "self_ns": {}, "attrs": {
                              "state_slots_in_use": 1,
                              "kv_tokens_held": 5}})   # it did not decode
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    moe = {"spans": 10, "moe_experts_hit": 10 * 120}
    record = {"config": config, "counters": {"moe": {"decode": moe}}}
    parts = mf.decode_step_bytes_by_part(config, 124, 120, 65000)
    want = 100 * parts["state"] / sum(parts.values())
    assert reader("decode_state_byte_share_pct")(record, None) == \
        pytest.approx(want)
    assert 55 < want < 70


@pytest.mark.parametrize("what", ["no_log", "no_counters", "no_gauges",
                                  "no_split"])
def test_decode_state_byte_share_with_nothing_to_read(config, monkeypatch,
                                                      what):
    """No span log, a program whose expert layers count nothing, steps
    without the gauges, or a configuration whose arithmetic has no split by
    part: no number, no exception."""
    from benchmark.harness import program_spans
    rows = None if what == "no_log" else step_rows(
        [{"active_slots": 2}] if what == "no_gauges"
        else [{"state_slots_in_use": 2, "kv_tokens_held": 9}])
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    moe = None if what == "no_counters" \
        else {"spans": 4, "moe_experts_hit": 40}
    if what == "no_split":
        config = bench_run.load_json(ROOT, "benchmark", "configs",
                                     "ling3_flash_ep4_share.json")
    record = {"config": config, "counters": {"moe": {"decode": moe}}}
    assert reader("decode_state_byte_share_pct")(record, None) is None
    gpt = bench_run.load_json(ROOT, "benchmark", "configs",
                              "cerebras_gpt_1p3b.json")
    assert reader("decode_state_byte_share_pct")(
        {"config": gpt, "counters": {}}, None) is None


def prefill_spans(attrs_list):
    return [{"name": "serving::prefill", "ts": 10 + i, "dur": 1,
             "span_id": str(i), "parent": None, "attrs": a}
            for i, a in enumerate(attrs_list)]


def test_ssm_scan_fill_on_recorded_spans(monkeypatch):
    from benchmark.harness import program_counters
    spans = prefill_spans([
        {"ssm_tokens_scanned": 9 * 384, "ssm_tokens_valid": 9 * 256},
        {"ssm_tokens_scanned": 9 * 128, "ssm_tokens_valid": 9 * 100},
        {"pool_donated": 1}])                    # a span without the keys
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: spans if name == "prefill"
                        else None)
    assert reader("ssm_scan_fill_pct")({}, None) == \
        pytest.approx(100 * 356 / 512)


@pytest.mark.parametrize("spans", [None, [],
                                   prefill_spans([{"pool_donated": 1}])])
def test_ssm_scan_fill_with_nothing_to_read(monkeypatch, spans):
    """No span log, no prefill in the window, or the parent's spans without
    the two counters: no number, no exception."""
    from benchmark.harness import program_counters
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: spans)
    assert reader("ssm_scan_fill_pct")({}, None) is None


def test_accepted_readers_read_the_new_cells_record(config):
    """`serve_mfu.hybrid` and `decode_hbm_roofline` find `serve_flops` and
    `decode_step_bytes` in the configuration's own module."""
    moe = {"spans": 10, "moe_pairs_total": 10 * 128 * 6 * 8,
           "moe_pairs_local": 7680, "moe_experts_hit": 10 * 120,
           "moe_expert_max": 30}
    record = {"config": config, "device_kind": "TPU v5 lite",
              "window_s": 30.0,
              "counters": {"moe": {"decode": moe, "prefill": {
                  "moe_pairs_local": 20000}},
                  "prompt_tokens": 90000, "output_tokens_processed": 95000,
                  "output_tokens": 95300, "context_pairs": 60_000_000}}
    got = reader("serve_mfu.hybrid")(record, None)
    flops = mf.serve_flops(config, 185000, 95300, 60_000_000, 27680)
    assert got == pytest.approx(100 * flops / (30 * 197e12))
    assert 1 < got < 10
    assert reader("moe_local_pair_share_pct")(record, None) == \
        pytest.approx(100 * 7680 / 61440)
