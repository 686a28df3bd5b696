"""The hybrid configuration's arithmetic against hand counts, its readers on
a recorded record, and its configuration file against the contract."""
import json
import os

import pytest

from bench_helpers import ROOT, bench_json

from benchmark import run as bench_run
from benchmark.harness import model_flops_hybrid as mf
from benchmark.harness import weights_hybrid as wh

CELL = "ling3_flash_serve_closed64"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling3_flash_ep4_share.json")) as f:
        return json.load(f)


def test_layer_pattern_is_the_published_rule_on_indices_0_to_6(config):
    assert wh.layer_kinds(config) == [
        ("kda", "swiglu"), ("kda", "moe"), ("kda", "moe"), ("kda", "moe"),
        ("kda", "moe"), ("mla", "moe"), ("kda", "moe")]


def test_parameter_counts_match_hand_counts(config):
    h = 2560
    kda = mf.layer_counts(config, ("kda", "swiglu"))
    # q, k, v, decay, output gate, out: six 2560 x 4096; beta 2560 x 32
    assert kda["matmul"] == 6 * h * 4096 + h * 32 + 3 * h * 6144
    assert kda["expert"] == 0
    mla = mf.layer_counts(config, ("mla", "moe"))
    mixer = h * 32 * 192 + h * 576 + 512 * 32 * 256 + h * 32 + 4096 * h
    assert mla["matmul"] == mixer + h * 512 + 3 * h * 768
    assert mla["expert"] == 3 * h * 768 == 5898240
    # stored: bf16 matmul weights, f32 norms / router / router bias
    assert mla["bytes"] == 2 * (mixer + 3 * h * 768) + 4 * (
        h * 512 + 512 + 2 * h + 512)
    # the whole share: 10.48 GB, as the engine reports it on the chip
    total = sum(mf.layer_counts(config, k)["bytes"]
                for k in wh.layer_kinds(config)) \
        + 6 * 128 * 2 * 5898240 + 2 * 2 * h * 39296 + 4 * h
    assert abs(total - 10.479e9) < 2e6


def test_serve_flops_and_decode_bytes_match_hand_counts(config):
    matmul = sum(mf.layer_counts(config, k)["matmul"]
                 for k in wh.layer_kinds(config))
    assert mf.kda_state_flops_per_token(config) == 32 * 7 * 128 * 128
    assert mf.mla_flops_per_pair(config) == 2 * 32 * (128 + 64 + 128)
    got = mf.serve_flops(config, processed_tokens=10, output_tokens=3,
                         context_pairs=100, local_pairs=7)
    assert got == 2 * matmul * 10 + 6 * 32 * 7 * 128 * 128 * 10 \
        + 2 * 5898240 * 7 + 2 * 2560 * 39296 * 3 + 20480 * 100
    assert mf.state_bytes_per_slot(config) == 6 * (
        4 * 32 * 128 * 128 + 2 * 3 * 3 * 4096)
    assert mf.latent_bytes_per_token(config) == 2 * 576
    plain = mf.decode_step_bytes(config, slots=64, experts_hit=0,
                                 latent_tokens=0)
    assert mf.decode_step_bytes(config, 64, 10, 1000) - plain == \
        10 * 2 * 5898240 + 1000 * 1152
    stored = sum(mf.layer_counts(config, k)["bytes"]
                 for k in wh.layer_kinds(config))
    assert plain == stored + 2 * 2560 * 39296 + 2 * 2560 * 64 + 4 * 2560 \
        + 2 * 64 * mf.state_bytes_per_slot(config)


RECORD = {
    "device_kind": "TPU v5 lite", "window_s": 30.0,
    "counters": {"prompt_tokens": 70000, "output_tokens_processed": 60000,
                 "output_tokens": 60200, "context_pairs": 5.0e7, "slots": 64,
                 "moe": {"decode": {"spans": 1000, "moe_pairs_total": 3072000,
                                    "moe_pairs_local": 768000,
                                    "moe_experts_hit": 480000,
                                    "moe_expert_max": 5000},
                         "prefill": {"spans": 200, "moe_pairs_total": 3360000,
                                     "moe_pairs_local": 840000,
                                     "moe_experts_hit": 150000,
                                     "moe_expert_max": 9000}}},
}


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def test_new_readers_on_a_recorded_record(config):
    record = dict(RECORD, config=config)
    assert reader("moe_local_pair_share_pct")(record, None) == 25.0
    assert reader("moe_experts_hit_pct")(record, None) == \
        pytest.approx(100 * 480 / 768)
    # fullest 5 tokens a step against 768 local picks over 768 experts
    assert reader("moe_expert_load_max_over_mean")(record, None) == \
        pytest.approx(5.0)
    flops = mf.serve_flops(config, 130000, 60200, 5.0e7, 768000 + 840000)
    assert reader("serve_mfu.hybrid")(record, None) == \
        pytest.approx(100 * flops / (30.0 * 197e12))
    assert 0 < reader("serve_mfu.hybrid")(record, None) < 100


@pytest.mark.parametrize("name", [
    "serve_mfu.hybrid", "decode_hbm_roofline", "moe_local_pair_share_pct",
    "moe_experts_hit_pct", "moe_expert_load_max_over_mean",
    "cache_state_share_pct"])
def test_new_readers_return_none_with_nothing_to_read(config, name,
                                                      monkeypatch):
    """The parent of PR 28 counts nothing and the accepted serve cell's
    record has no `moe` block: no number, no exception. The span log is
    the parent's: steps that decoded, without the new attrs (and not what
    a cell run earlier in this process left in the real one)."""
    from benchmark.harness import program_spans
    rows = {"steps": [{"attrs": {"kv_tokens_held": 40000},
                       "total_ns": {"decode_step": 1}}],
            "spans": {}, "requests": []}
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    parent = {"device_kind": "TPU v5 lite", "window_s": 30.0,
              "config": {"n_embd": 2048},
              "end_to_end": {"setup_s": 1.0},
              "counters": {"prompt_tokens": 1, "output_tokens_processed": 1,
                           "output_tokens": 1, "context_pairs": 1}}
    assert reader(name)(parent, None) is None
    counted_nothing = dict(RECORD, config=config, counters=dict(
        RECORD["counters"], moe={"decode": None, "prefill": None}))
    assert reader(name)(counted_nothing, None) is None


def test_decode_hbm_roofline_from_a_recorded_trace(config, monkeypatch):
    from benchmark.harness import program_spans
    rows = {"steps": [{"attrs": {"kv_tokens_held": 40000},
                       "total_ns": {"decode_step": 1}, "self_ns": {},
                       "dur_ns": 1}] * 3, "spans": {}, "requests": []}
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    record = dict(RECORD, config=config)
    trace = {"module_s": {"jit__decode_fn(123)": [0.016, 0.015, 0.017]}}
    least = mf.decode_step_bytes(config, 64, 480.0, 40000)
    got = reader("decode_hbm_roofline")(record, trace)
    assert got == pytest.approx(100 * least / 819e9 / 0.016)
    assert 40 < got < 100
    assert reader("decode_hbm_roofline")(record, None) is None


def test_configuration_file_keeps_the_catalog_numbers(config):
    """Every number of the catalog row under the same key, unless the key
    is in `reduced`; no width among the reduced keys."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    entry = {c["name"]: c for c in bench_json()["configs"]}[config["name"]]
    assert entry["reduced"] == config["reduced"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("deployment", "departures", "assumed", "tiny", "published"):
        assert config[key]
    assert config["deployment"]["chips_per_layer"] == 4
    mc = config["program"]["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["head_dim"]) == \
        (2560, 32, 128)
    assert (mc["num_experts"], mc["n_routed_experts"],
            mc["experts_per_tok"]) == (128, 512, 8)
