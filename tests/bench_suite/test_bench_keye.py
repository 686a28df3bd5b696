"""The Keye-VL-2.0 configuration's arithmetic against hand counts, its
configuration file against the catalog row, the entries it appended to
BENCHMARK.json, the three readers it added on recorded records and with
nothing to read, and the cell itself at its tiny sizes: correct as served,
not correct with a token altered, and its limits between the program's
readings and the fp8 control's on fixed requests (no window: nothing here
depends on the wall clock)."""
import json
import os

import numpy as np
import pytest

from bench_helpers import ROOT, bench_json, run_cell

from benchmark import run as bench_run
from benchmark.harness import model_flops_keye as mf
from benchmark.harness import weights_keye as wk

CELL = "keye_vl2_serve_closed16_ctx32k"
CONFIG = "keye_vl2_a3b_ep8_share"
H, N, KV, D, M, E, F, EX, R, V = \
    2048, 32, 4, 128, 16, 64, 768, 16, 128, 18992
ATTN = H * N * D + 2 * H * KV * D + N * D * H        # q, k, v, o
INDEX = H * M * E + H * E + H * M                    # W_qI, W_kI, W_w
EXPERT = 3 * H * F                                   # one SwiGLU expert
SMALL = 2 * H + 2 * D + 2 * E                        # norms, the key's bias
ROW, KEY = 2 * KV * D * 2, E * 2                     # cached, bfloat16
READERS = ("serve_mfu.dsa", "decode_hbm_roofline.dsa",
           "dsa_kv_read_share_pct")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def test_parameter_counts_match_hand_counts(config):
    counts = mf.layer_counts(config)
    assert counts["matmul"] == ATTN + INDEX + H * R
    assert counts["expert"] == EXPERT
    assert counts["bytes"] == 2 * (ATTN + INDEX) + 4 * (H * R + SMALL)
    assert (ATTN, INDEX) == (18_874_368, 2_260_992)
    layer = counts["bytes"] + EX * EXPERT * 2
    assert round(layer / 1e6) == 194                 # 194 MB a layer
    total = 8 * layer + 2 * 2 * V * H + 4 * H
    assert round(total / 1e9, 2) == 1.71
    # the cache: 16 slots of 33 280 positions, 8 layers, a row and a key
    cache = (16 * 2080 + 1) * 16 * 8 * (ROW + KEY)
    assert round(cache / 1e9, 2) == 9.27


def test_serve_flops_and_decode_bytes_match_hand_counts(config):
    flops = mf.serve_flops(config, 1000, 100, 8000, 5_000_000, 2_000_000)
    assert flops == 2 * 8 * (ATTN + INDEX + H * R) * 1000 \
        + 2 * EXPERT * 8000 + 2 * H * V * 100 \
        + M * (2 * E + 2) * 5_000_000 + 4 * N * D * 2_000_000
    parts = mf.decode_step_bytes_by_part(config, 16, 100, 8 * 16 * 17_700,
                                         8 * 16 * 2048)
    assert parts["experts"] == 100 * EXPERT * 2
    assert parts["index"] == 8 * 16 * 17_700 * KEY
    assert parts["selected"] == 8 * 16 * 2048 * ROW
    assert parts["other"] == 8 * 16 * (ROW + KEY) + 2 * H * V + 2 * H * 16 \
        + 4 * H
    # the index keys and the selected rows are the
    # two largest parts after the experts, ~0.29 and ~0.54 GB
    assert round(parts["index"] / 1e9, 2) == 0.29
    assert round(parts["selected"] / 1e9, 2) == 0.54
    assert sum(parts.values()) == mf.decode_step_bytes(
        config, 16, 100, 8 * 16 * 17_700, 8 * 16 * 2048)


def test_configuration_file_keeps_the_catalog_numbers(config):
    """Every key of the catalog row under the same key, unless the key is
    in `reduced`; nested groups whole; `reduced` is depth, the experts held,
    the vocabulary slice and the served length; the file states the 8-chip
    x 6-stage deployment, the departures and what was assumed."""
    catalog = os.environ.get("MODEL_CATALOG_JSONL", "")
    if not os.path.exists(catalog):
        pytest.skip("MODEL_CATALOG_JSONL names no architecture catalog")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert row["name"] == "Keye-VL-2.0-30B-A3B"
    entry = {c["name"]: c for c in bench_json()["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size", "max_position_embeddings"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["max_position_embeddings"]) == \
        (8, 16, 151936 // 8, 33280)
    assert config["router_width"] == 128
    assert config["first_k_dense_replace"] == 0
    for key in ("deployment", "departures", "assumed", "tiny", "published"):
        assert config[key]
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["deployment"]["stages"] == 6
    assert "memory_reckoned" in config["deployment"]
    mc = config["program"]["model_config"]
    sa = config["sa_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["num_kv_heads"],
            mc["head_dim"], mc["moe_intermediate_size"],
            mc["experts_per_tok"], mc["rope_theta"], mc["vocab_size"]) == \
        (H, N, KV, D, F, 8, 10000000, V)
    assert (mc["index_topk"], mc["index_heads"], mc["index_head_dim"],
            mc["index_block"]) == (sa["topk"], sa["indexer_num_heads"],
                                   sa["indexer_head_dim"],
                                   sa["q_chunk_size"])
    assert mc["mixers"] == ["gqa"] * 8 and mc["first_k_dense"] == 0
    assert mc["qk_norm"] and mc["gqa_rope"]
    assert (mc["router_score"], mc["shared_intermediate_size"],
            mc["n_routed_experts"], mc["num_experts"]) == \
        ("softmax", 0, 128, 16)
    pe = config["program"]["paged_engine_config"]
    traffic = bench_run.load_json(ROOT, "benchmark", "traffic",
                                  "closed16_ctx32k.json")
    assert pe["slots"] == traffic["clients"] == 16
    assert pe["max_len"] == config["max_position_embeddings"] == \
        traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
    assert (traffic["prompt_len"], traffic["output_len"]) == (
        {"dist": "loguniform", "lo": 8192, "hi": 32768},
        {"dist": "uniform", "lo": 128, "hi": 512})
    assert traffic["greedy"] and traffic["sizes"] == 48 \
        and traffic["order_bands"] == 8 and traffic["check_requests"] == 6
    assert config["draw_vocab"] == V
    ladder = pe["prefill_buckets"]
    assert ladder[-1] == traffic["prompt_len"]["hi"]
    assert not [b for b in ladder if b % sa["q_chunk_size"]]


def test_new_entries_are_there_and_list_their_cells():
    """What the PR that brought the cell appended is present; nothing is
    pinned to the end of a list or held with `==` on a set a later PR may
    extend."""
    bench = bench_json()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert CONFIG in configs and CELL in cells
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "closed16_ctx32k", 1)
    assert "2048" in cell["why"] and len(cell["why"]) <= 200
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {"setup_s", "ttft_p50_ms"}
    assert "serve_tokens_per_s" not in reported
    listing = {name for name, m in metrics.items()
               if CELL in m.get("workloads", [])}
    assert listing >= {
        "ttft_mean_ms", "prefill_ms_p50",
        "queue_wait_ms_p50", "prefill_device_ms_p50", "prefill_host_ms_p50",
        "sched_bookkeeping_ms_per_step", "host_starved_pct",
        "starved_decode_call_ms_per_step", "starved_prefill_call_ms_per_step",
        "starved_sched_ms_per_step", "starved_outside_step_ms_per_step",
        "decode_ahead_pct", "moe_local_pair_share_pct", "moe_experts_hit_pct",
        "moe_expert_load_max_over_mean", *READERS}
    assert {metrics[n]["moves"] for n in listing} == {"ttft_p50_ms"}
    # a window ends 11-14 requests here, and the 95th percentile's reader
    # wants 20 first tokens: it reads nothing in this cell
    assert CELL not in metrics["ttft_p95_ms"]["workloads"]
    for name, source, layer in zip(
            READERS, ("host_clock", "device_trace", "program_counter"),
            ("whole serve step", "engine", "KV manager")):
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("%", "higher", source, layer)
        assert CELL in m["workloads"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    # the readers that want full causal pairs or a row a token stay with
    # the cells they can read
    for name in ("serve_mfu.hybrid", "decode_hbm_roofline",
                 "latent_read_share_pct"):
        assert CELL not in metrics[name]["workloads"]


# ------------------------------------------------ the readers, off records

def spans(name, attrs_list):
    return [{"name": f"serving::{name}", "ts": 10 + i, "dur": 1,
             "span_id": str(i), "parent": None, "attrs": a}
            for i, a in enumerate(attrs_list)]


def recorded_logs():
    step = {"dsa_rows_indexed": 8 * 16 * 17_000,
            "dsa_rows_selected": 8 * 16 * 2048}
    short = dict(step, dsa_rows_selected=8 * (15 * 2048 + 1000))
    # the wait of a call: the host's selected rows beside the rows the
    # program says it read (one wait fewer than steps: the last in flight)
    wait = {"dsa_rows_selected": 8 * 16 * 2048,
            "dsa_kv_rows_read": 8 * 16 * 2048}
    wait_short = dict(wait, dsa_rows_selected=8 * (15 * 2048 + 1000))
    return {"prefill": spans("prefill", [
                {"dsa_index_pairs": 8 * 200_000_000,
                 "dsa_selected_pairs": 8 * 30_000_000},
                {"dsa_index_pairs": 8 * 100_000_000,
                 "dsa_selected_pairs": 8 * 20_000_000},
                {"pool_donated": 1}]),
            "decode_step": spans("decode_step", [step] * 900 + [short] * 100),
            "decode.wait": spans("decode.wait",
                                 [wait] * 899 + [wait_short] * 100)}


def record_of(config):
    return {"config": config, "device_kind": "TPU v5 lite",
            "window_s": 30.0,
            "counters": {"slots": 16, "prompt_tokens": 400_000,
                         "output_tokens_processed": 5000,
                         "output_tokens": 5030,
                         "moe": {"decode": {"spans": 1000,
                                            "moe_pairs_local": 16_000,
                                            "moe_experts_hit": 100_000},
                                 "prefill": {"spans": 25,
                                             "moe_pairs_local": 400_000}}}}


def test_serve_mfu_dsa_on_a_recorded_record(config, monkeypatch):
    from benchmark.harness import program_counters
    logs = recorded_logs()
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: logs.get(name))
    got = reader("serve_mfu.dsa")(record_of(config), None)
    index = 8 * 300_000_000 + 1000 * 8 * 16 * 17_000
    selected = 8 * 50_000_000 + 900 * 8 * 16 * 2048 \
        + 100 * 8 * (15 * 2048 + 1000)
    flops = mf.serve_flops(config, 405_000, 5030, 416_000, index, selected)
    assert got == pytest.approx(100 * flops / (30 * 197e12))
    assert 1 < got < 10


def test_decode_readers_on_a_recorded_record(config, monkeypatch):
    from benchmark.harness import program_counters
    logs = recorded_logs()
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: logs.get(name))
    trace = {"module_s": {"jit__decode_fn(1)": [0.010, 0.011, 0.012],
                          "jit_prefill_fn(2)": [0.3]}}
    selected = (900 * 8 * 16 * 2048 + 100 * 8 * (15 * 2048 + 1000)) / 1000
    least = mf.decode_step_bytes(config, 16, 100, 8 * 16 * 17_000, selected)
    got = reader("decode_hbm_roofline.dsa")(record_of(config), trace)
    assert got == pytest.approx(100 * least / 819e9 / 0.011)
    assert 0 < got <= 100
    assert reader("decode_hbm_roofline.dsa")(record_of(config), None) is None
    waited = (899 * 8 * 16 * 2048 + 100 * 8 * (15 * 2048 + 1000)) / 999
    assert reader("dsa_kv_read_share_pct")(record_of(config), None) == \
        pytest.approx(100 * waited / (8 * 16 * 2048))
    # a decode that read every slot's dense view says so in its count
    dense = dict(logs["decode.wait"][0]["attrs"],
                 dsa_kv_rows_read=8 * 16 * 33_280)
    logs["decode.wait"] = spans("decode.wait", [dense] * 10)
    assert reader("dsa_kv_read_share_pct")(record_of(config), None) == \
        pytest.approx(100 * 2048 / 33_280)


@pytest.mark.parametrize("what", ["no_log", "no_attrs"])
@pytest.mark.parametrize("name", READERS)
def test_new_readers_with_nothing_to_read(config, monkeypatch, name, what):
    """No span log, or the parent's spans without the new attrs: no number,
    no exception."""
    from benchmark.harness import program_counters
    logs = None if what == "no_log" else {
        "prefill": spans("prefill", [{"pool_donated": 1}]),
        "decode_step": spans("decode_step", [{"active": 16}]),
        "decode.wait": spans("decode.wait", [{"pool_donated": 1}])}
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: (logs or {}).get(name))
    trace = {"module_s": {"jit__decode_fn": [0.01]}}
    assert reader(name)(record_of(config), trace) is None


@pytest.mark.parametrize("other", ["nemotron3_nano_ep8_share",
                                   "cerebras_gpt_1p3b"])
@pytest.mark.parametrize("name", READERS[:2])
def test_new_readers_leave_other_configurations_alone(monkeypatch, name,
                                                      other):
    from benchmark.harness import program_counters
    logs = recorded_logs()
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: logs.get(name))
    config = bench_run.load_json(ROOT, "benchmark", "configs",
                                 f"{other}.json")
    trace = {"module_s": {"jit__decode_fn": [0.01]}}
    assert reader(name)(record_of(config), trace) is None


# ------------------------------------------------------- the cell, tiny

def test_cell_is_correct_at_its_tiny_sizes(capsys):
    """Untraced: the traced run of every cell is test_bench_cells.py's, and
    two files tracing one cell at once share its trace directory."""
    result, _, _ = run_cell(capsys, CELL, trace=0, seconds=1.5)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 5
    assert set(result["metrics"]) == {"setup_s", "ttft_p50_ms"}
    checks = result["checks"]
    assert checks["requests_failed"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0


def test_cell_is_not_correct_with_a_token_altered(capsys):
    from benchmark.harness import faults
    result, _, _ = run_cell(capsys, CELL, seconds=1.0,
                            hooks={"wrap_engine": faults.alter_token})
    assert result["correct"] is False
    assert result["checks"]["served_logit_gap_mean"]["value"] > \
        result["checks"]["served_logit_gap_mean"]["limit"]


def test_tiny_limits_lie_between_the_program_and_the_fp8_control():
    """Fixed requests served greedily by the tiny program, compared with the
    reference: the program's gaps under both tiny limits, the fp8 control's
    over the mean's."""
    from benchmark.harness import correctness
    from benchmark.loops import closed_loop_model as loop
    from paddle_tpu.serving import PagedEngineConfig, PagedGenerationEngine
    _, _, config, _ = bench_run.load_cell(CELL, tiny=True)
    seed = 2147483783
    model = loop.build_model(config, seed)
    eng = PagedGenerationEngine(model, PagedEngineConfig(
        **config["program"]["paged_engine_config"]))
    rng = np.random.default_rng(4)
    samples = []
    for n in (40, 23, 31):
        prompt = rng.integers(0, config["draw_vocab"], n).tolist()
        tokens = [eng.prefill(0, prompt)]
        for _ in range(11):
            tokens.append(int(eng.decode()[0]))
        samples.append((prompt, tokens))
        eng.reset_slot(0)
    gaps, n_tokens = loop.served_gap_readings(
        config, seed, samples, modes=("float32", "fp8_e4m3"))
    assert n_tokens == 36
    limits = correctness.load_limits(CELL, tiny=True)
    for name in ("served_logit_gap", "served_logit_gap_mean"):
        assert gaps["float32"][name] <= limits[name]
    # the mean separates; the extreme of 36 tokens need not
    assert gaps["fp8_e4m3"]["served_logit_gap_mean"] > \
        limits["served_logit_gap_mean"]
    assert gaps["fp8_e4m3"]["served_logit_gap"] > \
        4 * gaps["float32"]["served_logit_gap"]
