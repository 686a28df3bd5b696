"""The EvaByte configuration's arithmetic against hand counts (ISSUE 38's),
its configuration file against the catalog row, the entries PR 38 appended,
the four readers it added on recorded records and with nothing to read, and
the cell itself at its tiny sizes: correct as served, not correct under the
fp8 control or with a token altered."""
import json
import os

import pytest

from bench_helpers import ROOT, bench_json, run_cell

from benchmark import run as bench_run
from benchmark.harness import model_flops_evabyte as mf
from benchmark.harness import weights_evabyte as we

CELL = "evabyte_serve_closed24_ctx12k"
CONFIG = "evabyte_6p5b_pp4_stage"
H, F, V, P = 4096, 11008, 320, 8
ATTN = 4 * H * H                         # q, k, v, o
FFN = 3 * H * F                          # SwiGLU
SMALL = 2 * 32 * 128 + 2 * H             # phi, mu, two norms
ROW = 2 * 32 * 128 * 2                   # a cached row in bfloat16: 16 KB
READERS = ("serve_mfu.eva", "decode_hbm_roofline.eva",
           "decode_eva_byte_share_pct", "eva_summary_row_share_pct")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


def reader(name):
    return bench_run.load_module("layer_metrics", name).read


def test_parameter_counts_match_hand_counts(config):
    """ISSUE 38: a layer 67.1 M + 135.3 M = 202.4 M parameters = 404.8 MB;
    the embedding 1.3 M, the head 8 x 320 x 4096 = 10.5 M; eight layers and
    the rest 3.25 GB; the whole model 12.95 GB in its 32 layers."""
    counts = mf.layer_counts(config)
    assert counts["matmul"] == ATTN + FFN
    assert (round(ATTN / 1e6, 1), round(FFN / 1e6, 1),
            round((ATTN + FFN) / 1e6, 1)) == (67.1, 135.3, 202.4)
    assert counts["bytes"] == 2 * (ATTN + FFN) + 4 * SMALL
    assert round(counts["bytes"] / 1e6, 1) == 404.8
    shapes = we.layer_shapes(config)
    assert sum(mf._count(s) for s in shapes.values()) == ATTN + FFN + SMALL
    top = we.global_shapes(config)
    assert top == {"embed": (V, H), "norm_f": (H,), "head": (H, P * V)}
    assert (round(V * H / 1e6, 1), round(P * V * H / 1e6, 1)) == (1.3, 10.5)
    stored = 8 * counts["bytes"] + 2 * (V * H + P * V * H) + 4 * H
    assert round(stored / 1e9, 2) == 3.26
    assert round(32 * counts["bytes"] / 1e9, 2) == 12.95


def test_blocks_visible_rows_and_pairs_match_hand_counts(config):
    assert mf.row_bytes(config) == ROW == 16384
    assert mf.blocks_for(12800, config) == 128 + 50 == 178
    assert [mf.blocks_for(n, config) for n in (1, 2048, 2049)] == \
        [2, 136, 137]
    # the pool: (24 x 178 + 1) blocks of 16 rows, 8 layers: 8.96 GB, of
    # which the rings are 6.4 GB and all the summaries 2.5 GB
    pool = (24 * 178 + 1) * 16 * ROW * 8
    assert round(pool / 1e9, 2) == 8.96
    assert round(24 * 128 * 16 * ROW * 8 / 1e9, 1) == 6.4
    assert round(24 * 50 * 16 * ROW * 8 / 1e9, 1) == 2.5
    # kept one row a token: 39 GB
    assert round(24 * 12800 * ROW * 8 / 1e9) == 40
    assert [mf.visible_rows(p, config) for p in (0, 2047, 2048, 12799)] == \
        [(1, 0), (2048, 0), (1, 128), (512, 768)]
    assert mf.prefill_pairs(1, config) == 1
    assert mf.prefill_pairs(2048, config) == 2048 * 2049 // 2
    assert mf.prefill_pairs(2049, config) == 2048 * 2049 // 2 + 1 + 128
    assert mf.prefill_pairs(5000, config) == sum(
        sum(mf.visible_rows(p, config)) for p in range(5000))


def test_serve_flops_and_decode_bytes_match_hand_counts(config):
    assert mf.attn_flops_per_pair(config) == 4 * H
    got = mf.serve_flops(config, processed_tokens=10, output_tokens=3,
                         visible_pairs=100)
    assert got == 2 * 8 * (ATTN + FFN) * 10 + 2 * V * H * 3 \
        + 8 * 4 * H * 100
    parts = mf.decode_step_bytes_by_part(config, window_rows=24000,
                                         summary_rows=8400)
    assert set(parts) == {"weights", "window", "summary", "written",
                          "other"}
    assert parts["weights"] == 8 * (2 * (ATTN + FFN) + 4 * SMALL)
    assert parts["window"] == 8 * ROW * 24000
    assert parts["summary"] == 8 * ROW * 8400
    assert parts["written"] == 8 * 2 * ROW * 24
    assert parts["other"] == 2 * H * V + 2 * H * 24 + 4 * H
    assert mf.decode_step_bytes(config, 24000, 8400) == sum(parts.values())
    # ISSUE 38's means: ~1 000 ring rows and ~350 summaries a slot are
    # 4.2 GB of rows against 3.25 GB of weights: the rows lead, ~56 %
    rows = parts["window"] + parts["summary"]
    assert round(rows / 1e9, 1) == 4.2
    assert round(parts["weights"] / 1e9, 2) == 3.24
    assert 55 < 100 * rows / sum(parts.values()) < 58
    other = mf.decode_step_bytes_by_part(config, 10, 0, slots=3)
    assert other["written"] == 8 * 2 * ROW * 3


def test_configuration_file_keeps_the_catalog_numbers(config):
    """Every key of the catalog row under the same key, unless the key is
    in `reduced`; `reduced` is depth and served length alone; the file
    states the four-stage deployment, the departures and what was
    assumed."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guide")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    assert row["name"] == "EvaByte"
    entry = {c["name"]: c for c in bench_json()["configs"]}[CONFIG]
    assert entry["reduced"] == config["reduced"] == \
        ["num_hidden_layers", "max_position_embeddings"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (8, 12800)
    for key in ("deployment", "departures", "assumed", "tiny", "published"):
        assert config[key]
    assert config["deployment"]["chips_per_layer"] == 1
    assert config["deployment"]["stages"] == 4
    assert "four times" in config["deployment"]["batch"]
    mc = config["program"]["model_config"]
    assert (mc["hidden_size"], mc["num_heads"], mc["head_dim"],
            mc["intermediate_size"], mc["eva_window"], mc["eva_chunk"],
            mc["vocab_size"], mc["num_pred_heads"], mc["rope_theta"]) == \
        (4096, 32, 128, 11008, 2048, 16, 320, 8, 100000)
    assert mc["mixers"] == ["eva"] * 8 and mc["first_k_dense"] == 8
    assert mc["norm_unit_offset"] is True
    assert mc["rms_norm_eps"] == config["rms_norm_eps"] == 1e-5
    pe = config["program"]["paged_engine_config"]
    traffic = bench_run.load_json(ROOT, "benchmark", "traffic",
                                  "closed24_ctx12k.json")
    assert pe["slots"] == traffic["clients"] == 24
    assert pe["max_len"] == config["max_position_embeddings"] == \
        traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
    assert (traffic["prompt_len"], traffic["output_len"]) == (
        {"dist": "loguniform", "lo": 2048, "hi": 12288},
        {"dist": "uniform", "lo": 128, "hi": 512})
    assert traffic["greedy"] and traffic["sizes"] == 48 \
        and traffic["order_bands"] == 8 and traffic["check_requests"] == 6
    assert config["draw_vocab"] == 320
    # the ladder keeps the prompts' median and quartiles off bucket edges,
    # and every bucket is whole chunks
    lo, hi = traffic["prompt_len"]["lo"], traffic["prompt_len"]["hi"]
    ladder = pe["prefill_buckets"]
    for q in (0.25, 0.5, 0.75):
        length = lo * (hi / lo) ** q
        bucket = min(b for b in ladder if b >= length)
        below = max(b for b in ladder if b < length)
        assert below * 1.05 < length < bucket / 1.05, (q, length)
    assert ladder[-1] == hi and not [b for b in ladder if b % 16]


def test_new_entries_are_there_and_list_their_cells():
    """What PR 38 added is present; nothing is pinned to the end of a list
    or held with `==` on a set a later PR may extend."""
    bench = bench_json()
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert CONFIG in configs and CELL in cells
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "closed24_ctx12k", 1)
    assert "host share" in cell["why"] and len(cell["why"]) <= 200
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {"setup_s", "ttft_p50_ms"}
    listing = {name for name, m in metrics.items()
               if CELL in m.get("workloads", [])}
    assert listing >= {
        "ttft_p95_ms", "ttft_mean_ms", "prefill_ms_p50",
        "prefill_device_ms_p50", "prefill_host_ms_p50", "queue_wait_ms_p50",
        "latent_read_share_pct", "sched_bookkeeping_ms_per_step",
        "host_starved_pct", "starved_decode_call_ms_per_step",
        "starved_prefill_call_ms_per_step", "starved_sched_ms_per_step",
        "starved_outside_step_ms_per_step", *READERS}
    assert {metrics[n]["moves"] for n in listing} == {"ttft_p50_ms"}
    for name, source, layer in zip(
            READERS, ("host_clock", "device_trace", "program_counter",
                      "program_counter"),
            ("whole serve step", "engine", "KV manager", "KV manager")):
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            ("%", "higher", source, layer)
        assert CELL in m["workloads"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", f"{name}.py"))
    # the readers that want full causal pairs and a row a token stay with
    # the cells they can read
    assert CELL not in metrics["serve_mfu.hybrid"]["workloads"]
    assert CELL not in metrics["decode_hbm_roofline"]["workloads"]


# ------------------------------------------------ the readers, off records

def step_rows(attrs_list, decoded=True):
    return {"steps": [{"dur_ns": 1, "attrs": a, "self_ns": {},
                       "total_ns": {"decode_step": 1} if decoded else {}}
                      for a in attrs_list],
            "spans": {}, "requests": []}


def spans(name, attrs_list):
    return [{"name": f"serving::{name}", "ts": 10 + i, "dur": 1,
             "span_id": str(i), "parent": None, "attrs": a}
            for i, a in enumerate(attrs_list)]


def test_serve_mfu_eva_on_a_recorded_record(config, monkeypatch):
    from benchmark.harness import program_counters
    logs = {"prefill": spans("prefill", [{"eva_pairs": 9_000_000},
                                         {"eva_pairs": 3_000_000},
                                         {"pool_donated": 1}]),
            "decode.wait": spans("decode.wait", [
                {"latent_rows_held": 8 * 30000}] * 600)}
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: logs.get(name))
    record = {"config": config, "device_kind": "TPU v5 lite",
              "window_s": 30.0,
              "counters": {"prompt_tokens": 200000,
                           "output_tokens_processed": 14000,
                           "output_tokens": 14040}}
    got = reader("serve_mfu.eva")(record, None)
    flops = mf.serve_flops(config, 214000, 14040,
                           12_000_000 + 600 * 30000)
    assert got == pytest.approx(100 * flops / (30 * 197e12))
    assert 5 < got < 20


def test_decode_readers_on_a_recorded_record(config, monkeypatch):
    from benchmark.harness import program_spans
    rows = step_rows([
        {"window_rows_held": 24000, "summary_rows_held": 8000},
        {"window_rows_held": 26000, "summary_rows_held": 9000},
        {"active_slots": 3}])                   # a step without the gauges
    rows["steps"] += step_rows([{"window_rows_held": 1,
                                 "summary_rows_held": 1}],
                               decoded=False)["steps"]
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    record = {"config": config, "device_kind": "TPU v5 lite",
              "counters": {"slots": 24}}
    trace = {"module_s": {"jit_decode_fn(1)": [0.040, 0.042, 0.044],
                          "jit_prefill_fn(2)": [0.3]}}
    least = mf.decode_step_bytes(config, 25000, 8500, slots=24)
    assert reader("decode_hbm_roofline.eva")(record, trace) == \
        pytest.approx(100 * least / 819e9 / 0.042)
    assert reader("decode_hbm_roofline.eva")(record, None) is None
    parts = mf.decode_step_bytes_by_part(config, 25000, 8500, slots=24)
    want = 100 * (parts["window"] + parts["summary"]) / sum(parts.values())
    assert reader("decode_eva_byte_share_pct")(record, None) == \
        pytest.approx(want)
    assert 50 < want < 65
    # every step that carries the two, whether or not it decoded
    assert reader("eva_summary_row_share_pct")(record, None) == \
        pytest.approx(100 * 17001 / (50001 + 17001))


def nothing_record(config):
    return {"config": config, "device_kind": "TPU v5 lite",
            "window_s": 30.0,
            "counters": {"slots": 24, "prompt_tokens": 1,
                         "output_tokens_processed": 1, "output_tokens": 1}}


@pytest.mark.parametrize("what", ["no_log", "no_gauges"])
@pytest.mark.parametrize("name", READERS)
def test_new_readers_with_nothing_to_read(config, monkeypatch, name, what):
    """No span log, or the parent's spans without the new attrs: no number,
    no exception."""
    from benchmark.harness import program_counters, program_spans
    rows = None if what == "no_log" else step_rows(
        [{"active_slots": 2, "kv_tokens_held": 9}])
    logs = None if what == "no_log" else spans("prefill",
                                               [{"pool_donated": 1}])
    monkeypatch.setattr(program_spans, "read", lambda record: rows)
    monkeypatch.setattr(program_counters, "window_spans",
                        lambda record, name: logs)
    trace = {"module_s": {"jit_decode_fn": [0.04]}}
    assert reader(name)(nothing_record(config), trace) is None


@pytest.mark.parametrize("other", ["nemotron3_nano_ep8_share",
                                   "cerebras_gpt_1p3b"])
@pytest.mark.parametrize("name", READERS[:3])
def test_new_readers_leave_other_configurations_alone(monkeypatch, name,
                                                      other):
    """A configuration whose arithmetic has no window (or none at all) gets
    no number from the three readers that need it, whatever the spans
    say."""
    from benchmark.harness import program_counters, program_spans
    monkeypatch.setattr(program_spans, "read", lambda record: step_rows(
        [{"window_rows_held": 5, "summary_rows_held": 1}]))
    monkeypatch.setattr(
        program_counters, "window_spans", lambda record, name: spans(
            name, [{"eva_pairs": 5, "latent_rows_held": 8}]))
    config = bench_run.load_json(ROOT, "benchmark", "configs",
                                 f"{other}.json")
    trace = {"module_s": {"jit_decode_fn": [0.04]}}
    assert reader(name)(nothing_record(config), trace) is None


# ------------------------------------------------------- the cell, tiny

def test_cell_is_correct_at_its_tiny_sizes_and_reads_its_share(capsys):
    result, out, _ = run_cell(capsys, CELL, trace=1, seconds=1.5)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 5
    metrics = result["metrics"]
    for name in ("latent_read_share_pct", "decode_eva_byte_share_pct",
                 "eva_summary_row_share_pct", "ttft_p95_ms",
                 "prefill_host_ms_p50", "host_starved_pct"):
        assert name in metrics, name
        assert metrics[name]["value"] is not None
    for name in ("latent_read_share_pct", "decode_eva_byte_share_pct",
                 "eva_summary_row_share_pct"):
        assert 0 < metrics[name]["value"] <= 100
    checks = result["checks"]
    assert checks["requests_failed"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    untraced, _, _ = run_cell(capsys, CELL, trace=0, seconds=1.0, seed=9)
    assert set(untraced["metrics"]) == {"setup_s", "ttft_p50_ms"}
    assert untraced["correct"] is True


def test_cell_is_not_correct_with_a_token_altered(capsys):
    from benchmark.harness import faults
    result, _, _ = run_cell(capsys, CELL, seconds=1.0,
                            hooks={"wrap_engine": faults.alter_token})
    assert result["correct"] is False
    assert result["checks"]["served_logit_gap_mean"]["value"] > \
        result["checks"]["served_logit_gap_mean"]["limit"]


def test_fp8_control_fails_the_tiny_limits(capsys):
    """The reference with fp8 matmul operands at the same positions, put in
    the program's place, reads over the tiny limit of the mean; the program
    under both."""
    from benchmark.harness import correctness
    _, cell, cfg, traffic = bench_run.load_cell(CELL, tiny=True)
    ctx = bench_run.make_ctx(cell, cfg, traffic, 11, 1.0, True,
                             control_mode="fp8_e4m3")
    record = bench_run.load_module("loops", traffic["kind"]).run(ctx)
    capsys.readouterr()
    limits = correctness.load_limits(CELL, tiny=True)
    assert all(c["ok"] for c in record["checks"])
    for name in ("served_logit_gap", "served_logit_gap_mean"):
        assert record["readings"][name] <= limits[name]
    # the mean separates; the extreme of some twenty tokens need not
    control = record["control_readings"]
    assert control["served_logit_gap_mean"] > limits["served_logit_gap_mean"]
    assert control["served_logit_gap"] > 4 * record["readings"][
        "served_logit_gap"]
