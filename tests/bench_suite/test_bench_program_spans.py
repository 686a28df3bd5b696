"""The readers PR 26 added: `harness/program_spans.py` over the program's
span log (rows, the window, overflow, a program without the log), the seven
program-span metrics in a `--cpu-tiny --trace 1` run of the serve cell, the
program's spans inside the harness's own in a kept trace, and the two flash
roofline readers on a slice of a real trace whose kernels carry names."""
import glob
import importlib.util
import os
import sys

import pytest

from bench_helpers import ROOT, bench_json, run_cell

from benchmark.harness import (kernel_names, model_flops, peaks,
                               program_spans, trace_reduce)

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = "cgpt1p3b_serve_closed8"
TRAIN = "gpt2m_train_b8"
SPAN_METRICS = ["queue_wait_ms_p50", "batch_occupancy_pct",
                "prefill_share_pct", "kv_blocks_in_use_pct",
                "kv_block_fill_pct", "decode_upload_ms_p50",
                "decode_wait_ms_p50"]
TRACE_METRICS = ["flash_fwd_roofline", "flash_bwd_roofline"]


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the entries

def test_benchmark_json_gained_nine_entries_at_the_end_and_nothing_else():
    per_layer = bench_json()["per_layer"]
    assert [m["name"] for m in per_layer[-9:]] == SPAN_METRICS + TRACE_METRICS
    assert len(per_layer) == 21
    for m in per_layer[-9:]:
        assert m["workloads"] == [TRAIN if m["name"] in TRACE_METRICS
                                  else SERVE]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    assert {m["layer"] for m in per_layer[-9:]} == \
        {"scheduler", "KV manager", "engine", "flash kernels"}


# ----------------------------------------------------- rows from made-up spans

def span(name, ts, dur, sid, parent=None, attrs=None):
    return {"name": "serving::" + name, "ts": ts, "dur": dur,
            "span_id": sid, "parent": parent, "attrs": attrs}


MADE_UP = [
    span("queue", 90, 20, "q1", attrs={"request_id": 1}),
    span("step", 100, 100, "s1", attrs={"active_slots": 2, "slots": 4}),
    span("retire", 101, 4, "a", "s1"),
    span("refill", 106, 40, "b", "s1"),
    span("prefill", 110, 30, "c", "b", {"request_id": 1}),
    span("decode_step", 150, 40, "d", "s1"),
    span("decode.upload", 151, 5, "e", "d"),
    span("decode.dispatch", 156, 10, "f", "d"),
    span("decode.wait", 166, 20, "g", "d"),
    span("step", 300, 10, "s2", attrs={"active_slots": 0, "slots": 4}),
    span("queue", 400, 7, "q2", attrs={"request_id": 2}),   # past the end
    span("step", 400, 10, "s3"),
]


def test_rows_charge_each_span_what_its_children_do_not_cover():
    rows = program_spans.rows_of(MADE_UP, 80, 350)
    assert len(rows["steps"]) == 2
    first = rows["steps"][0]
    assert first["self_ns"] == {
        "step": 100 - 4 - 40 - 40, "retire": 4, "refill": 10, "prefill": 30,
        "decode_step": 5, "decode.upload": 5, "decode.dispatch": 10,
        "decode.wait": 20}
    assert sum(first["self_ns"].values()) == first["dur_ns"] == 100
    assert first["total_ns"]["refill"] == 40
    assert rows["spans"]["decode.wait"] == [20]
    assert rows["spans"]["step"] == [100, 10]
    assert rows["requests"] == [{"request_id": 1, "queue_ns": 20}]


def test_a_span_that_starts_outside_the_window_is_not_in_it():
    rows = program_spans.rows_of(MADE_UP, 150, 450)
    assert [s["dur_ns"] for s in rows["steps"]] == [10, 10]
    assert "decode.wait" not in rows["spans"]
    assert rows["requests"] == [{"request_id": 2, "queue_ns": 7}]


# -------------------------------------------- the window, overflow, no log

class Log:
    """What `paddle_tpu.profiler.span_log()` hands out, made by hand."""

    def __init__(self, spans, whole=True):
        self.spans, self.whole, self.appended = spans, whole, len(spans)

    def window(self, start_ns, end_ns):
        if not self.whole:
            return None
        return [s for s in self.spans if start_ns <= s["ts"] < end_ns]


def record_for(monkeypatch, log):
    """A record whose window is [80, 350) ns on a harness started at 0."""
    monkeypatch.setattr(program_spans, "_harness_t0", lambda: 0.0)
    monkeypatch.setattr(program_spans, "_log", lambda: log)
    monkeypatch.setattr(program_spans, "_cache", (None, None))
    return {"end_to_end": {"setup_s": 80e-9}, "window_s": 270e-9,
            "config": {"program": {"paged_engine_config":
                                   {"block_size": 16}}}}


def test_readers_on_made_up_steps(monkeypatch):
    steps = [dict(s, attrs={"active_slots": 2, "slots": 4,
                            "kv_blocks_in_use": 10, "kv_blocks_total": 40,
                            "kv_tokens_held": 80})
             if s["span_id"] == "s1" else s for s in MADE_UP]
    record = record_for(monkeypatch, Log(steps))
    assert reader("queue_wait_ms_p50")(record, None) == \
        pytest.approx(20e-6)
    assert reader("batch_occupancy_pct")(record, None) == 50.0
    assert reader("prefill_share_pct")(record, None) == \
        pytest.approx(100 * 30 / 110)
    assert reader("kv_blocks_in_use_pct")(record, None) == 25.0
    assert reader("kv_block_fill_pct")(record, None) == 50.0
    assert reader("decode_upload_ms_p50")(record, None) == \
        pytest.approx(5e-6)
    assert reader("decode_wait_ms_p50")(record, None) == \
        pytest.approx(20e-6)


@pytest.mark.parametrize("name", SPAN_METRICS)
@pytest.mark.parametrize("why", ["overflow", "no log in the program",
                                 "no window in the record",
                                 "nothing in the window"])
def test_reader_returns_none_where_there_is_nothing_whole_to_read(
        monkeypatch, name, why):
    log = {"overflow": Log(MADE_UP, whole=False),
           "no log in the program": None,
           "nothing in the window": Log([])}.get(why, Log(MADE_UP))
    record = record_for(monkeypatch, log)
    if why == "no window in the record":
        del record["window_s"]
    assert reader(name)(record, None) is None


def test_overflow_of_the_programs_own_log_reads_as_none(monkeypatch):
    """The real SpanLog, small: once the window's start is overwritten the
    helper hands out nothing, and says something again when it is whole."""
    from paddle_tpu import profiler
    from paddle_tpu.observability.flight_recorder import SpanLog
    small = SpanLog(capacity=8)
    monkeypatch.setattr(profiler._tracer, "log", small)
    monkeypatch.setattr(program_spans, "_cache", (None, None))
    monkeypatch.setattr(program_spans, "_harness_t0", lambda: 0.0)
    import time
    t0 = time.perf_counter()
    for _ in range(3):
        with profiler.RecordEvent("serving::step", attrs={"slots": 1}):
            with profiler.RecordEvent("serving::decode_step"):
                with profiler.RecordEvent("serving::decode.wait"):
                    pass
    record = {"end_to_end": {"setup_s": t0}, "window_s": 5.0}
    assert small.dropped == 1
    assert program_spans.read(record) is None
    assert reader("decode_wait_ms_p50")(record, None) is None
    later = {"end_to_end": {"setup_s": time.perf_counter()}, "window_s": 5.0}
    with profiler.RecordEvent("serving::step", attrs={"slots": 1}):
        pass
    assert len(program_spans.read(later)["steps"]) == 1


def test_a_program_without_the_log_reads_as_none(monkeypatch):
    """The parent of PR 26 has no `span_log`: nothing is read, nothing
    raises."""
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "span_log")
    monkeypatch.setattr(program_spans, "_cache", (None, None))
    assert program_spans.read({"end_to_end": {"setup_s": 0.0},
                               "window_s": 1.0}) is None


def test_harness_t0_is_run_pys_own():
    from benchmark import run
    assert program_spans._harness_t0() == run._T0
    assert program_spans.window_ns({"end_to_end": {"setup_s": 2.0},
                                    "window_s": 3.0}) == \
        (int((run._T0 + 2.0) * 1e9), int((run._T0 + 5.0) * 1e9))


# ------------------------------------------------ the serve cell, CPU, tiny

_traced = {}


def traced_serve(capsys, tmp_path_factory):
    """One `--cpu-tiny --trace 1 --keep-trace` run of the serve cell."""
    if not _traced:
        from benchmark import run
        import json
        keep = str(tmp_path_factory.mktemp("kept_trace"))
        capsys.readouterr()
        assert run.main(["--workload", SERVE, "--seed", "3000000019",
                         "--seconds", "2", "--trace", "1", "--cpu-tiny",
                         "--keep-trace", keep]) == 0
        out = capsys.readouterr().out
        _traced["line"] = json.loads(out.strip().splitlines()[-1])
        _traced["details"] = json.loads(out.strip().splitlines()[-2])
        _traced["xplane"] = glob.glob(os.path.join(keep, "*.xplane.pb"))[0]
    return _traced


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_cpu_tiny_traced_serve_run_prints_the_metric(capsys,
                                                     tmp_path_factory, name):
    line = traced_serve(capsys, tmp_path_factory)["line"]
    assert line["correct"] is True
    metric = line["metrics"][name]
    declared = {m["name"]: m for m in bench_json()["per_layer"]}[name]
    assert metric["unit"] == declared["unit"]
    assert metric["value"] > 0
    if declared["unit"] == "%":
        assert metric["value"] <= 100.0


def test_inside_readings_agree_with_the_outside_wrappers(capsys,
                                                         tmp_path_factory):
    """The cross-check PERF.md reports from the chip, at tiny size: what
    the wrappers on the engine's instance time from outside, the spans
    time from inside."""
    run = traced_serve(capsys, tmp_path_factory)
    m = {k: v["value"] for k, v in run["line"]["metrics"].items()}
    assert m["decode_upload_ms_p50"] + m["decode_wait_ms_p50"] \
        < m["decode_step_ms_p50"]
    assert m["batch_occupancy_pct"] > 50
    assert m["queue_wait_ms_p50"] < m["ttft_mean_ms"]
    assert m["kv_block_fill_pct"] <= 100


def test_kept_trace_holds_the_programs_spans_inside_the_harnesss(
        capsys, tmp_path_factory):
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(
        traced_serve(capsys, tmp_path_factory)["xplane"])
    bench_steps = [(a, b) for n, a, b in trace_reduce.host_spans(profile)
                   if n == "step"]
    inside = trace_reduce.host_spans(profile, prefix="serving::")
    names = {n.split("#")[0] for n, _, _ in inside}
    assert {"step", "retire", "refill", "grow", "decode_step",
            "decode.upload", "decode.dispatch", "decode.wait", "emit",
            "bookkeeping", "prefill"} <= names
    steps = [(a, b) for n, a, b in inside if n.split("#")[0] == "step"]
    assert len(steps) >= len(bench_steps) - 1 > 3
    for a, b in steps:             # one clock: each lies in a bench:step
        assert any(x <= a and b <= y for x, y in bench_steps)
    waits = [(a, b) for n, a, b in inside
             if n.split("#")[0] == "decode.wait"]
    for a, b in waits:
        assert any(x <= a and b <= y for x, y in steps)


# -------------------------------------- kernels by name, on a recorded slice

@pytest.fixture(scope="module")
def named():
    from jax.profiler import ProfileData
    with open(os.path.join(
            HERE, "recorded_trace_train_named_slice.textproto")) as f:
        return trace_reduce.summarize(ProfileData.from_text_proto(f.read()))


@pytest.fixture(scope="module")
def unnamed():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE,
                           "recorded_trace_train_slice.textproto")) as f:
        return trace_reduce.summarize(ProfileData.from_text_proto(f.read()))


def test_named_slice_tells_the_three_kernels_apart(named):
    fwd = kernel_names.seconds_of(named, "flash_fwd")
    dq = kernel_names.seconds_of(named, "flash_dq")
    dkv = kernel_names.seconds_of(named, "flash_dkv")
    assert fwd and dq and dkv
    assert fwd + dq + dkv == pytest.approx(
        trace_reduce.time_of(named, trace_reduce.PALLAS))
    assert kernel_names.seconds_of(named, "flash_dq", "flash_dkv") == \
        pytest.approx(dq + dkv)
    assert kernel_names.seconds_of(named, "paged_attn") is None
    assert kernel_names.seconds_of(named, "flash") is None   # whole names


@pytest.mark.parametrize("name,phase,kernels", [
    ("flash_fwd_roofline", "fwd", ("flash_fwd",)),
    ("flash_bwd_roofline", "bwd", ("flash_dq", "flash_dkv"))])
def test_roofline_reader_on_the_named_slice(named, name, phase, kernels):
    shapes = {"batch": 8, "heads": 16, "seq": 1024, "head_dim": 64,
              "itemsize": 2, "layers": 1}
    record = {"shapes": shapes, "device_kind": "TPU v5 lite",
              "trace_steps": 1}
    value = reader(name)(record, named)
    w = model_flops.flash_flops_bytes(8, 16, 1024, 64, 2)
    peak = peaks.peaks_for("TPU v5 lite")
    least = max(w[f"{phase}_flops"] / peak["flops_bf16"],
                w[f"{phase}_bytes"] / peak["hbm_bytes_per_s"])
    spent = kernel_names.seconds_of(named, *kernels)
    assert value == pytest.approx(100 * least / spent)
    assert 0 < value < 100


def test_the_two_rooflines_recombine_to_flash_roofline(named):
    """(least forward + least backward) over (forward + backward time) is
    what `flash_roofline` reads from the same trace."""
    record = {"shapes": {"batch": 8, "heads": 16, "seq": 1024,
                         "head_dim": 64, "itemsize": 2, "layers": 1},
              "device_kind": "TPU v5 lite", "trace_steps": 1}
    fwd = reader("flash_fwd_roofline")(record, named)
    bwd = reader("flash_bwd_roofline")(record, named)
    t_fwd = kernel_names.seconds_of(named, "flash_fwd")
    t_bwd = kernel_names.seconds_of(named, "flash_dq", "flash_dkv")
    whole = reader("flash_roofline")(record, named)
    assert (fwd * t_fwd + bwd * t_bwd) / (t_fwd + t_bwd) == \
        pytest.approx(whole)


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_roofline_reader_finds_nothing_in_a_trace_without_names(unnamed,
                                                                 name):
    """PR 25's slice is the parent's trace: `kernel_metadata={}`."""
    record = {"shapes": {"batch": 8, "heads": 16, "seq": 1024,
                         "head_dim": 64, "itemsize": 2, "layers": 24},
              "device_kind": "TPU v5 lite", "trace_steps": 6}
    assert trace_reduce.time_of(unnamed, trace_reduce.PALLAS)
    assert reader(name)(record, unnamed) is None
    assert reader(name)(record, None) is None


def test_cut_trace_keeps_what_the_reduction_reads():
    """tools/cut_trace.py on PR 25's slice: a narrower slice of the same
    trace, clipped, shifted to 0, still reducible."""
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from google.protobuf import text_format
    from jax.profiler import ProfileData
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    spec = importlib.util.spec_from_file_location(
        "cut_trace", os.path.join(ROOT, "benchmark", "tools",
                                  "cut_trace.py"))
    cut_trace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut_trace)
    space = xplane_pb2.XSpace()
    with open(os.path.join(HERE,
                           "recorded_trace_train_slice.textproto")) as f:
        text_format.Parse(f.read(), space)
    out = cut_trace.cut(space, 0.5, 1.5, 600)
    summary = trace_reduce.summarize(ProfileData.from_text_proto(
        text_format.MessageToString(out)))
    assert summary["window_s"] == pytest.approx(1e-3)
    assert 0 < summary["busy_s"] <= summary["window_s"]
    assert max(len(name) for name in summary["op_s"]) == 600
    assert trace_reduce.time_of(summary, trace_reduce.PALLAS)
    spans = trace_reduce.host_spans(ProfileData.from_text_proto(
        text_format.MessageToString(out)))
    assert [s[0] for s in spans] == ["put_batch", "step"]
