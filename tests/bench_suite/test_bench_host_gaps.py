"""The readers PR 36 added over `harness/host_gaps.py`: when the engine had
nothing in flight and which span was innermost then, on made-up spans with
known stamps (the four groups and the in-flight time to the nanosecond),
where there is nothing whole to read, as entries of BENCHMARK.json, and in a
`--cpu-tiny --trace 1` run of a GPT cell and of a hybrid cell."""
import os

import pytest

from bench_helpers import ROOT, bench_json, run_cell

from benchmark.harness import host_gaps
from test_bench_program_spans import Log, reader, record_for, span

SERVE_CELLS = {"cgpt1p3b_serve_closed8", "ling3_flash_serve_closed64",
               "dsv3_serve_closed64_ctx4k", "cgpt1p3b_serve_prefill8",
               "nemotron3_nano_serve_closed128"}
PER_STEP = {group: f"starved_{group}_ms_per_step"
            for group in host_gaps.GROUPS}
LAYER_OF = {"host_starved_pct": "engine", "prefill_host_ms_p50": "engine",
            PER_STEP["decode_call"]: "engine",
            PER_STEP["prefill_call"]: "engine",
            PER_STEP["sched"]: "scheduler",
            PER_STEP["outside_step"]: "scheduler"}

# the window of `record_for` is [80, 350): two steps begin in it, the first
# prefills one request and decodes, the second only decodes; in flight are
# [117, 139], [156, 188] and [215, 237]
TWO_STEPS = [
    span("queue", 90, 20, "q1"),
    span("step", 100, 100, "s1"),
    span("retire", 101, 4, "a", "s1"),
    span("retire.slot", 102, 2, "a1", "a"),
    span("refill", 106, 40, "b", "s1"),
    span("prefill.admit", 107, 4, "pa", "b"),
    span("prefill", 112, 28, "p", "b"),
    span("prefill.upload", 113, 3, "pu", "p"),
    span("prefill.dispatch", 117, 3, "pd", "p"),
    span("prefill.wait", 122, 17, "pw", "p"),
    span("prefill.publish", 141, 3, "pp", "b"),
    span("grow", 146, 2, "g", "s1"),
    span("decode.prepare", 149, 1, "dp", "s1"),
    span("decode_step", 150, 40, "d", "s1"),
    span("decode.upload", 151, 5, "du", "d"),
    span("decode.dispatch", 156, 10, "dd", "d"),
    span("decode.wait", 168, 20, "dw", "d"),
    span("emit", 190, 4, "e", "s1"),
    span("bookkeeping", 194, 3, "k", "s1"),
    span("step.counts", 197, 2, "c", "s1"),
    span("step", 210, 30, "s2"),
    span("decode.prepare", 211, 1, "dp2", "s2"),
    span("decode_step", 212, 26, "d2", "s2"),
    span("decode.upload", 213, 2, "du2", "d2"),
    span("decode.dispatch", 215, 3, "dd2", "d2"),
    span("decode.wait", 218, 19, "dw2", "d2"),
    span("decode.commit", 238, 1, "dc2", "s2"),
    span("step", 400, 90, "s3"),                  # past the window's end
    span("decode_step", 410, 70, "d3", "s3"),     # and without children
]
BY_SPAN = {"step": 4 + 2, "decode.commit": 1, "retire": 2,
           "retire.slot": 2, "refill": 5,
           "prefill.admit": 4, "prefill": 3, "prefill.upload": 3,
           "prefill.publish": 3, "grow": 2, "decode.prepare": 1 + 1,
           "decode_step": 3 + 2, "decode.upload": 5 + 2, "emit": 4,
           "bookkeeping": 3, "step.counts": 2, "outside_step": 10}
BY_GROUP = {"decode_call": 15, "prefill_call": 13, "sched": 26,
            "outside_step": 10}
IN_FLIGHT = 22 + 32 + 22


def record_of(monkeypatch, log):
    monkeypatch.setattr(host_gaps, "_cache", (None, None))
    return record_for(monkeypatch, log)


def without(*ids):
    return [s for s in TWO_STEPS if s["span_id"] not in ids]


# ------------------------------------------------ the split, made-up spans

def test_the_four_groups_and_the_in_flight_time_are_the_window():
    got = host_gaps.split(TWO_STEPS, 80, 350)
    assert got["window_ns"] == (100, 240) and got["steps"] == 2
    assert got["in_flight_ns"] == IN_FLIGHT
    assert got["starved_ns"] == BY_GROUP
    assert sum(BY_GROUP.values()) + IN_FLIGHT == 240 - 100
    assert got["prefill_host_ns"] == [4 + 28 + 3 - (139 - 117)]


def test_a_starved_stretch_is_charged_to_the_innermost_span_open():
    got = host_gaps.split(TWO_STEPS, 80, 350)
    assert got["starved_by_span"] == BY_SPAN
    # `retire.slot` lies inside `retire` inside `step`: each its own slice
    assert sum(BY_SPAN.values()) == sum(BY_GROUP.values())
    # the stretch between a dispatch's end and its wait's start is the
    # call's self time and in flight: charged to nobody
    assert BY_SPAN["decode_step"] == (151 - 150) + (190 - 188) \
        + (213 - 212) + (238 - 237)


def test_in_flight_intervals_that_overlap_count_once():
    """A second engine's call on another thread, half over the first's:
    the union, and the slices under both lose what either covers."""
    other = [span("decode_step", 180, 20, "x", None),
             span("decode.dispatch", 181, 2, "xd", "x"),
             span("decode.wait", 184, 12, "xw", "x")]
    got = host_gaps.split(TWO_STEPS + other, 80, 350)
    assert got["in_flight_ns"] == IN_FLIGHT + (196 - 188)
    want = dict(BY_SPAN, decode_step=BY_SPAN["decode_step"] - 2,
                bookkeeping=BY_SPAN["bookkeeping"] - 2)
    del want["emit"]
    assert got["starved_by_span"] == want
    lo, hi = got["window_ns"]
    assert sum(got["starved_ns"].values()) + got["in_flight_ns"] == hi - lo


def test_readers_on_made_up_steps(monkeypatch):
    record = record_of(monkeypatch, Log(TWO_STEPS))
    for group, name in PER_STEP.items():
        assert reader(name)(record, None) == \
            pytest.approx(BY_GROUP[group] / 2 * 1e-6)
    assert reader("host_starved_pct")(record, None) == \
        pytest.approx(100 * 64 / 140)
    assert reader("prefill_host_ms_p50")(record, None) == \
        pytest.approx(13e-6)


def test_a_window_without_a_prefill_says_nothing_of_prefills(monkeypatch):
    decode_only = without("b", "pa", "p", "pu", "pd", "pw", "pp")
    record = record_of(monkeypatch, Log(decode_only))
    assert reader("prefill_host_ms_p50")(record, None) is None
    assert reader(PER_STEP["prefill_call"])(record, None) == 0
    assert reader(PER_STEP["sched"])(record, None) == \
        pytest.approx((26 - 5 + 40) / 2 * 1e-6)


# -------------------------------------------- nothing whole to read: None

@pytest.mark.parametrize("name", sorted(LAYER_OF))
@pytest.mark.parametrize("why", [
    "overflow", "no log in the program", "no window in the record",
    "nothing in the window", "a prefill without its dispatch",
    "a prefill without its wait", "a decode step without its children"])
def test_reader_returns_none_where_there_is_nothing_whole_to_read(
        monkeypatch, name, why):
    log = {"overflow": Log(TWO_STEPS, whole=False),
           "no log in the program": None,
           "nothing in the window": Log([]),
           "a prefill without its dispatch": Log(without("pd")),
           "a prefill without its wait": Log(without("pw")),
           "a decode step without its children": Log(
               without("du2", "dd2", "dw2")),
           }.get(why, Log(TWO_STEPS))
    record = record_of(monkeypatch, log)
    if why == "no window in the record":
        del record["window_s"]
    assert reader(name)(record, None) is None


# ------------------------------------------------------------ the entries

@pytest.mark.parametrize("name", sorted(LAYER_OF))
def test_the_entry_is_present_lists_the_serve_cells_and_has_its_file(name):
    bench = bench_json()
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry["workloads"]) >= SERVE_CELLS
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": "%" if name.endswith("_pct") else "ms",
        "better": "lower", "source": "program_counter",
        "layer": LAYER_OF[name], "moves": "ttft_p50_ms"}
    reports = next(m for m in bench["end_to_end"]
                   if m["name"] == "ttft_p50_ms")["workloads"]
    assert set(entry["workloads"]) <= set(reports)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       name + ".py"))


# ----------------------------------------------------- through the command

@pytest.mark.parametrize("cell", ["cgpt1p3b_serve_prefill8",
                                  "nemotron3_nano_serve_closed128"])
def test_cpu_tiny_traced_serve_run_prints_all_six(capsys, cell):
    line, _out, _err = run_cell(capsys, cell, trace=1, seed=3000000029,
                                seconds=2.0)
    assert line["correct"] is True
    values = {name: line["metrics"][name]["value"] for name in LAYER_OF}
    assert 0 < values["host_starved_pct"] < 100
    assert all(values[name] >= 0 for name in PER_STEP.values())
    assert values[PER_STEP["decode_call"]] > 0
    assert values[PER_STEP["prefill_call"]] > 0
    assert values[PER_STEP["sched"]] > 0
    assert values["prefill_host_ms_p50"] > 0
    assert line["metrics"]["host_starved_pct"]["unit"] == "%"
