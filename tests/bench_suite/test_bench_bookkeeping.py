"""The reader PR 35 added: `sched_bookkeeping_ms_per_step`, the mean
`serving::bookkeeping` span a step of the window, on made-up spans, where
there is nothing whole to read, as an entry of BENCHMARK.json and in a
`--cpu-tiny --trace 1` run of a serve cell."""
import os

import pytest

from bench_helpers import ROOT, bench_json, run_cell

from test_bench_program_spans import Log, reader, record_for, span

NAME = "sched_bookkeeping_ms_per_step"
SERVE_CELLS = ["cgpt1p3b_serve_closed8", "ling3_flash_serve_closed64",
               "dsv3_serve_closed64_ctx4k", "cgpt1p3b_serve_prefill8",
               "nemotron3_nano_serve_closed128"]

TWO_STEPS = [
    span("step", 100, 100, "s1"),
    span("retire", 101, 4, "a", "s1"),
    span("decode_step", 110, 60, "b", "s1"),
    span("bookkeeping", 175, 20, "c", "s1",
         {"ledger_events": 12, "ledger_blocks_walked": 1,
          "ledger_pool_blocks": 513}),
    span("step", 220, 50, "s2"),
    span("bookkeeping", 260, 8, "d", "s2"),
    span("step", 400, 90, "s3"),                  # past the window's end
    span("bookkeeping", 470, 1000, "e", "s3"),
]


def test_mean_bookkeeping_over_the_windows_two_steps(monkeypatch):
    record = record_for(monkeypatch, Log(TWO_STEPS))
    assert reader(NAME)(record, None) == pytest.approx((20 + 8) / 2 * 1e-6)


@pytest.mark.parametrize("why", ["overflow", "no log in the program",
                                 "no window in the record",
                                 "nothing in the window",
                                 "steps without the span"])
def test_none_where_there_is_nothing_whole_to_read(monkeypatch, why):
    log = {"overflow": Log(TWO_STEPS, whole=False),
           "no log in the program": None,
           "nothing in the window": Log([]),
           "steps without the span": Log(
               [s for s in TWO_STEPS if "bookkeeping" not in s["name"]]),
           }.get(why, Log(TWO_STEPS))
    record = record_for(monkeypatch, log)
    if why == "no window in the record":
        del record["window_s"]
    assert reader(NAME)(record, None) is None


def test_the_entry_is_last_lists_the_five_serve_cells_and_has_its_file():
    bench = bench_json()
    entry = bench["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "ttft_p50_ms", "workloads": SERVE_CELLS}
    reports = next(m for m in bench["end_to_end"]
                   if m["name"] == "ttft_p50_ms")["workloads"]
    assert set(SERVE_CELLS) <= set(reports)
    assert {w["name"] for w in bench["workloads"]
            if "serve" in w["name"]} == set(SERVE_CELLS)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       NAME + ".py"))


def test_cpu_tiny_traced_serve_run_prints_it(capsys):
    line, _out, _err = run_cell(capsys, "cgpt1p3b_serve_prefill8", trace=1,
                                seed=3000000023, seconds=2.0)
    assert line["correct"] is True
    metric = line["metrics"][NAME]
    assert metric["unit"] == "ms" and metric["value"] > 0
