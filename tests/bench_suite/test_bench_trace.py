"""trace_reduce.py on a small recorded trace kept beside this test: busy
union, idle share, time by kernel, nesting, gap attribution, short names."""
import os

import numpy as np
import pytest

from bench_helpers import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE,
                           "recorded_trace_train_slice.textproto")) as f:
        return ProfileData.from_text_proto(f.read())


def test_busy_is_the_union_of_nested_and_adjacent_events(profile):
    events = trace_reduce.events_of(profile)["/device:TPU:0"]
    assert len(events) == 214
    # an independent count: occupancy of a 1 ns grid
    lo = min(s for _, s, _ in events)
    hi = max(e for _, _, e in events)
    grid = np.zeros(int(hi - lo) + 1, bool)
    for _, s, e in events:
        grid[int(round(s - lo)):int(round(e - lo))] = True
    summary = trace_reduce.summarize(profile)
    assert summary["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert summary["busy_s"] == pytest.approx(grid.sum() / 1e9, rel=1e-3)
    assert 0 < summary["busy_s"] < summary["window_s"]
    idle_pct = 100 * (1 - summary["busy_s"] / summary["window_s"])
    assert idle_pct == pytest.approx(1.124, abs=0.01)


def test_self_times_add_up_to_busy_and_parents_are_not_counted_twice(profile):
    summary = trace_reduce.summarize(profile)
    assert sum(summary["op_s"].values()) == pytest.approx(summary["busy_s"])
    whiles = {k: v for k, v in summary["op_s"].items()
              if k.startswith("%while")}
    events = trace_reduce.events_of(profile)["/device:TPU:0"]
    spans = {n: e - s for n, s, e in events if n.startswith("%while")}
    for name, self_s in whiles.items():     # a while is charged its gaps only
        assert self_s < 0.05 * spans[name] / 1e9


def test_pallas_kernels_are_found_by_their_custom_call_target(profile):
    summary = trace_reduce.summarize(profile)
    events = trace_reduce.events_of(profile)["/device:TPU:0"]
    by_hand = sum(e - s for n, s, e in events if trace_reduce.PALLAS in n)
    assert trace_reduce.time_of(summary, trace_reduce.PALLAS) == \
        pytest.approx(by_hand / 1e9) == pytest.approx(0.001044843)
    assert trace_reduce.time_of(summary, "no_such_kernel") is None
    top = summary["device_ops"][0]
    assert top[0] == ("%closed_call.14 pallas "
                      "(bf16[128,1024,64], f32[128,1024,128])")


def test_gap_is_charged_to_the_host_span_over_it(profile):
    summary = trace_reduce.summarize(profile)
    assert [s[0] for s in trace_reduce.host_spans(profile)] == \
        ["put_batch", "step", "put_batch"]
    gaps = dict(summary["idle_gaps"])
    assert gaps["put_batch"] == pytest.approx(2.7583e-05, rel=1e-3)
    assert trace_reduce.attribute_gap((0.0, 1.0), []) == "none"


def test_short_name_and_union_on_made_up_events():
    assert trace_reduce.union_intervals([(0, 5), (1, 2), (5, 7), (9, 10)]) \
        == [(0, 7), (9, 10)]
    assert trace_reduce.self_times(
        [("p", 0, 10e9), ("c", 1e9, 4e9), ("c", 5e9, 6e9)]) == \
        {"p": 6.0, "c": 4.0}
    assert trace_reduce.short_name(
        '%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %p), '
        'kind=kLoop') == "%fusion.7 fusion bf16[8,128]"


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    from jax.profiler import ProfileData
    empty = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    assert trace_reduce.summarize(empty) is None
