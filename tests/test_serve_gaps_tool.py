"""`tools/serve_gaps.py` on a small made-up profile: one device plane and one
host thread with two `serving::step`s (a prefill and a decode, then a decode
alone), the device plane's clock one nanosecond early. Known stamps give the
device's idle time by group and by innermost span, the stamps' own split
beside it, the launch and the fetch tail, and the longest step."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "serve_gaps", os.path.join(ROOT, "tools", "serve_gaps.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# (name, start ns, duration ns) on the host thread; the stamps of
# tests/bench_suite/test_bench_host_gaps.py with the benchmark's own spans
# around them: in flight are [117, 139], [156, 188] and [215, 237]
HOST = [
    ("bench:step", 99, 102),
    ("serving::step", 100, 100),
    ("serving::retire", 101, 4), ("serving::retire.slot", 102, 2),
    ("serving::refill", 106, 40),
    ("serving::prefill.admit", 107, 4),
    ("serving::prefill", 112, 28),
    ("serving::prefill.upload", 113, 3),
    ("serving::prefill.dispatch", 117, 3),
    ("serving::prefill.wait", 122, 17),
    ("serving::prefill.publish", 141, 3),
    ("serving::grow", 146, 2),
    ("serving::decode.prepare", 149, 1),
    ("serving::decode_step", 150, 40),
    ("serving::decode.upload", 151, 5),
    ("serving::decode.dispatch", 156, 10),
    ("serving::decode.wait", 168, 20),
    ("serving::emit", 190, 4), ("serving::bookkeeping", 194, 3),
    ("serving::step.counts", 197, 2),
    ("bench:harvest", 202, 5),
    ("bench:step", 209, 32),
    ("serving::step", 210, 30),
    ("serving::decode.prepare", 211, 1),
    ("serving::decode_step", 212, 26),
    ("serving::decode.upload", 213, 2),
    ("serving::decode.dispatch", 215, 3),
    ("serving::decode.wait", 218, 19),
    ("serving::decode.commit", 238, 1),
    ("python_frame", 100, 140),
]
# the device: the prefill's module, an upload's own small program, two
# decode modules, the second beginning one nanosecond BEFORE the start of
# its dispatch (215): the device plane's clock is at least that early
DEVICE = [("jit_prefill_fn(1)", 119, 18),
          ("jit_convert_element_type(2)", 152, 1),
          ("jit__decode_fn(3)", 160, 26), ("jit__decode_fn(3)", 214, 21)]


def plane(plane_id, name, lines, scale):
    names = sorted({n for events in lines.values() for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {plane_id} name: "{name}"']
    for i, (line, events) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0')
        out += [f"    events {{ metadata_id: {ids[n]} "
                f"offset_ps: {a * 1000 * scale} "
                f"duration_ps: {d * 1000 * scale} }}" for n, a, d in events]
        out.append("  }")
    out += [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items()]
    return "\n".join(out + ["}"])


def profile_of(host=HOST, device=DEVICE, scale=1):
    """The made-up profile, a stamp `scale` nanoseconds."""
    from jax.profiler import ProfileData
    planes = [plane(2, "/host:CPU", {"python3": host}, scale)]
    if device:
        planes.insert(0, plane(1, "/device:TPU:0", {
            "XLA Modules": device, "XLA Ops": device}, scale))
    return ProfileData.from_text_proto("\n".join(planes))


S = "serving::"


def test_idle_by_group_and_by_span_on_the_shifted_clock(tool):
    got = tool.report(profile_of())
    assert got["window_ns"] == (100, 240) and got["steps"] == 2
    assert got["clock_shift_ns"] == 1
    assert (got["busy_ns"], got["idle_ns"]) == (66, 74)
    assert got["idle_by_group"] == {
        "decode_call": 14, "prefill_call": 13, "sched": 26,
        "outside_step": 10, "in_call": 11}
    assert got["idle_by_span"] == {
        S + "step": 6, S + "decode.commit": 1, S + "retire": 2,
        S + "retire.slot": 2,
        S + "refill": 5, S + "prefill.admit": 4, S + "prefill": 3,
        S + "prefill.upload": 3, S + "prefill.dispatch": 3,
        S + "prefill.wait": 1, S + "prefill.publish": 3, S + "grow": 2,
        S + "decode.prepare": 2, S + "decode_step": 5,
        S + "decode.upload": 6, S + "decode.dispatch": 5,
        S + "decode.wait": 2, S + "emit": 4, S + "bookkeeping": 3,
        S + "step.counts": 2, "bench:step": 2, "bench:harvest": 5,
        "none": 3}


def test_the_stamps_split_stands_beside_it(tool):
    got = tool.report(profile_of())
    assert got["in_flight_ns"] == 22 + 32 + 22
    assert got["starved_by_group"] == {
        "decode_call": 15, "prefill_call": 13, "sched": 26,
        "outside_step": 10, "in_call": 0}
    lo, hi = got["window_ns"]
    assert sum(got["starved_by_group"].values()) + got["in_flight_ns"] \
        == hi - lo
    # the upload's own small program ran while nothing was in flight: the
    # one nanosecond by which starved time is not idle time
    assert got["busy_while_starved_ns"] == 1
    assert got["starved_by_span"][S + "decode.upload"] == 7
    assert got["idle_by_span"][S + "decode.upload"] == 6
    assert hi - lo - got["in_flight_ns"] - got["busy_while_starved_ns"] \
        <= got["idle_ns"]


def test_launch_and_fetch_tail_a_call(tool):
    got = tool.report(profile_of())
    assert got["launch_ns"] == {"prefill": [3], "decode": [5, 0]}
    assert got["tail_ns"] == {"prefill": [1], "decode": [1, 1]}
    # a call's in-flight time less its module needs no common clock
    assert got["launch_ns"]["decode"][1] + got["tail_ns"]["decode"][1] \
        == (237 - 215) - 21


def test_the_longest_step_with_its_phases(tool):
    step = tool.report(profile_of())["longest_step"]
    assert (step["dur_ns"], step["at_ns"], step["idle_ns"]) == (100, 0, 55)
    assert sum(step["phases"].values()) == 100
    assert step["phases"][S + "prefill.wait"] == 17
    assert step["phases"][S + "decode_step"] == 5
    assert step["phases"][S + "step"] == 4


def test_a_call_without_its_children_leaves_the_stamps_column_empty(tool):
    host = [s for s in HOST if s[0] != S + "prefill.wait"]
    got = tool.report(profile_of(host))
    assert got["in_flight_ns"] is None and got["starved_by_group"] is None
    assert got["idle_ns"] == 74               # the device's side still stands
    assert got["launch_ns"]["prefill"] == []
    assert "no in-flight split" in tool.render(got)


def test_what_is_printed(tool):
    """A stamp 0.1 ms, so that the table's milliseconds have digits."""
    text = tool.render(tool.report(profile_of(scale=100_000)))
    assert "window 0.0140 s of 2 whole serving::step(s)" in text
    assert "idle 0.0074 s = 52.86 %" in text
    assert "device plane moved 0.100 ms later" in text
    assert "starved 0.0064 s = 45.71 %" in text
    assert "device busy while starved 0.100 ms" in text
    rows = {line.split()[0]: line.split()[1:] for line in text.splitlines()
            if line.split() and line.split()[0] in tool.GROUPS}
    assert {k: [float(v) for v in row] for k, row in rows.items()} == {
        "decode_call": [0.7, 0.75], "prefill_call": [0.65, 0.65],
        "sched": [1.3, 1.3], "outside_step": [0.5, 0.5]}
    assert "decode: 2 calls; launch (module start - start of " \
        "decode.dispatch) median 0.250 ms; fetch tail (end of decode.wait " \
        "- module end) median 0.100 ms; the two together (no common clock " \
        "needed) 0.350 ms" in text
    assert "longest serving::step: 10.000 ms, 0.0 ms into the window, " \
        "device idle 5.500 ms of it" in text


def test_no_device_plane_or_no_step_reads_as_none(tool):
    assert tool.report(profile_of(device=[])) is None
    assert tool.report(profile_of(
        [s for s in HOST if s[0] != S + "step"])) is None
    assert tool.main([]) == 2
