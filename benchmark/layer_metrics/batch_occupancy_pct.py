"""Mean share of the decode batch that holds a request: `active_slots /
slots` from the `serving::step` span's attrs, over the window's steps that
decoded. Below 100 % the decode executable computes rows nobody reads."""
from benchmark.harness import program_spans


def read(record, trace):
    return program_spans.step_mean_pct(
        record, lambda s: s["attrs"]["active_slots"] / s["attrs"]["slots"]
        if "decode_step" in s["total_ns"] and s["attrs"].get("slots")
        else None)
