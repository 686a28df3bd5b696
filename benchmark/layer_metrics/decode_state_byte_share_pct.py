"""Share of the least bytes of a decode step that are the state-space
blocks': the live slots' state and convolution tail read and written, and
those blocks' weights, over the whole of `harness.flops`'
`decode_step_bytes_by_part` (state, expert, attention, other), from the
means of the window's decode steps: `state_slots_in_use` and
`kv_tokens_held` on `serving::step`, `moe_experts_hit` on
`serving::decode.wait`. It says whether the state-space mechanism leads in
the cell that was added for it: under 50 the experts' or the attention's
bytes do. None from a configuration whose arithmetic has no such split, or
from a program that counts none of the three."""
import importlib
import statistics

from benchmark.harness import program_spans


def read(record, trace):
    flops_of = record["config"].get("harness", {}).get("flops")
    by_part = getattr(importlib.import_module(
        f"benchmark.harness.{flops_of}"), "decode_step_bytes_by_part",
        None) if flops_of else None
    moe = (record["counters"].get("moe") or {}).get("decode")
    rows = program_spans.read(record)
    if by_part is None or not moe or not rows:
        return None
    steps = [s["attrs"] for s in rows["steps"]
             if "decode_step" in s["total_ns"]
             and "state_slots_in_use" in s["attrs"]
             and "kv_tokens_held" in s["attrs"]]
    if not steps:
        return None
    parts = by_part(
        record["config"],
        statistics.mean(a["state_slots_in_use"] for a in steps),
        moe["moe_experts_hit"] / moe["spans"],
        statistics.mean(a["kv_tokens_held"] for a in steps))
    return 100.0 * parts["state"] / sum(parts.values())
