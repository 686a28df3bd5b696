"""How full the referenced KV blocks are: tokens resident in the active
slots over the room of the blocks in use (`kv_tokens_held /
(kv_blocks_in_use x block_size)`), the mean over the window's steps.
Reserved against in use: the rest is the slots' last partial blocks and
what the prefix cache keeps of requests that have ended."""
from benchmark.harness import program_spans


def read(record, trace):
    try:
        block = record["config"]["program"]["paged_engine_config"][
            "block_size"]
    except (KeyError, TypeError):
        return None
    return program_spans.step_mean_pct(
        record, lambda s: s["attrs"].get("kv_tokens_held", 0)
        / (s["attrs"]["kv_blocks_in_use"] * block)
        if s["attrs"].get("kv_blocks_in_use") else None)
