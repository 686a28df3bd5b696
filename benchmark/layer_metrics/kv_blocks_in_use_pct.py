"""Mean share of the KV pool's blocks that are referenced (request tables
and the prefix cache): `kv_blocks_in_use / kv_blocks_total` from the
`serving::step` span's attrs as each step of the window ends."""
from benchmark.harness import program_spans


def read(record, trace):
    return program_spans.step_mean_pct(
        record, lambda s: s["attrs"]["kv_blocks_in_use"]
        / s["attrs"]["kv_blocks_total"]
        if s["attrs"].get("kv_blocks_total") else None)
