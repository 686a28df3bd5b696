"""Share of the decode steps' token-expert picks that fell on an expert this
chip holds: `moe_pairs_local / moe_pairs_total` summed over the window's
`serving::decode.wait` spans. With 128 of 512 experts held and even routing
it reads 25; the rest of the picks are the absent chips' work."""


def read(record, trace):
    moe = (record["counters"].get("moe") or {}).get("decode")
    if not moe or not moe["moe_pairs_total"]:
        return None
    return 100.0 * moe["moe_pairs_local"] / moe["moe_pairs_total"]
