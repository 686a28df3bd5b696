"""Share of the held experts (over all expert layers) that at least one live
token chose in a decode step, mean over the window's steps: the share of the
expert weights a step has to read. `moe_experts_hit` on
`serving::decode.wait` over experts held x expert layers."""


def read(record, trace):
    moe = (record["counters"].get("moe") or {}).get("decode")
    config = record["config"]
    if not moe:
        return None
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return 100.0 * moe["moe_experts_hit"] / (
        moe["spans"] * config["num_experts"] * layers)
