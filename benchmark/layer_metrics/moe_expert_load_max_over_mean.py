"""How uneven the routing is in a decode step: the fullest held expert's
tokens (`moe_expert_max`, the maximum over the expert layers) over the mean
tokens of a held expert (`moe_pairs_local` over experts held x expert
layers), both summed over the window's `serving::decode.wait` spans. 1 is
perfectly even; a grouped matmul pads every expert to the fullest."""


def read(record, trace):
    moe = (record["counters"].get("moe") or {}).get("decode")
    config = record["config"]
    if not moe or not moe["moe_pairs_local"]:
        return None
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    mean = moe["moe_pairs_local"] / (config["num_experts"] * layers)
    return moe["moe_expert_max"] / mean
