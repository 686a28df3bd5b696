"""Operations and bytes Keye-VL-2.0's language model needs on this chip
(layers of grouped-query attention behind a learned selection of positions,
and a share of softmax-routed experts), from shapes and from what the
program counted of its positions.

As in the siblings: what the mathematics requires. Bucket padding is not
counted, nor an expert applied to a token that did not choose it, nor the
masked pairs of a dense pass over the selection: the indexer scores each
(query, earlier position) pair once (`dsa_index_pairs` of a prefill,
`dsa_rows_indexed` of a decode step), and attention reads each selected pair
once (`dsa_selected_pairs`, `dsa_rows_selected`). Bytes are the least a
decode step must move: each weight it multiplies read once, an expert's
weights only if some token chose it, each indexer key a query scores read
once, each selected K/V row read once, the token's row and key written.
"""
from .weights_keye import EXPERT_LEAVES, F32_LEAVES, layer_shapes, sizes

# leaves no matmul multiplies a token's activations by
_NOT_MULTIPLIED = ("norm1", "norm2", "q_norm", "k_norm", "k_idx_norm",
                   "k_idx_bias")


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def _item(config, what):
    return 2 if config["dtype"][what] == "bfloat16" else 4


def layer_counts(config):
    """{"matmul": parameters every token is multiplied by (the router among
    them), "expert": parameters of ONE routed expert, "bytes": bytes of
    every non-expert leaf as stored}."""
    shapes = layer_shapes(config)
    return {"matmul": sum(_count(s) for leaf, s in shapes.items()
                          if leaf not in _NOT_MULTIPLIED + EXPERT_LEAVES),
            "expert": sum(_count(s[1:]) for leaf, s in shapes.items()
                          if leaf in EXPERT_LEAVES),
            "bytes": sum(_count(s) * (4 if leaf in F32_LEAVES
                                      else _item(config, "param"))
                         for leaf, s in shapes.items()
                         if leaf not in EXPERT_LEAVES)}


def index_flops_per_pair(config):
    """One layer, one (query, scored position) pair: the m indexer heads'
    dot products over e (2 each) and their weighted sum (2 each)."""
    _, _, _, m, e = sizes(config)
    return m * (2 * e + 2)


def attn_flops_per_pair(config):
    """One layer, one (query, selected position) pair: the score and the
    value sum over d, 2 each per query head."""
    n, _, d, _, _ = sizes(config)
    return 4 * n * d


def serve_flops(config, processed_tokens, output_tokens, local_pairs,
                index_pairs, selected_pairs):
    """Model FLOPs of serving on this chip: every processed token (prompt or
    output) runs every layer's non-expert matmuls (2 per parameter); each
    token-expert pick that fell on a held expert runs that expert
    (`local_pairs`, summed over the layers); every output token needs a row
    of logits over the vocabulary slice; the indexer scores `index_pairs`
    and attention reads `selected_pairs` (both summed over the layers)."""
    c = layer_counts(config)
    return 2 * config["num_hidden_layers"] * c["matmul"] * processed_tokens \
        + 2 * c["expert"] * local_pairs \
        + 2 * config["hidden_size"] * config["vocab_size"] * output_tokens \
        + index_flops_per_pair(config) * index_pairs \
        + attn_flops_per_pair(config) * selected_pairs


def decode_step_bytes_by_part(config, slots, experts_hit, rows_indexed,
                              rows_selected):
    """Least bytes one decode step of `slots` slots moves, by what they are:
    `weights` (every layer's non-expert leaves once), `experts` (each held
    expert some token chose, `experts_hit` summed over the layers), `index`
    (each indexer key scored, `rows_indexed` summed over the layers),
    `selected` (each selected K/V row, `rows_selected` likewise), `other`
    (the rows and keys written, the head's slice, an embedding row a
    slot, the last norm)."""
    n, kv, d, _, e = sizes(config)
    item, h, layers = _item(config, "param"), config["hidden_size"], \
        config["num_hidden_layers"]
    row, key = 2 * kv * d * _item(config, "kv"), e * _item(config, "kv")
    c = layer_counts(config)
    return {"weights": layers * c["bytes"],
            "experts": c["expert"] * item * experts_hit,
            "index": key * rows_indexed,
            "selected": row * rows_selected,
            "other": layers * slots * (row + key)
            + item * h * config["vocab_size"] + item * h * slots + 4 * h}


def decode_step_bytes(config, slots, experts_hit, rows_indexed,
                      rows_selected):
    """The sum of `decode_step_bytes_by_part`."""
    return sum(decode_step_bytes_by_part(
        config, slots, experts_hit, rows_indexed, rows_selected).values())
