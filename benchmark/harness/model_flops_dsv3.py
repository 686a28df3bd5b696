"""Operations and bytes DeepSeek-V3's main model needs on this chip (MLA
behind a query bottleneck in every layer, dense and expert feed-forwards, a
share of the experts), from shapes.

As in model_flops_hybrid.py: what the mathematics requires. Bucket padding
is not counted, nor an expert applied to a token that did not choose it, nor
the absorbed form's wider products (a token's latent row goes through
`W_kvb` once either way; per context position the expanded form is the
cheaper way to the same numbers). Bytes are the least a decode step must
move: each weight it multiplies read once (the query bottleneck's two
matrices among them), an expert's weights only if some token chose it, each
resident latent row of each layer read once.
"""
from .weights_dsv3 import (EXPERT_LEAVES, F32_LEAVES, layer_kinds,
                           layer_shapes)

# leaves no matmul multiplies a token's activations by
_NOT_MULTIPLIED = ("norm1", "norm2", "qnorm", "cnorm", "router_bias")


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def layer_counts(config, kinds):
    """{"matmul": parameters every token is multiplied by (the router and
    the shared expert among them), "expert": parameters of ONE routed
    expert, "bytes": bytes of every non-expert leaf as stored}."""
    shapes = layer_shapes(config, kinds)
    itemsize = 2 if config["dtype"]["param"] == "bfloat16" else 4
    matmul = sum(_count(s) for leaf, s in shapes.items()
                 if leaf not in _NOT_MULTIPLIED + EXPERT_LEAVES)
    expert = sum(_count(s[1:]) for leaf, s in shapes.items()
                 if leaf in EXPERT_LEAVES)
    stored = sum(_count(s) * (4 if leaf in F32_LEAVES else itemsize)
                 for leaf, s in shapes.items() if leaf not in EXPERT_LEAVES)
    return {"matmul": matmul, "expert": expert, "bytes": stored}


def mla_flops_per_pair(config):
    """One MLA layer, one (token, context position) pair, expanded form:
    the score over nope + rope dims and the value sum, 2 each per head."""
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def serve_flops(config, processed_tokens, output_tokens, context_pairs,
                local_pairs):
    """Model FLOPs of serving on this chip: every processed token (prompt or
    output) runs every layer's non-expert matmuls (2 per parameter); each
    token-expert pick that fell on a held expert runs that expert
    (`local_pairs`, summed over the expert layers); every output token needs
    a row of logits over the vocabulary slice; every layer's attention reads
    the context behind each token (`context_pairs`, per layer)."""
    kinds = layer_kinds(config)
    counts = [layer_counts(config, k) for k in kinds]
    expert = max(c["expert"] for c in counts)
    return 2 * sum(c["matmul"] for c in counts) * processed_tokens \
        + 2 * expert * local_pairs \
        + 2 * config["hidden_size"] * config["vocab_size"] * output_tokens \
        + len(kinds) * mla_flops_per_pair(config) * context_pairs


def latent_bytes_per_token(config):
    """One token's cached rows over all the layers."""
    item = 2 if config["dtype"]["kv"] == "bfloat16" else 4
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return item * width * len(layer_kinds(config))


def decode_step_bytes(config, slots, experts_hit, latent_tokens):
    """Least bytes one decode step of `slots` slots moves: the non-expert
    weights once, each routed expert some token chose once (`experts_hit`,
    summed over the expert layers), the head's slice once, an embedding row
    a slot, every layer's resident latent rows read (`latent_tokens` over
    all slots)."""
    counts = [layer_counts(config, k) for k in layer_kinds(config)]
    item = 2 if config["dtype"]["param"] == "bfloat16" else 4
    expert = max(c["expert"] for c in counts) * item
    h = config["hidden_size"]
    return sum(c["bytes"] for c in counts) \
        + expert * experts_hit \
        + item * h * config["vocab_size"] + item * h * slots + 4 * h \
        + latent_bytes_per_token(config) * latent_tokens
