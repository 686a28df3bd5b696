"""Operations and bytes Nemotron-H's language model needs on this chip
(blocks of one part: Mamba-2, grouped-query attention, a share of a relu2
expert layer), from shapes.

As in model_flops_hybrid.py: what the mathematics requires. Bucket padding
is not counted, nor an expert applied to a token that did not choose it, nor
the chunked form's extra products (the token-by-token recurrence is the
cheaper way to the same numbers). Bytes are the least a decode step must
move: each weight it multiplies read once, an expert's weights only if some
token chose it, each live slot's state read and written once, each resident
K/V row read once.
"""
from .weights_nemotron_h import (EXPERT_LEAVES, F32_LEAVES, layer_kinds,
                                 layer_shapes, mamba_sizes)

# leaves no matmul multiplies a token's activations by
_NOT_MULTIPLIED = ("norm1", "norm2", "conv_w", "conv_b", "dt_bias", "a_log",
                   "d_skip", "ssm_norm", "router_bias")


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def _item(config, what):
    return 2 if config["dtype"][what] == "bfloat16" else 4


def layer_counts(config, kind):
    """{"matmul": parameters every token is multiplied by (the router and
    the shared expert among them), "expert": parameters of ONE routed
    expert, "bytes": bytes of every non-expert leaf as stored}."""
    shapes = layer_shapes(config, kind)
    matmul = sum(_count(s) for leaf, s in shapes.items()
                 if leaf not in _NOT_MULTIPLIED + EXPERT_LEAVES)
    expert = sum(_count(s[1:]) for leaf, s in shapes.items()
                 if leaf in EXPERT_LEAVES)
    stored = sum(_count(s) * (4 if leaf in F32_LEAVES
                              else _item(config, "param"))
                 for leaf, s in shapes.items() if leaf not in EXPERT_LEAVES)
    return {"matmul": matmul, "expert": expert, "bytes": stored}


def ssm_flops_per_token(config):
    """One Mamba-2 block, one token: per entry of the heads x P x N state
    the decay (1), the rank-one update (2) and the read-out against C (2);
    the convolution's K taps a channel (2 each)."""
    heads, inner, chan = mamba_sizes(config)
    return 5 * inner * config["ssm_state_size"] \
        + 2 * config["conv_kernel"] * chan


def gqa_flops_per_pair(config):
    """One attention block, one (token, context position) pair: the score
    and the value sum over d, 2 each per query head."""
    return 4 * config["num_attention_heads"] * config["head_dim"]


def serve_flops(config, processed_tokens, output_tokens, context_pairs,
                local_pairs):
    """Model FLOPs of serving on this chip: every processed token (prompt or
    output) runs every block's non-expert matmuls (2 per parameter) and the
    state-space blocks' recurrence; each token-expert pick that fell on a
    held expert runs that expert (`local_pairs`, summed over the expert
    blocks); every output token needs a row of logits over the vocabulary
    slice; attention reads the context behind each token (`context_pairs`,
    per attention block)."""
    kinds = layer_kinds(config)
    counts = [layer_counts(config, k) for k in kinds]
    expert = max(c["expert"] for c in counts)
    return 2 * sum(c["matmul"] for c in counts) * processed_tokens \
        + kinds.count("mamba2") * ssm_flops_per_token(config) \
        * processed_tokens \
        + 2 * expert * local_pairs \
        + 2 * config["hidden_size"] * config["vocab_size"] * output_tokens \
        + kinds.count("gqa") * gqa_flops_per_pair(config) * context_pairs


def state_bytes_per_slot(config):
    """One slot's state and convolution tail over the state-space blocks,
    as cached (float32 state; the tail in the cache's type)."""
    heads, inner, chan = mamba_sizes(config)
    per_block = 4 * inner * config["ssm_state_size"] \
        + _item(config, "kv") * (config["conv_kernel"] - 1) * chan
    return per_block * layer_kinds(config).count("mamba2")


def kv_bytes_per_token(config):
    """One token's K/V rows over the attention blocks."""
    return _item(config, "kv") * 2 * config["num_key_value_heads"] \
        * config["head_dim"] * layer_kinds(config).count("gqa")


def decode_step_bytes_by_part(config, slots, experts_hit, latent_tokens):
    """Least bytes one decode step moves, by what they are: `state` (the
    state and tail of `slots` live slots read and written, and the
    state-space blocks' weights), `expert` (each routed expert some token
    chose, `experts_hit` summed over the expert blocks, and the expert
    blocks' router and shared expert), `attention` (the resident K/V rows,
    `latent_tokens` over all slots, and the attention blocks' weights),
    `other` (the head's slice, an embedding row a slot, the last norm)."""
    kinds = layer_kinds(config)
    stored = {k: layer_counts(config, k) for k in set(kinds)}
    item, h = _item(config, "param"), config["hidden_size"]

    def weights(kind):
        return kinds.count(kind) * stored[kind]["bytes"]

    return {
        "state": weights("mamba2")
        + 2 * state_bytes_per_slot(config) * slots,
        "expert": weights("moe")
        + stored["moe"]["expert"] * item * experts_hit,
        "attention": weights("gqa")
        + kv_bytes_per_token(config) * latent_tokens,
        "other": item * h * config["vocab_size"] + item * h * slots + 4 * h}


def decode_step_bytes(config, slots, experts_hit, latent_tokens):
    """Least bytes one decode step of `slots` slots moves: the sum of
    `decode_step_bytes_by_part`."""
    return sum(decode_step_bytes_by_part(
        config, slots, experts_hit, latent_tokens).values())
