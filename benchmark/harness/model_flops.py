"""Operations and bytes the algorithm needs, computed from shapes.

Model FLOPs count what the mathematics requires: recomputation (remat, the
flash backward's internal recompute) is NOT counted, bucket padding is NOT
counted, and a causal attention counts the lower triangle only.
"""


def sizes(config):
    """The handful of sizes every formula needs, from a configuration file's
    top-level (published-name) keys."""
    h = config["n_embd"]
    return {"hidden": h, "layers": config["n_layer"], "heads": config["n_head"],
            "ffn": config.get("n_inner") or 4 * h,
            "vocab": config["vocab_size"],
            "positions": config["n_positions"]}


def block_params(config):
    """Parameters of the transformer blocks (matmul weights and biases and
    the two layer norms), without embeddings."""
    s = sizes(config)
    h, f = s["hidden"], s["ffn"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return s["layers"] * per_layer


def total_params(config):
    s = sizes(config)
    return block_params(config) + s["vocab"] * s["hidden"] \
        + s["positions"] * s["hidden"] + 2 * s["hidden"]


def train_flops_per_token(config, seq):
    """6*N + 6*L*S*H per token: forward 2 FLOPs per parameter per token,
    backward twice that (6N, N = every parameter the tied head multiplies:
    blocks + the vocab matrix once), plus causal attention: QK^T and PV are
    2*S*H each per token per layer forward at full S, halved by causality
    (2*L*S*H forward), times three for forward + backward = 6*L*S*H.
    The same arithmetic as the program's bench.py:501-504."""
    s = sizes(config)
    n = block_params(config) + s["vocab"] * s["hidden"]
    return 6 * n + 6 * s["layers"] * seq * s["hidden"]


def serve_flops(config, prompt_tokens, output_tokens, context_sum):
    """Model FLOPs of serving: every processed token (prompt or output) runs
    the blocks once (2 FLOPs per block parameter); every OUTPUT token needs
    one row of logits (2*V*H); attention reads the context behind each
    token: QK^T and PV are 2*H*ctx each per layer, so 4*L*H per (token,
    context position) pair. `context_sum` is that number of pairs."""
    s = sizes(config)
    processed = prompt_tokens + output_tokens
    return 2 * block_params(config) * processed \
        + 2 * s["vocab"] * s["hidden"] * output_tokens \
        + 4 * s["layers"] * s["hidden"] * context_sum


def causal_pairs(length, start=0):
    """Number of (query, key) pairs when positions start..start+length-1
    each attend to everything up to and including themselves."""
    return length * start + length * (length + 1) // 2


def flash_flops_bytes(batch, heads, seq, head_dim, itemsize=2, causal=True):
    """Causal flash attention forward + backward at (B, H, S, D), what the
    algorithm needs: forward QK^T and PV (2 matmuls), backward dV, dP, dQ,
    dK (4 matmuls) and the recompute of QK^T that the flash backward cannot
    avoid is NOT counted (it is the kernel's cost, not the algorithm's).
    Each matmul is 2*S*S*D per head, halved by causality.
    Bytes: the minimum traffic — read q, k, v, do and o once, write o, dq,
    dk, dv once (the S x S scores never touch HBM)."""
    tri = 0.5 if causal else 1.0
    per_matmul = 2 * seq * seq * head_dim * tri
    fwd = 2 * per_matmul * batch * heads
    bwd = 4 * per_matmul * batch * heads
    tensor = batch * heads * seq * head_dim * itemsize
    fwd_bytes = 4 * tensor                # q, k, v in; o out
    bwd_bytes = 8 * tensor                # q, k, v, o, do in; dq, dk, dv out
    return {"fwd_flops": fwd, "bwd_flops": bwd,
            "fwd_bytes": fwd_bytes, "bwd_bytes": bwd_bytes}
