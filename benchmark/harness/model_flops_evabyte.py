"""Operations and bytes EvaByte's language model needs on this chip (layers of
an EVA attention mixer and a SwiGLU feed-forward; a head of `num_pred_heads`
heads of which the served step reads the first), from shapes.

As in the siblings: what the mathematics requires. Bucket padding is not
counted, nor the prediction heads the served step does not run, nor the
dense view's rows that no query sees. Bytes are the least a decode step must
move: each weight it multiplies read once, each ring row and each summary
row a query sees read once, the token's row and the open chunk's summary
written.
"""
from .weights_evabyte import F32_LEAVES, head_dim, layer_shapes

_NOT_MULTIPLIED = ("norm1", "norm2", "phi", "mu")


def _count(shape):
    n = 1
    for s in shape:
        n *= s
    return n


def _item(config, what):
    return 2 if config["dtype"][what] == "bfloat16" else 4


def layer_counts(config):
    """{"matmul": parameters every token is multiplied by, "bytes": bytes of
    every leaf of a layer as stored}."""
    shapes = layer_shapes(config)
    return {"matmul": sum(_count(s) for leaf, s in shapes.items()
                          if leaf not in _NOT_MULTIPLIED),
            "bytes": sum(_count(s) * (4 if leaf in F32_LEAVES
                                      else _item(config, "param"))
                         for leaf, s in shapes.items())}


def row_bytes(config):
    """One cached row: a token's [k, v] or a chunk's [kbar, vbar] of every
    head, one layer."""
    return _item(config, "kv") * 2 * config["num_attention_heads"] \
        * head_dim(config)


def blocks_for(n_tokens, config, block_size=16):
    """Pool blocks a slot of `n_tokens` tokens holds: the ring as far as
    the first window has grown, and a summary row a chunk begun."""
    chunks = -(-n_tokens // config["chunk_size"])
    return min(-(-n_tokens // block_size),
               config["window_size"] // block_size) \
        + -(-chunks // block_size)


def visible_rows(p, config):
    """(ring rows, summary rows) the query at position `p` scores: the
    tokens of its own window up to itself, and every chunk of every closed
    window."""
    w = config["window_size"]
    return p % w + 1, p // w * (w // config["chunk_size"])


def prefill_pairs(n, config):
    """(query, visible row) pairs of a prompt of `n` tokens from position
    0: the sum of `visible_rows` over it, in closed form."""
    w = config["window_size"]
    full, rest = divmod(n, w)
    return full * w * (w + 1) // 2 + rest * (rest + 1) // 2 \
        + (w // config["chunk_size"]) * (w * full * (full - 1) // 2
                                         + rest * full)


def attn_flops_per_pair(config):
    """One layer, one (query, visible row) pair: the score and the value
    sum over d, 2 each per head."""
    return 4 * config["num_attention_heads"] * head_dim(config)


def serve_flops(config, processed_tokens, output_tokens, visible_pairs):
    """Model FLOPs of serving on this chip: every processed token (prompt or
    output) runs every layer's matmuls (2 per parameter); every output token
    needs the first head's row of logits; attention scores the rows each
    token sees (`visible_pairs`, a layer's worth)."""
    layers = config["num_hidden_layers"]
    return 2 * layers * layer_counts(config)["matmul"] * processed_tokens \
        + 2 * config["hidden_size"] * config["vocab_size"] * output_tokens \
        + layers * attn_flops_per_pair(config) * visible_pairs


def decode_step_bytes_by_part(config, window_rows, summary_rows, slots=None):
    """Least bytes one decode step moves, by what they are: `weights` (every
    layer's, once), `window` (the ring rows the slots' queries see, a
    layer's worth `window_rows`, in every layer), `summary` (the summary
    rows they see), `written` (a token's row and a chunk's summary a slot a
    layer), `other` (the first head's slice, an embedding row a slot, the
    last norm)."""
    layers, row = config["num_hidden_layers"], row_bytes(config)
    slots = config["program"]["paged_engine_config"]["slots"] \
        if slots is None else slots
    item, h = _item(config, "param"), config["hidden_size"]
    return {"weights": layers * layer_counts(config)["bytes"],
            "window": layers * row * window_rows,
            "summary": layers * row * summary_rows,
            "written": layers * 2 * row * slots,
            "other": item * h * config["vocab_size"] + item * h * slots
            + 4 * h}


def decode_step_bytes(config, window_rows, summary_rows, slots=None):
    """The sum of `decode_step_bytes_by_part`."""
    return sum(decode_step_bytes_by_part(
        config, window_rows, summary_rows, slots).values())
