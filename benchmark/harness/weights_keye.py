"""Weights from the seed for Keye-VL-2.0's language model (every layer
grouped-query attention behind a learned selection of positions, and an
expert layer of softmax-routed experts): made on the device, one jitted call
a layer, in the type they are served in.

The scheme is the siblings': every (leaf, layer) pair has its own key,
`fold_in(fold_in(root, leaf), layer)` (`weights_hybrid.root_key`,
`weights_dsv3._key_of`), and every routed expert its own key under that,
`fold_in(key, global expert id)`: an expert's values do not depend on which
share of the experts a chip holds, so the shares add up to the uncut layer.
Matmul weights are stored [in, out]. Leaves (`H` hidden, `n` query heads and
`kv` key/value heads of `d`, `m` indexer heads of `e`, `E` experts held, `R`
routed over, `f` the expert width, `V` the vocabulary slice):

  layer:  norm1 norm2 [H] f32   wq [H, n*d]  wk wv [H, kv*d]  wo [n*d, H]
          q_norm k_norm [d] f32
          wq_idx [H, m*e]  wk_idx [H, e]  k_idx_norm k_idx_bias [e] f32
          w_idx [H, m]
          router [H, R] f32   we_gate we_up [E, H, f]   we_down [E, f, H]
  global: embed [V, H]   norm_f [H] f32   head [H, V]

Values: matmul and embedding weights and the router N(0, std); the
projections that write into the residual (wo, we_down) N(0, std/sqrt(2L));
norm scales 1 + N(0, bias_std) and the LayerNorm's bias N(0, bias_std), so a
dropped one shows.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from .weights_dsv3 import _CONFIGS, _key_of
from .weights_hybrid import root_key

SCALE_LEAVES = ("norm1", "norm2", "norm_f", "q_norm", "k_norm", "k_idx_norm")
F32_LEAVES = SCALE_LEAVES + ("k_idx_bias", "router")
RESIDUAL_LEAVES = ("wo", "we_down")
EXPERT_LEAVES = ("we_gate", "we_up", "we_down")
# the order is the key: append, never insert
LEAVES = ("embed", "norm_f", "head", "norm1", "norm2", "wq", "wk", "wv",
          "wo", "q_norm", "k_norm", "wq_idx", "wk_idx", "k_idx_norm",
          "k_idx_bias", "w_idx", "router", "we_gate", "we_up", "we_down")


def sizes(config):
    """(n, kv, d, m, e): query heads, key/value heads, head width, indexer
    heads, indexer head width."""
    sa = config["sa_config"]
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], sa["indexer_num_heads"],
            sa["indexer_head_dim"])


def global_shapes(config):
    h, v = config["hidden_size"], config["vocab_size"]
    return {"embed": (v, h), "norm_f": (h,), "head": (h, v)}


def layer_shapes(config):
    """{leaf: shape} of one layer."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    n, kv, d, m, e = sizes(config)
    ex = config["num_experts"]
    return {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d),
            "wv": (h, kv * d), "wo": (n * d, h), "q_norm": (d,),
            "k_norm": (d,), "wq_idx": (h, m * e), "wk_idx": (h, e),
            "k_idx_norm": (e,), "k_idx_bias": (e,), "w_idx": (h, m),
            "norm2": (h,), "router": (h, config["router_width"]),
            "we_gate": (ex, h, f), "we_up": (ex, h, f),
            "we_down": (ex, f, h)}


def _value(key, leaf, shape, config):
    init = config["init"]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in SCALE_LEAVES:
        return 1.0 + init["bias_std"] * noise
    if leaf == "k_idx_bias":
        return init["bias_std"] * noise
    if leaf in RESIDUAL_LEAVES:
        return init["std"] / np.sqrt(2 * config["num_hidden_layers"]) * noise
    return init["std"] * noise


def _leaf(root, leaf, layer, shape, config, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, LEAVES.index(leaf)),
                             layer)
    dtype = jnp.float32 if leaf in F32_LEAVES else dtype
    if leaf in EXPERT_LEAVES:
        ids = config.get("experts_held_first", 0) \
            + jnp.arange(shape[0], dtype=jnp.uint32)
        return jax.lax.map(
            lambda i: _value(jax.random.fold_in(key, i), leaf, shape[1:],
                             config).astype(dtype), ids)
    return _value(key, leaf, shape, config).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(config_key, layer, dtype):
    """The jitted maker of layer `layer`'s leaves (None: the globals)."""
    config = _CONFIGS[config_key]
    shapes = global_shapes(config) if layer is None else layer_shapes(config)
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, layer or 0, shape, config, dtype)
        for leaf, shape in shapes.items()})


def _key(config):
    """`_key_of`, with the indexer's sizes in it (a nested group)."""
    return _key_of(dict(config, sa_sizes=json.dumps(config["sa_config"],
                                                    sort_keys=True)))


def make_layer(config, seed, layer, dtype):
    """{leaf: array} of one layer, one jitted call."""
    return _maker(_key(config), int(layer),
                  jnp.dtype(dtype).name)(root_key(seed))


def make_globals(config, seed, dtype):
    return _maker(_key(config), None, jnp.dtype(dtype).name)(root_key(seed))


def named(config, seed, dtype):
    """{parameter name of the served model: array}: `top.embed`,
    `top.norm_f`, `top.head` and `layers.<i>.<leaf>`, the layout of the
    program's `HybridDecoder.named_parameters()`. A layer at a time, so that
    no call holds more than one layer's float32 noise."""
    out = {f"top.{leaf}": value for leaf, value in
           make_globals(config, seed, dtype).items()}
    for i in range(config["num_hidden_layers"]):
        for leaf, value in make_layer(config, seed, i, dtype).items():
            out[f"layers.{i}.{leaf}"] = value
    return out
