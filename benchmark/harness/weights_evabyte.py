"""Weights from the seed for EvaByte's language model (every layer an EVA
attention mixer and a SwiGLU feed-forward): made on the device, one jitted
call a layer, in the type they are served in.

The scheme is the siblings': every (leaf, layer) pair has its own key,
`fold_in(fold_in(root, leaf), layer)` (`weights_hybrid.root_key`,
`weights_dsv3._key_of`). Matmul weights are stored [in, out]. Leaves (`H`
hidden, `n` heads of `d`, `F` feed-forward width, `V` vocabulary, `P`
prediction heads):

  layer:  norm1 norm2 [H] f32   wq wk wv [H, n*d]   wo [n*d, H]
          phi mu [n, d] f32     w_gate w_up [H, F]  w_down [F, H]
  global: embed [V, H]   norm_f [H] f32   head [H, P*V] (head p is columns
          [p V, (p + 1) V))

Values: matmul and embedding weights N(0, std); the projections that write
into the residual (wo, w_down) N(0, std/sqrt(2L)); the norm leaves hold `w`
of the scale `1 + w` (`norm_add_unit_offset`), N(0, bias_std), so a dropped
offset or scale shows; `phi` N(0, phi_std) and `mu` N(0, mu_std), so that a
chunk's pooling weights are neither uniform nor one-hot and a dropped `mu`
moves a score (the configuration's `init` has the numbers and `assumed` the
reason).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .weights_dsv3 import _CONFIGS, _key_of
from .weights_hybrid import root_key

NORM_LEAVES = ("norm1", "norm2", "norm_f")
F32_LEAVES = NORM_LEAVES + ("phi", "mu")
RESIDUAL_LEAVES = ("wo", "w_down")
# the order is the key: append, never insert
LEAVES = ("embed", "norm_f", "head", "norm1", "norm2", "wq", "wk", "wv",
          "phi", "mu", "wo", "w_gate", "w_up", "w_down")


def head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def global_shapes(config):
    h, v = config["hidden_size"], config["vocab_size"]
    return {"embed": (v, h), "norm_f": (h,),
            "head": (h, config["num_pred_heads"] * v)}


def layer_shapes(config):
    """{leaf: shape} of one layer."""
    h, f = config["hidden_size"], config["intermediate_size"]
    n, d = config["num_attention_heads"], head_dim(config)
    return {"norm1": (h,), "wq": (h, n * d), "wk": (h, n * d),
            "wv": (h, n * d), "phi": (n, d), "mu": (n, d), "wo": (n * d, h),
            "norm2": (h,), "w_gate": (h, f), "w_up": (h, f),
            "w_down": (f, h)}


def _value(key, leaf, shape, config):
    init = config["init"]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in NORM_LEAVES:
        return init["bias_std"] * noise
    if leaf in ("phi", "mu"):
        return init[leaf + "_std"] * noise
    if leaf in RESIDUAL_LEAVES:
        return init["std"] / np.sqrt(2 * config["num_hidden_layers"]) * noise
    return init["std"] * noise


def _leaf(root, leaf, layer, shape, config, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, LEAVES.index(leaf)),
                             layer)
    dtype = jnp.float32 if leaf in F32_LEAVES else dtype
    return _value(key, leaf, shape, config).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(config_key, layer, dtype):
    """The jitted maker of layer `layer`'s leaves (None: the globals)."""
    config = _CONFIGS[config_key]
    shapes = global_shapes(config) if layer is None else layer_shapes(config)
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, layer or 0, shape, config, dtype)
        for leaf, shape in shapes.items()})


def make_layer(config, seed, layer, dtype):
    """{leaf: array} of one layer, one jitted call."""
    return _maker(_key_of(config), int(layer),
                  jnp.dtype(dtype).name)(root_key(seed))


def make_globals(config, seed, dtype):
    return _maker(_key_of(config), None,
                  jnp.dtype(dtype).name)(root_key(seed))


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
