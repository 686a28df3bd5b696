"""Weights from the seed for Nemotron-H's language model (every block ONE of
a Mamba-2 mixer, a grouped-query attention mixer, or a relu2 expert layer):
made on the device, one jitted call a block, in the type they are served in.

The scheme is weights_hybrid.py's: every (leaf, block) pair has its own key,
`fold_in(fold_in(root, leaf), block)`, and every routed expert its own key
under that, `fold_in(key, global expert id)`: an expert's values do not
depend on which share of the experts a chip holds, which is what lets a test
add the 8 shares up to the uncut block. Matmul weights are stored [in, out].
The seed's root key and the makers' configuration key are the siblings'
(`weights_hybrid.root_key`, `weights_dsv3._key_of`).

A block's kind is its letter in `hybrid_override_pattern`: `M` mamba2, `*`
gqa, `E` moe. A mixer block's one norm is `norm1`, an expert block's `norm2`
(the names the decoder's two-part layers give the norm before each part).
Leaves (`H` hidden, `I` = mamba_num_heads x mamba_head_dim, `G` n_groups, `N`
ssm_state_size, `C` = I + 2 G N the convolution's channels, `n`/`kv` query
and key/value heads of `d`, `E` experts held (`num_experts`), `R` experts the
router scores (`router_width`)):

  mamba2: norm1 [H] f32  w_in [H, I + C + heads]  ([z, xBC, dt])
          conv_w [K, C]  conv_b [C]  dt_bias a_log d_skip [heads] f32
          ssm_norm [I] f32  w_out [I, H]
  gqa:    norm1 [H] f32  wq [H, n*d]  wk wv [H, kv*d]  wo [n*d, H]
  moe:    norm2 [H] f32  router [H, R] f32  router_bias [R] f32
          we_up [E, H, f]  we_down [E, f, H]  ws_up [H, fs]  ws_down [fs, H]
Global: embed [V, H]  norm_f [H] f32  head [H, V].

Values: matmul and embedding weights N(0, std); the projections that write
into the residual (w_out, wo, we_down, ws_down) N(0, std/sqrt(2L)); norm
scales 1 + N(0, bias_std) so a dropped scale shows; convolution taps
N(0, conv_std) and its bias N(0, bias_std); `dt_bias` the inverse softplus of
a step drawn log-uniform in [time_step_min, time_step_max]; `a_log` =
ln U[1, 16]; `d_skip` 1 + N(0, bias_std); the router's bias N(0, bias_std).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .weights_dsv3 import _CONFIGS, _key_of
from .weights_hybrid import root_key

KIND_OF = {"M": "mamba2", "*": "gqa", "E": "moe"}
NORM_LEAVES = ("norm1", "norm2", "norm_f", "ssm_norm")
F32_LEAVES = NORM_LEAVES + ("dt_bias", "a_log", "d_skip", "router",
                            "router_bias")
RESIDUAL_LEAVES = ("w_out", "wo", "we_down", "ws_down")
EXPERT_LEAVES = ("we_up", "we_down")
# the order is the key: append, never insert
LEAVES = ("embed", "norm_f", "head", "norm1", "norm2",
          "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
          "ssm_norm", "w_out", "wq", "wk", "wv", "wo",
          "router", "router_bias", "we_up", "we_down", "ws_up", "ws_down")


def layer_kinds(config):
    """The kind of each block the configuration keeps, from its pattern."""
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == config["num_hidden_layers"]
    return [KIND_OF[c] for c in pattern]


def global_shapes(config):
    h, v = config["hidden_size"], config["vocab_size"]
    return {"embed": (v, h), "norm_f": (h,), "head": (h, v)}


def mamba_sizes(config):
    """(heads, inner width, the convolution's channels)."""
    heads = config["mamba_num_heads"]
    inner = heads * config["mamba_head_dim"]
    return heads, inner, \
        inner + 2 * config["n_groups"] * config["ssm_state_size"]


def layer_shapes(config, kind):
    """{leaf: shape} of one block of kind mamba2 | gqa | moe."""
    h = config["hidden_size"]
    if kind == "mamba2":
        heads, inner, chan = mamba_sizes(config)
        return {"norm1": (h,), "w_in": (h, inner + chan + heads),
                "conv_w": (config["conv_kernel"], chan), "conv_b": (chan,),
                "dt_bias": (heads,), "a_log": (heads,), "d_skip": (heads,),
                "ssm_norm": (inner,), "w_out": (inner, h)}
    if kind == "gqa":
        n, kv = config["num_attention_heads"], config["num_key_value_heads"]
        d = config["head_dim"]
        return {"norm1": (h,), "wq": (h, n * d), "wk": (h, kv * d),
                "wv": (h, kv * d), "wo": (n * d, h)}
    e, f = config["num_experts"], config["moe_intermediate_size"]
    fs, r = config["moe_shared_expert_intermediate_size"], \
        config["router_width"]
    return {"norm2": (h,), "router": (h, r), "router_bias": (r,),
            "we_up": (e, h, f), "we_down": (e, f, h),
            "ws_up": (h, fs), "ws_down": (fs, h)}


def _value(key, leaf, shape, config):
    init = config["init"]
    if leaf == "dt_bias":
        lo, hi = np.log(config["time_step_min"]), \
            np.log(config["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))         # softplus^-1(dt)
    if leaf == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in NORM_LEAVES + ("d_skip",):
        return 1.0 + init["bias_std"] * noise
    if leaf in ("router_bias", "conv_b"):
        return init["bias_std"] * noise
    if leaf == "conv_w":
        return init["conv_std"] * noise
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
            lambda e: _value(jax.random.fold_in(key, e), leaf, shape[1:],
                             config).astype(dtype), ids)
    return _value(key, leaf, shape, config).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(config_key, layer, dtype):
    """The jitted maker of block `layer`'s leaves (None: the globals)."""
    config = _CONFIGS[config_key]
    shapes = global_shapes(config) if layer is None \
        else layer_shapes(config, layer_kinds(config)[layer])
    return jax.jit(lambda root: {
        leaf: _leaf(root, leaf, layer or 0, shape, config, dtype)
        for leaf, shape in shapes.items()})


def make_layer(config, seed, layer, dtype):
    """{leaf: array} of one block, one jitted call."""
    return _maker(_key_of(config), int(layer),
                  jnp.dtype(dtype).name)(root_key(seed))


def make_globals(config, seed, dtype):
    return _maker(_key_of(config), None,
                  jnp.dtype(dtype).name)(root_key(seed))


def named(config, seed, dtype):
    """{parameter name of the served model: array}: `top.embed`,
    `top.norm_f`, `top.head` and `layers.<i>.<leaf>`, the layout of the
    program's `HybridDecoder.named_parameters()`. A block at a time, so that
    no call holds more than one block's float32 noise."""
    out = {f"top.{leaf}": value for leaf, value in
           make_globals(config, seed, dtype).items()}
    for i in range(config["num_hidden_layers"]):
        for leaf, value in make_layer(config, seed, i, dtype).items():
            out[f"layers.{i}.{leaf}"] = value
    return out
