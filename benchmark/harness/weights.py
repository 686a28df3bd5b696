"""Weights from the seed: made on the device, in one jitted call, in the
type they are served or trained in.

Every (leaf, layer) pair has its own key, `fold_in(fold_in(root, leaf), layer)`,
so the same seed gives the same values whether the leaves come out stacked
over layers (the train step's layout, and the reference's) or one array per
layer (the served model's named parameters).

Canonical leaves (GPT-2 family; `L` layers, `H` hidden, `F` ffn, `V` vocab,
`P` positions), matmul weights stored [in, out]:

    wte [V,H]  wpe [P,H]  lnf_w [H]  lnf_b [H]
    ln1_w ln1_b ln2_w ln2_b [L,H]
    w_qkv [L,H,3H] b_qkv [L,3H]   w_proj [L,H,H] b_proj [L,H]
    w_fc1 [L,H,F]  b_fc1 [L,F]    w_fc2 [L,F,H]  b_fc2 [L,H]

Values: matmul and embedding weights N(0, std), the two residual
projections N(0, std/sqrt(2L)) (GPT-2's scaled init), biases N(0, bias_std),
layer-norm scales 1 + N(0, bias_std): small but not zero, so a bias or a
scale that is dropped shows in the comparison.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model_flops import sizes

GLOBAL_LEAVES = ("wte", "wpe", "lnf_w", "lnf_b")
BLOCK_LEAVES = ("ln1_w", "ln1_b", "w_qkv", "b_qkv", "w_proj", "b_proj",
                "ln2_w", "ln2_b", "w_fc1", "b_fc1", "w_fc2", "b_fc2")
LEAVES = GLOBAL_LEAVES + BLOCK_LEAVES


def leaf_shapes(config):
    """{leaf: per-layer shape} (global leaves: their whole shape)."""
    s = sizes(config)
    h, f = s["hidden"], s["ffn"]
    return {"wte": (s["vocab"], h), "wpe": (s["positions"], h),
            "lnf_w": (h,), "lnf_b": (h,),
            "ln1_w": (h,), "ln1_b": (h,), "ln2_w": (h,), "ln2_b": (h,),
            "w_qkv": (h, 3 * h), "b_qkv": (3 * h,),
            "w_proj": (h, h), "b_proj": (h,),
            "w_fc1": (h, f), "b_fc1": (f,),
            "w_fc2": (f, h), "b_fc2": (h,)}


def root_key(seed):
    """A key from any whole number: the driver's seeds pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _leaf_value(key, leaf, shape, config):
    init = config["init"]
    std, bias_std = init["std"], init["bias_std"]
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in ("ln1_w", "ln2_w", "lnf_w"):
        return 1.0 + bias_std * noise
    if leaf in ("w_proj", "w_fc2"):          # the residual projections
        return std / np.sqrt(2 * config["n_layer"]) * noise
    if leaf in ("wte", "wpe", "w_qkv", "w_fc1"):
        return std * noise
    return bias_std * noise


def _layer_value(root, leaf, layer, config, dtype):
    key = jax.random.fold_in(jax.random.fold_in(root, LEAVES.index(leaf)),
                             layer)
    return _leaf_value(key, leaf, leaf_shapes(config)[leaf],
                       config).astype(dtype)


def stacked(config, root, dtype):
    """The canonical pytree, block leaves stacked over layers. Traceable."""
    out = {leaf: _layer_value(root, leaf, 0, config, dtype)
           for leaf in GLOBAL_LEAVES}
    for leaf in BLOCK_LEAVES:
        out[leaf] = jnp.stack([_layer_value(root, leaf, l, config, dtype)
                               for l in range(config["n_layer"])])
    return out


# served model's parameter name for (leaf, layer): the one place that knows
# the layout of `paddle_tpu.text.models.gpt.GPT.named_parameters()`
_GLOBAL_NAMES = {"wte": "wte.weight", "wpe": "wpe.weight",
                 "lnf_w": "ln_f.weight", "lnf_b": "ln_f.bias"}
_BLOCK_NAMES = {"ln1_w": "ln1.weight", "ln1_b": "ln1.bias",
                "w_qkv": "attn.qkv.weight", "b_qkv": "attn.qkv.bias",
                "w_proj": "attn.out_proj.weight",
                "b_proj": "attn.out_proj.bias",
                "ln2_w": "ln2.weight", "ln2_b": "ln2.bias",
                "w_fc1": "mlp.fc1.weight", "b_fc1": "mlp.fc1.bias",
                "w_fc2": "mlp.fc2.weight", "b_fc2": "mlp.fc2.bias"}


def named(config, root, dtype):
    """{parameter name of the served GPT: array}, one array per layer.
    Traceable; the same values as `stacked`."""
    out = {name: _layer_value(root, leaf, 0, config, dtype)
           for leaf, name in _GLOBAL_NAMES.items()}
    for l in range(config["n_layer"]):
        for leaf, name in _BLOCK_NAMES.items():
            out[f"blocks.{l}.{name}"] = _layer_value(root, leaf, l, config,
                                                     dtype)
    return out


def make(config, seed, dtype, layout="stacked", out_shardings=None):
    """One jitted call from the seed."""
    build = {"stacked": stacked, "named": named}[layout]
    fn = jax.jit(functools.partial(build, config, dtype=jnp.dtype(dtype)),
                 out_shardings=out_shardings)
    return fn(root_key(seed))


def split_qkv(tree, layout, heads):
    """The fused qkv leaves as three leaves each (`w_qkv.q`, `w_qkv.k`, ...):
    under softmax a key's bias has no gradient at all, so the comparison has
    to see it as a leaf of its own. `tree` holds stacked arrays whose last
    axis is the fused 3H one."""
    out = {}
    for name, x in tree.items():
        if name not in ("w_qkv", "b_qkv"):
            out[name] = x
            continue
        d = x.shape[-1] // 3 // heads
        if layout == "head_major":
            parts = x.reshape(x.shape[:-1] + (heads, 3, d))
            parts = [parts[..., i, :] for i in range(3)]
        elif layout == "qkv_major":
            parts = x.reshape(x.shape[:-1] + (3, heads, d))
            parts = [parts[..., i, :, :] for i in range(3)]
        else:
            raise ValueError(f"unknown qkv_layout {layout!r}")
        for part, which in zip(parts, "qkv"):
            out[f"{name}.{which}"] = part.reshape(part.shape[0], -1)
    return out
