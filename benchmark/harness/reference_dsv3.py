"""The plain reference of DeepSeek-V3's main model: MLA behind a query
bottleneck with YaRN rotary in every layer, dense SwiGLU in the leading
layers and the sigmoid-routed expert layer in the rest, in straightforward
jax.numpy.

Imports nothing of the program and takes nothing the program has made. The
router and the expert share are the same equations as the sibling's and are
imported from reference_hybrid.py (`route`, `moe_ffn`, `swiglu`, with the
precision helpers `_dot`, `_act`, `rms_norm`): one copy of the group-limited
sigmoid routing the two configurations share; everything MLA and YaRN is
written here. Float32 under `jax.default_matmul_precision("highest")`. No
kernels, no cache, no batching, no absorbed form: one sequence [T] at a time,
keys and values expanded per head, the full causal softmax. Attention runs in
blocks of heads and of queries (`HEAD_BLOCK`, `QUERY_BLOCK`: the same
numbers block by block, so that 4 608 positions of 128 heads fit a chip), and
one layer's weights are made from the seed at a time.

Block: `h = h + MLA(RMSNorm(h)); h = h + FFN(RMSNorm(h))`, eps 1e-6, final
RMSNorm, untied head, no biases. `x` below is a sublayer's normed input.

MLA (n heads): `c_q = RMSNorm(W_qa x)` (q_lora_rank); `q = W_qb c_q` ->
n x (nope + rope); `[c, k_r] = W_kva x` -> kv_lora_rank + rope;
`c = RMSNorm(c)`; `[k_n, v] = W_kvb c` -> n x (nope + v); rotary on the rope
dims of `q` and on `k_r`, which all heads share; causal softmax of
`(q_n.k_n + q_r.k_r) * scale`; out `W_o (sum softmax * v)`.

YaRN (`rope_scaling`: factor s, original length L, beta_fast, beta_slow,
mscale, mscale_all_dim; R rope dims, R/2 pairs, half-split (i, i + R/2)):
`f_i = theta^(-2i/R)`; `dim(b) = R ln(L / (2 pi b)) / (2 ln theta)`;
`low = max(floor(dim(beta_fast)), 0)`, `high = min(ceil(dim(beta_slow)),
R - 1)`; `r_i = clip((i - low) / (high - low), 0, 1)`; frequency
`f_i (1 - r_i) + (f_i / s) r_i`. With `m(a) = 0.1 a ln(s) + 1`: cos and sin
are multiplied by `m(mscale) / m(mscale_all_dim)` (1 when the two are equal)
and `scale = (nope + rope)^-0.5 * m(mscale_all_dim)^2`. Without
`rope_scaling`: plain rotary, `scale = (nope + rope)^-0.5`.

Expert layer: reference_hybrid.py's docstring has it; it routes over
`router_width` experts and computes the `num_experts` held from
`experts_held_first`.

`mode`: "float32" (the reference itself), "bfloat16" and "fp8_e4m3" (the
control: both operands of every projection and attention matmul rounded to
float8_e4m3fn, per-tensor abs-max scale, the attention's per block), as in
reference_hybrid.py. Router, norms and residual stream stay float32.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .reference_hybrid import _act, _dot, moe_ffn, rms_norm, swiglu

MODES = ("float32", "bfloat16", "fp8_e4m3")
HEAD_BLOCK = 32
QUERY_BLOCK = 256


def yarn_frequencies(cfg):
    """(the R/2 rotary frequencies [R/2] float32, the factor on cos and
    sin, the softmax scale)."""
    rope = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    half = rope // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    scale = (cfg["qk_nope_head_dim"] + rope) ** -0.5
    s = cfg.get("rope_scaling")
    if not s:
        return freq, 1.0, scale

    def dim_of(turns):
        return rope * math.log(s["original_max_position_embeddings"]
                               / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(s["beta_fast"])), 0)
    high = min(math.ceil(dim_of(s["beta_slow"])), rope - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freq = freq * (1.0 - ramp) + freq / s["factor"] * ramp

    def m(a):
        return 0.1 * a * math.log(s["factor"]) + 1.0 \
            if s["factor"] > 1 else 1.0

    all_dim = s.get("mscale_all_dim", 0)
    if all_dim:
        scale = scale * m(all_dim) ** 2
    return freq, m(s.get("mscale", 1)) / m(all_dim), scale


def rotary(x, positions, freq, factor):
    """x [T, ..., R] rotated in half-split pairs (i, i + R/2)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def causal_attention(q_n, q_r, k_n, k_r, v, scale, mode):
    """softmax((q_n.k_n + q_r.k_r) * scale) v under the causal mask: q_n,
    k_n [T, n, nope], q_r [T, n, rope], k_r [T, rope] (shared by the heads),
    v [T, n, vd] -> [T, n, vd]. A block of heads and of queries at a time;
    every block sees all T keys, so the numbers are those of one pass."""
    t, n = q_n.shape[:2]
    hb, qb = math.gcd(n, HEAD_BLOCK), math.gcd(t, QUERY_BLOCK)

    def one_block(args):
        qn, qr, kn, vv, first = args        # [qb, hb, .], [T, hb, .]
        scores = (_dot("qnd,knd->nqk", qn, kn, mode)
                  + _dot("qnd,kd->nqk", qr, k_r, mode)) * scale
        seen = (first + jnp.arange(qb))[:, None] >= jnp.arange(t)[None, :]
        probs = _act(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1),
                     mode)
        return _dot("nqk,knd->qnd", probs, vv, mode)

    def head_block(args):
        qn, qr, kn, vv = args               # [T, hb, .]
        split = lambda a: a.reshape((t // qb, qb) + a.shape[1:])
        out = jax.lax.map(
            lambda xs: one_block((xs[0], xs[1], kn, vv, xs[2])),
            (split(qn), split(qr), jnp.arange(0, t, qb)))
        return out.reshape((t,) + out.shape[2:])

    heads = lambda a: jnp.moveaxis(
        a.reshape(t, n // hb, hb, a.shape[-1]), 1, 0)
    out = jax.lax.map(head_block, tuple(map(heads, (q_n, q_r, k_n, v))))
    return jnp.moveaxis(out, 0, 1).reshape(t, n, v.shape[-1])


def mla_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    freq, factor, scale = yarn_frequencies(cfg)
    c_q = _act(rms_norm(_act(_dot("th,hc->tc", x, w["wq_a"], mode), mode),
                        w["qnorm"], eps), mode)
    q = _act(_dot("tc,cd->td", c_q, w["wq_b"], mode), mode) \
        .reshape(t, n, nope + rope)
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], pos, freq, factor)
    a = _act(_dot("th,hc->tc", x, w["wa"], mode), mode)
    c = _act(rms_norm(a[:, :rank], w["cnorm"], eps), mode)
    k_r = rotary(a[:, rank:], pos, freq, factor)
    kv = _act(_dot("tr,rc->tc", c, w["wkvb"], mode), mode) \
        .reshape(t, n, nope + vd)
    o = causal_attention(q_n, q_r, kv[..., :nope], k_r, kv[..., nope:],
                         scale, mode)
    return _dot("tc,ch->th", _act(o.reshape(t, n * vd), mode), w["wo"], mode)


def expert_sizes(cfg):
    """What reference_hybrid's `route` and `moe_ffn` read, under its names:
    there `n_routed_experts` is the router's width."""
    return dict(cfg, n_routed_experts=cfg["router_width"])


def layer(h, w, kinds, cfg, mode="float32", held=None):
    """One block on one sequence h [T, H] (float32 residual)."""
    _, ffn = kinds
    held = held or (cfg.get("experts_held_first", 0), cfg["num_experts"])
    x = _act(rms_norm(h, w["norm1"], cfg["rms_norm_eps"]), mode)
    h = h + mla_mixer(x, w, cfg, mode)
    x = _act(rms_norm(h, w["norm2"], cfg["rms_norm_eps"]), mode)
    if ffn == "swiglu":
        return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"], mode)
    return h + moe_ffn(x, w, expert_sizes(cfg), mode, held)


def _sizes_of(cfg):
    """The numbers (and only those) the layer functions read, hashable;
    `rope_scaling` as a tuple of its own."""
    flat = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if cfg.get("rope_scaling"):
        flat["rope_scaling"] = tuple(sorted(cfg["rope_scaling"].items()))
    return tuple(sorted(flat.items()))


def _cfg_of(sizes):
    cfg = dict(sizes)
    if "rope_scaling" in cfg:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, kinds, mode):
    cfg = _cfg_of(sizes)
    return jax.jit(lambda h, w: layer(h, w, kinds, cfg, mode))


@functools.lru_cache(maxsize=None)
def _head_fn(sizes, mode):
    cfg = _cfg_of(sizes)
    return jax.jit(lambda h, g: _dot(
        "th,hv->tv", _act(rms_norm(h, g["norm_f"], cfg["rms_norm_eps"]),
                          mode), g["head"], mode))


def _hidden(cfg, kinds, embed, make_layer, ids_list, modes):
    """{mode: [final residual [T, H] of each id sequence]}: layer after
    layer, `make_layer(i)` called once when its turn comes, used for every
    sequence and mode, and dropped."""
    sizes = _sizes_of(cfg)
    hidden = {m: [embed[ids].astype(jnp.float32) for ids in ids_list]
              for m in modes}
    for i, k in enumerate(kinds):
        w = make_layer(i)
        for m in modes:
            fn = _layer_fn(sizes, tuple(k), m)
            hidden[m] = [fn(h, w) for h in hidden[m]]
        del w
    return hidden


def logits(cfg, kinds, make_globals, make_layer, ids, mode="float32"):
    """[T] token ids -> [T, V] float32 logits. `make_globals()` gives
    `embed`, `norm_f`, `head`; `make_layer(i)` the leaves of layer `i`
    (weights_dsv3.py has the names)."""
    with jax.default_matmul_precision("highest"):
        g = make_globals()
        h = _hidden(cfg, kinds, g["embed"], make_layer, [ids], (mode,))
        return _head_fn(_sizes_of(cfg), mode)(h[mode][0], g) \
            .astype(jnp.float32)


def served_rows(config, weights, seed, samples, modes=("float32",),
                pad_to=None):
    """{mode: [the logits at the positions that produced `tokens`, one
    [len(tokens), V] array per (prompt, tokens) of `samples`]}: one forward
    over prompt + served tokens each, right-padded to a multiple of `pad_to`
    (the positions served, unless given): ONE shape then serves every
    request of every seed, so the two layer programs compile once and come
    from the compile cache ever after; compiling a shape costs more than
    running it (causal throughout, so padding cannot reach the rows read).
    Each layer's leaves are regenerated from the seed once, in the type
    they are served in, and widened to float32."""
    import numpy as np
    pad_to = pad_to or config["max_position_embeddings"]
    dtype = config["dtype"]["param"]
    widen = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    padded = []
    for prompt, tokens in samples:
        ids = list(prompt) + list(tokens[:-1])
        row = np.zeros((-(-len(ids) // pad_to) * pad_to,), np.int32)
        row[:len(ids)] = ids
        padded.append(jnp.asarray(row))
    with jax.default_matmul_precision("highest"):
        g = widen(weights.make_globals(config, seed, dtype))
        hidden = _hidden(
            config, weights.layer_kinds(config), g["embed"],
            lambda i: widen(weights.make_layer(config, seed, i, dtype)),
            padded, modes)
        out = {}
        for m in modes:
            head = _head_fn(_sizes_of(config), m)
            out[m] = [np.asarray(
                head(h, g)[len(prompt) - 1:len(prompt) - 1 + len(tokens)],
                np.float32)
                for h, (prompt, tokens) in zip(hidden[m], samples)]
    return out
