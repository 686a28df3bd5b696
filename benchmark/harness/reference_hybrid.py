"""The plain reference of the hybrid decoder: KDA linear-attention and MLA
mixers, dense SwiGLU and expert feed-forwards, in straightforward jax.numpy.

Imports nothing of the program and takes nothing the program has made.
Float32 under `jax.default_matmul_precision("highest")` (on a TPU a default
f32 matmul is one bf16 pass). No kernels, no cache, no batching: one sequence
[T] at a time, the KDA recurrence as a `lax.scan` over tokens, attention as
the full causal softmax, the expert layer as every held expert applied to
every token and weighted by the router's picks. One layer's weights are made
from the seed at a time (`make_layer(i)`), so the whole never has to fit.

Block: `h = h + Mixer(RMSNorm(h)); h = h + FFN(RMSNorm(h))`, final RMSNorm,
untied head. `x` below is the normed input of a sublayer.

KDA (n heads of dk = dv): `q, k, v = SiLU(conv(W x))`, `conv` a causal
depthwise convolution over time, kernel K, taps per channel (tap K-1 on the
current token); per head `q = l2norm(q) / sqrt(dk)`, `k = l2norm(k)`; decay
per channel `g = lower_bound * sigmoid(exp(a_log_h) * (W_f x + b_f))`,
`a = exp(g)`; `beta = sigmoid(w_b,h . x)`; state per head, dk x dv float32,
`S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T`; `o_t = S_t^T q_t`;
`y = RMSNorm_head(o) * sigmoid(W_g x)`; out `W_o y`. No rotary.

MLA: `q = W_q x` -> n x (nope + rope); `[c, k_r] = W_a x` -> rank + rope;
`c = RMSNorm(c)`; `[k_n, v] = W_kvb c` -> n x (nope + v); rotary (half-split
pairs, theta) on q's rope dims and on k_r, which all heads share; causal
softmax of `(q_n.k_n + q_r.k_r) / sqrt(nope + rope)`; each head's output
times `sigmoid(w_gate,h . x)`; out `W_o o`.

Experts: `s = sigmoid(W_r x)` over all R routed experts; the choice is made on
`s + bias`: `n_group` groups, a group's score the sum of its top 2, keep the
`topk_group` best groups, then the top `num_experts_per_tok` experts of
those; weights are the chosen `s` normalised to sum 1, times
`routed_scaling_factor`. The layer computes only the experts `held`
(a range of global ids: the chip's share) plus the shared expert:
`y = sum_{e in held, chosen} w_e Expert_e(x) + Shared(x)`; what the absent
experts would add is left out. Each expert is
`W_down(SiLU(W_gate x) * W_up x)`.

`mode` puts the reference in the program's place at a lower precision, the
control of the correctness check: "float32" (the reference itself),
"bfloat16" (weights and activations bfloat16, statistics float32) and
"fp8_e4m3" (both operands of every projection and attention matmul rounded
to float8_e4m3fn, per-tensor abs-max scale). The router, the norms and the
recurrent state stay float32 in every mode, as the configuration states.
"""
import functools

import jax
import jax.numpy as jnp

MODES = ("float32", "bfloat16", "fp8_e4m3")


def _fp8_round(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _dot(spec, a, b, mode):
    if mode == "fp8_e4m3":
        a, b = _fp8_round(a), _fp8_round(b)
    if mode == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))


def _act(x, mode):
    """What a lower-precision program keeps between operations."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) \
        if mode == "bfloat16" else x


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def causal_conv(x, taps):
    """x [T, C], taps [K, C]: y_t = sum_j taps[j] * x_{t-(K-1)+j}."""
    k = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(k))


def kda_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n, d = cfg["num_attention_heads"], cfg["head_dim"]
    proj = lambda name: _act(_dot("th,hc->tc", x, w[name], mode), mode)
    q, k, v = (jax.nn.silu(causal_conv(proj("w" + c), w["conv_" + c]
                                       .astype(jnp.float32)))
               .reshape(t, n, d) for c in "qkv")
    q = _l2norm(q) / jnp.sqrt(jnp.float32(d))
    k = _l2norm(k)
    gate_in = (proj("wf") + w["bf"]).reshape(t, n, d)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[None, :, None] * gate_in)
    beta = jax.nn.sigmoid(proj("wb"))                        # [T, n]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("nk,nkv->nv", k_t, s))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("nk,nkv->nv", q_t, s)

    _, o = jax.lax.scan(step, jnp.zeros((n, d, d), jnp.float32),
                        (q, k, v, g, beta))
    y = rms_norm(o, w["onorm"], cfg["rms_norm_eps"]).reshape(t, n * d) \
        * jax.nn.sigmoid(proj("wg"))
    return _dot("tc,ch->th", _act(y, mode), w["wo"], mode)


def rotary(x, positions, theta):
    """x [T, ..., R] rotated in half-split pairs (i, i + R/2)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    q = _act(_dot("th,hc->tc", x, w["wq"], mode), mode) \
        .reshape(t, n, nope + rope)
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], pos, cfg["rope_theta"])
    a = _act(_dot("th,hc->tc", x, w["wa"], mode), mode)
    c = _act(rms_norm(a[:, :rank], w["cnorm"], cfg["rms_norm_eps"]), mode)
    k_r = rotary(a[:, rank:], pos, cfg["rope_theta"])
    kv = _act(_dot("tr,rc->tc", c, w["wkvb"], mode), mode) \
        .reshape(t, n, nope + vd)
    k_n, v = kv[..., :nope], kv[..., nope:]
    scores = (_dot("qnd,knd->nqk", q_n, k_n, mode)
              + _dot("qnd,kd->nqk", q_r, k_r, mode)) \
        / jnp.sqrt(jnp.float32(nope + rope))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = _act(jax.nn.softmax(scores, axis=-1), mode)
    o = _dot("nqk,knd->qnd", probs, v, mode)
    gate = jax.nn.sigmoid(_act(_dot("th,hn->tn", x, w["wgate"], mode), mode))
    o = (o * gate[:, :, None]).reshape(t, n * vd)
    return _dot("tc,ch->th", _act(o, mode), w["wo"], mode)


def swiglu(x, gate, up, down, mode):
    u = _act(jax.nn.silu(_dot("th,hf->tf", x, gate, mode))
             * _dot("th,hf->tf", x, up, mode), mode)
    return _dot("tf,fh->th", u, down, mode)


def route(x, w, cfg):
    """[T, R] float32: the weight of each routed expert for each token, zero
    where it was not chosen. Float32 whatever the mode."""
    r, groups = cfg["n_routed_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(jnp.einsum("th,hr->tr", x.astype(jnp.float32),
                                  w["router"]))
    biased = s + w["router_bias"]
    per_group = biased.reshape(-1, groups, r // groups)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], -1)
    keep = jax.lax.top_k(group_score, cfg["topk_group"])[1]
    group_ok = jnp.zeros_like(group_score, bool).at[
        jnp.arange(x.shape[0])[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(group_ok, r // groups, axis=1), biased,
                       -jnp.inf)
    chosen = jax.lax.top_k(masked, cfg["num_experts_per_tok"])[1]
    picked = jnp.zeros_like(s, bool).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(True)
    weights = jnp.where(picked, s, 0.0)
    return weights / jnp.sum(weights, -1, keepdims=True) \
        * cfg["routed_scaling_factor"]


def moe_routed(x, w, cfg, mode, held):
    """The routed part the experts `held` = (first, count) give."""
    first, count = held
    weights = route(x, w, cfg)[:, first:first + count]          # [T, E]

    def one(acc, xs):
        gate, up, down, w_e = xs
        return acc + w_e[:, None] * swiglu(x, gate, up, down, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (w["we_gate"], w["we_up"], w["we_down"], weights.T))
    return y


def moe_ffn(x, w, cfg, mode, held):
    return moe_routed(x, w, cfg, mode, held) \
        + swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], mode)


def layer(h, w, kinds, cfg, mode="float32", held=None):
    """One block on one sequence h [T, H] (float32 residual)."""
    mixer, ffn = kinds
    held = held or (cfg.get("experts_held_first", 0), cfg["num_experts"])
    x = _act(rms_norm(h, w["norm1"], cfg["rms_norm_eps"]), mode)
    h = h + (kda_mixer if mixer == "kda" else mla_mixer)(x, w, cfg, mode)
    x = _act(rms_norm(h, w["norm2"], cfg["rms_norm_eps"]), mode)
    if ffn == "swiglu":
        return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"], mode)
    return h + moe_ffn(x, w, cfg, mode, held)


def _sizes_of(cfg):
    """The numbers (and only those) the layer functions read, hashable."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float)) and
                        not isinstance(v, bool)))


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, kinds, mode):
    cfg = dict(sizes)
    return jax.jit(lambda h, w: layer(h, w, kinds, cfg, mode))


@functools.lru_cache(maxsize=None)
def _head_fn(sizes, mode):
    cfg = dict(sizes)
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
    (weights_hybrid.py has the names)."""
    with jax.default_matmul_precision("highest"):
        g = make_globals()
        h = _hidden(cfg, kinds, g["embed"], make_layer, [ids], (mode,))
        return _head_fn(_sizes_of(cfg), mode)(h[mode][0], g) \
            .astype(jnp.float32)


def served_rows(config, weights, seed, samples, modes=("float32",),
                pad_to=512):
    """{mode: [the logits at the positions that produced `tokens`, one
    [len(tokens), V] array per (prompt, tokens) of `samples`]}: one forward
    over prompt + served tokens each, right-padded to a multiple of `pad_to`
    so that a few shapes serve every request (causal throughout, so padding
    cannot reach the rows read). `weights` is the configuration's weights
    module: each layer's leaves are regenerated from the seed once, in the
    type they are served in, and widened to float32."""
    import numpy as np
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
