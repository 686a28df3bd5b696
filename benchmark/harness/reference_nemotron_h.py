"""The plain reference of Nemotron-H's language model: every block is ONE of
a Mamba-2 state-space mixer, a grouped-query softmax attention mixer or a
sigmoid-routed relu2 expert layer, in straightforward jax.numpy.

Imports nothing of the program and takes nothing the program has made. The
router is the same equation as the siblings' (sigmoid scores, the choice on
score + bias, normalised chosen scores times the scaling factor; one group
of all the experts is no group limit) and is imported from
reference_hybrid.py (`route`, with the precision helpers `_dot`, `_act`,
`rms_norm`, and the frame's `_sizes_of`, `_head_fn`): one copy of what the
configurations share; Mamba-2, the grouped-query attention and the relu2
experts are written here. Float32 under
`jax.default_matmul_precision("highest")`. No kernels, no cache, no batching,
no chunked form: one sequence [T] at a time, the state-space recurrence a
`lax.scan` over tokens, attention the full causal softmax, every held expert
applied to every token and weighted by the router's picks. One block's
weights are made from the seed at a time.

Block: `h = h + Part(RMSNorm(h))` with ONE norm (eps 1e-5) and one part;
final RMSNorm, untied head, no bias but the convolution's. `x` below is the
block's normed input.

Mamba-2 (`heads` x `P` = inner width I; `G` groups of B and C, a group
serves heads/G heads; state size N; kernel K): `[z, xBC, dt] = W_in x` of
widths I, I + 2 G N, heads. `xBC = SiLU(conv(xBC) + b_conv)`, `conv` a causal
depthwise convolution over time (tap K-1 on the current token); split into
`x_s` [heads, P], `B`, `C` [G, N]. `dt = softplus(dt + dt_bias)`,
`a = -exp(A_log)`. State per head, P x N float32:
`S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) B_t`;
`y_t = S_t C_t + D x_t`. `y = GroupRMSNorm(y * SiLU(z))` (the gate before
the norm; G groups of I/G, one weight of I); out `W_out y`.

Grouped-query attention (n query heads over kv key/value heads of d, query
head j reads key/value head j // (n / kv)): `q = W_q x`, `k = W_k x`,
`v = W_v x`; causal softmax of `q.k / sqrt(d)`; out `W_o o`. No rotary, no
position table.

Experts: reference_hybrid.py's docstring has the routing; it scores
`router_width` experts and the block computes the `num_experts` held from
`experts_held_first`, plus the shared expert:
`y = sum_{e held, chosen} w_e Expert_e(x) + Shared(x)`,
`Expert(x) = W_down relu(W_up x)^2` (no gate matrix), the shared one wider.

`mode`: "float32" (the reference itself), "bfloat16" and "fp8_e4m3" (the
control: both operands of every projection and attention matmul rounded to
float8_e4m3fn, per-tensor abs-max scale), as in reference_hybrid.py. The
router, the norms, the residual stream and the state's recurrence stay
float32 in every mode, as the configuration states.
"""
import functools

import jax
import jax.numpy as jnp

from .reference_hybrid import (_act, _dot, _head_fn, _sizes_of, causal_conv,
                               rms_norm, route)

MODES = ("float32", "bfloat16", "fp8_e4m3")


def mamba2_mixer(x, w, cfg, mode):
    t = x.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = heads * p
    proj = _act(_dot("th,hc->tc", x, w["w_in"], mode), mode)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"].astype(jnp.float32))
                      + w["conv_b"].astype(jnp.float32))
    xs = xbc[:, :inner].reshape(t, heads, p)
    # a group's B and C serve its heads/G heads
    b, c = (jnp.repeat(part.reshape(t, g, n), heads // g, axis=1)
            for part in jnp.split(xbc[:, inner:], 2, axis=-1))
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [T, heads]
    a = -jnp.exp(w["a_log"])

    def step(s, xs_t):
        x_t, b_t, c_t, dt_t = xs_t
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (xs, b, c, dt))
    y = (y + w["d_skip"][:, None] * xs).reshape(t, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(t, g, inner // g), 1.0,
                 cfg["layer_norm_epsilon"]).reshape(t, inner) * w["ssm_norm"]
    return _dot("tc,ch->th", _act(y, mode), w["w_out"], mode)


def gqa_mixer(x, w, cfg, mode):
    t = x.shape[0]
    n, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    proj = lambda name, heads: _act(
        _dot("th,hc->tc", x, w[name], mode), mode).reshape(t, heads, d)
    q = proj("wq", n).reshape(t, kv, n // kv, d)
    k, v = proj("wk", kv), proj("wv", kv)
    scores = _dot("qkgd,skd->kgqs", q, k, mode) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    probs = _act(jax.nn.softmax(scores, axis=-1), mode)
    o = _dot("kgqs,skd->qkgd", probs, v, mode).reshape(t, n * d)
    return _dot("tc,ch->th", _act(o, mode), w["wo"], mode)


def relu2_mlp(x, up, down, mode):
    u = _act(jnp.square(jax.nn.relu(_dot("th,hf->tf", x, up, mode))), mode)
    return _dot("tf,fh->th", u, down, mode)


def expert_sizes(cfg):
    """What reference_hybrid's `route` reads, under its names: there
    `n_routed_experts` is the router's width."""
    return dict(cfg, n_routed_experts=cfg["router_width"])


def moe_routed(x, w, cfg, mode, held):
    """The routed part the experts `held` = (first, count) give."""
    first, count = held
    weights = route(x, w, expert_sizes(cfg))[:, first:first + count]

    def one(acc, xs):
        up, down, w_e = xs
        return acc + w_e[:, None] * relu2_mlp(x, up, down, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (w["we_up"], w["we_down"], weights.T))
    return y


def moe_ffn(x, w, cfg, mode, held):
    return moe_routed(x, w, cfg, mode, held) \
        + relu2_mlp(x, w["ws_up"], w["ws_down"], mode)


def layer(h, w, kind, cfg, mode="float32", held=None):
    """One block of kind mamba2 | gqa | moe on one sequence h [T, H]
    (float32 residual)."""
    eps = cfg["layer_norm_epsilon"]
    if kind == "moe":
        held = held or (cfg.get("experts_held_first", 0), cfg["num_experts"])
        x = _act(rms_norm(h, w["norm2"], eps), mode)
        return h + moe_ffn(x, w, cfg, mode, held)
    x = _act(rms_norm(h, w["norm1"], eps), mode)
    return h + (mamba2_mixer if kind == "mamba2" else gqa_mixer)(
        x, w, cfg, mode)


def _head_sizes(cfg):
    """`_head_fn` of reference_hybrid.py reads its epsilon as
    `rms_norm_eps`."""
    return _sizes_of(dict(cfg, rms_norm_eps=cfg["layer_norm_epsilon"]))


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, kind, mode):
    cfg = dict(sizes)
    return jax.jit(lambda h, w: layer(h, w, kind, cfg, mode))


def _hidden(cfg, kinds, embed, make_layer, ids_list, modes):
    """{mode: [final residual [T, H] of each id sequence]}: block after
    block, `make_layer(i)` called once when its turn comes, used for every
    sequence and mode, and dropped."""
    sizes = _sizes_of(cfg)
    hidden = {m: [embed[ids].astype(jnp.float32) for ids in ids_list]
              for m in modes}
    for i, kind in enumerate(kinds):
        w = make_layer(i)
        for m in modes:
            fn = _layer_fn(sizes, kind, m)
            hidden[m] = [fn(h, w) for h in hidden[m]]
        del w
    return hidden


def logits(cfg, kinds, make_globals, make_layer, ids, mode="float32"):
    """[T] token ids -> [T, V] float32 logits. `make_globals()` gives
    `embed`, `norm_f`, `head`; `make_layer(i)` the leaves of block `i`
    (weights_nemotron_h.py has the names)."""
    with jax.default_matmul_precision("highest"):
        g = make_globals()
        h = _hidden(cfg, kinds, g["embed"], make_layer, [ids], (mode,))
        return _head_fn(_head_sizes(cfg), mode)(h[mode][0], g) \
            .astype(jnp.float32)


def served_rows(config, weights, seed, samples, modes=("float32",),
                pad_to=None):
    """{mode: [the logits at the positions that produced `tokens`, one
    [len(tokens), V] array per (prompt, tokens) of `samples`]}: one forward
    over prompt + served tokens each, right-padded to `pad_to` (the
    positions served, unless given): ONE shape then serves every request of
    every seed, so the three block programs compile once and come from the
    compile cache ever after (causal throughout, so padding cannot reach
    the rows read). Each block's leaves are regenerated from the seed once,
    in the type they are served in, and widened to float32."""
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
            head = _head_fn(_head_sizes(config), m)
            out[m] = [np.asarray(
                head(h, g)[len(prompt) - 1:len(prompt) - 1 + len(tokens)],
                np.float32)
                for h, (prompt, tokens) in zip(hidden[m], samples)]
    return out
