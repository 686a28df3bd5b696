"""The mechanisms of the hybrid decoder as pure functions over raw arrays:
KDA linear attention and the Mamba-2 state-space mixer (each a chunked form
for prefill and the one-step recurrence for decode), MLA over a latent cache
(expanded for prefill, absorbed for decode), grouped-query attention over
paged K/V rows (with, where declared, DeepSeek Sparse Attention's learned
selection in front: an indexer scores every cached position and attention
reads only the `index_topk` it picks), EVA attention (exact softmax inside an
aligned window, one learned summary row a chunk of every closed window), and
the expert layer of a chip that holds a share of the experts (sigmoid,
group-limited routing, or a plain softmax). Plain XLA, but for the
threshold of the learned selection in prefill (the Pallas kernel
`ops/pallas/topk_threshold.py`); `text/models/hybrid.py` wires them into
a model and `serving/blocks.py` holds the kinds of cache they read and
write.

Weights are bfloat16 (matmuls accumulate in float32), the residual stream,
the norms, the router, the index scores and the recurrent state are
float32.
"""
import math

import jax
import jax.numpy as jnp

from ...ops.pallas.topk_threshold import row_threshold

HIGHEST = jax.lax.Precision.HIGHEST
KDA_CHUNK = 64
KDA_SUBCHUNK = 16


def mm(spec, a, b):
    """bfloat16 operands, float32 result."""
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def f32mm(spec, a, b):
    """float32 operands at full precision (the state's arithmetic)."""
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def swiglu(x, gate, up, down):
    return mm("...f,fh->...h",
              jax.nn.silu(mm("...h,hf->...f", x, gate))
              * mm("...h,hf->...f", x, up), down)


def relu2_mlp(x, up, down):
    return mm("...f,fh->...h",
              jnp.square(jax.nn.relu(mm("...h,hf->...f", x, up))), down)


def short_conv(history, conv_in, taps):
    """The causal depthwise convolution of `conv_in` [..., T, C] behind
    `history` [..., K-1, C] with `taps` [K, C] float32 (tap K-1 on the
    current row), in float32."""
    seq = jnp.concatenate([history, conv_in], -2).astype(jnp.float32)
    t = conv_in.shape[-2]
    return sum(taps[j] * jax.lax.slice_in_dim(seq, j, j + t, axis=-2)
               for j in range(taps.shape[0]))


# ------------------------------------------------------------------- KDA

def kda_inputs(x, w, cfg, conv_in, history):
    """What the recurrence consumes, from the normed input `x` [..., T, H]:
    q, k, v [..., T, n, d], log-decay g [..., T, n, d] (<= 0), beta
    [..., T, n], and the output gate [..., T, n*d]. `conv_in` [..., T, 3nd]
    are the projections the convolution reads (`kda_conv_in`) and `history`
    [..., K-1, 3nd] the ones before them (zeros at the start of a request):
    the cache keeps the last K-1 rows of their concatenation."""
    n, d = cfg.num_heads, cfg.head_dim
    taps = jnp.concatenate([w["conv_q"], w["conv_k"], w["conv_v"]], -1) \
        .astype(jnp.float32)
    conv = short_conv(history, conv_in, taps)
    q, kk, v = (part.reshape(part.shape[:-1] + (n, d))
                for part in jnp.split(jax.nn.silu(conv), 3, -1))
    q = l2norm(q) * (d ** -0.5)
    kk = l2norm(kk)
    gate_in = (mm("...h,hc->...c", x, w["wf"]) + w["bf"]) \
        .reshape(x.shape[:-1] + (n, d))
    g = cfg.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None] * gate_in)
    beta = jax.nn.sigmoid(mm("...h,hn->...n", x, w["wb"]))
    out_gate = jax.nn.sigmoid(mm("...h,hc->...c", x, w["wg"]))
    return q, kk, v, g, beta, out_gate


def kda_conv_in(x, w, dtype):
    """The three projections the convolution reads, in the type the cache
    keeps their last rows in."""
    return jnp.concatenate([mm("...h,hc->...c", x, w[name])
                            for name in ("wq", "wk", "wv")],
                           -1).astype(dtype)


def kda_output(o, out_gate, w, cfg):
    """Per-head RMSNorm of the state's read-out, the output gate, W_o."""
    y = rms_norm(o, w["onorm"], cfg.rms_norm_eps)
    y = y.reshape(y.shape[:-2] + (-1,)) * out_gate
    return mm("...c,ch->...h", y, w["wo"])


def kda_recurrent_step(state, q, k, v, g, beta):
    """One token for every slot: state [S, n, dk, dv]; q, k, v, g [S, n, d];
    beta [S, n]. Returns (new state, o [S, n, dv])."""
    with jax.named_scope("kda_state"):
        state = jnp.exp(g)[..., None] * state
        u = beta[..., None] * (v - f32mm("snk,snkv->snv", k, state))
        state = state + k[..., None] * u[..., None, :]
        return state, f32mm("snk,snkv->snv", q, state)


def kda_chunked(q, k, v, g, beta, valid):
    """The same recurrence over one sequence from a zero state, a chunk of
    KDA_CHUNK tokens at a time: q, k, v, g [T, n, d], beta [T, n], `valid`
    [T] bool (bucket padding is False: it neither decays nor writes).
    Returns (o [T, n, dv], final state [n, dk, dv]).

    With G the cumulative log-decay inside a chunk and S0 the state it
    starts from, `u_t = beta_t (v_t - (k_t e^{G_t})^T S0 - sum_{i<t} A_ti
    u_i)`, `A_ti = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}`, is a unit lower
    triangular system solved by forward substitution for all chunks at
    once; only `S0 -> S_C` runs chunk after chunk. `e^{G_t - G_i}` is never
    split into `e^{G_t} e^{-G_i}` across a whole chunk (64 steps of decay
    down to e^-320 leave float32): each sub-chunk of KDA_SUBCHUNK rows
    measures its exponents from its own first row, so every factor lies
    within e^+-80."""
    t, n, d = q.shape
    c = min(KDA_CHUNK, -(-t // KDA_SUBCHUNK) * KDA_SUBCHUNK)
    sub = KDA_SUBCHUNK
    pad = -t % c
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, (0, pad))
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    nc, ns = (t + pad) // c, c // sub

    def chunks(a):                       # [T, n, ...] -> [N, n, C, ...]
        return jnp.moveaxis(a.reshape((nc, c) + a.shape[1:]), 1, 2)

    with jax.named_scope("kda_state"):
        q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
        cum = jnp.cumsum(g, axis=2)                          # G, inclusive
        # r_I: G just before sub-chunk I's first row
        ref = (cum - g)[:, :, ::sub]                         # [N, n, ns, d]
        row_ref = jnp.repeat(ref, sub, axis=2)               # [N, n, C, d]
        hat = jnp.exp(cum - row_ref)                 # e^{G_t - r_I(t)} <= 1
        # e^{r_I - G_i} for the columns i of sub-chunks <= I, else 0
        expo = ref[:, :, :, None, :] - cum[:, :, None, :, :]  # [N,n,ns,C,d]
        col_sub = jnp.arange(c) // sub
        seen = col_sub[None, :] <= jnp.arange(ns)[:, None]    # [ns, C]
        bar_k = k[:, :, None] * jnp.exp(
            jnp.where(seen[:, :, None], jnp.minimum(expo, 80.0), -jnp.inf))

        def against_bar(rows):           # [N, n, C, d] -> [N, n, C, C]
            r = rows.reshape(rows.shape[:2] + (ns, sub, d))
            return f32mm("bnIsc,bnIic->bnIsi", r, bar_k) \
                .reshape(rows.shape[:2] + (c, c))

        lower = jnp.tril(jnp.ones((c, c), bool))
        a_mat = jnp.where(lower & ~jnp.eye(c, dtype=bool),
                          against_bar(k * hat), 0.0) * beta[..., None]
        b_mat = jnp.where(lower, against_bar(q * hat), 0.0)
        k_dec = k * jnp.exp(cum)                              # k e^{G_t}
        rhs = jnp.concatenate([v, k_dec], -1) * beta[..., None]

        def substitute(i, sol):
            row = jax.lax.dynamic_index_in_dim(a_mat, i, 2, keepdims=False)
            new = jax.lax.dynamic_index_in_dim(rhs, i, 2, keepdims=False) \
                - f32mm("bni,bnie->bne", row, sol)
            return jax.lax.dynamic_update_index_in_dim(sol, new, i, 2)

        sol = jax.lax.fori_loop(0, c, substitute, jnp.zeros_like(rhs))
        tv, w_mat = sol[..., :d], sol[..., d:]
        q_dec = q * jnp.exp(cum)
        total = cum[:, :, -1]                                 # G_C [N, n, d]
        k_rest = k * jnp.exp(total[:, :, None] - cum)         # k e^{G_C-G_i}

        def chunk_step(state, xs):
            tv_c, w_c, qd_c, b_c, kr_c, tot_c = xs
            u = tv_c - f32mm("nck,nkv->ncv", w_c, state)
            o = f32mm("nck,nkv->ncv", qd_c, state) \
                + f32mm("nci,niv->ncv", b_c, u)
            state = jnp.exp(tot_c)[..., None] * state \
                + f32mm("nck,ncv->nkv", kr_c, u)
            return state, o

        state, o = jax.lax.scan(
            chunk_step, jnp.zeros((n, d, d), jnp.float32),
            (tv, w_mat, q_dec, b_mat, k_rest, total))
        o = jnp.moveaxis(o, 1, 2).reshape(t + pad, n, d)[:t]
        return o, state


# ------------------------------------------------------------------- MLA

def _yarn_mscale(scaling, a):
    """YaRN's m(a) = 0.1 a ln(factor) + 1 (1 where nothing is stretched)."""
    return 0.1 * a * math.log(scaling["factor"]) + 1.0 \
        if scaling["factor"] > 1 else 1.0


def mla_scale(cfg):
    """The softmax scale of the MLA layers: (nope + rope)^-0.5, times
    m(mscale_all_dim)^2 under YaRN."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s = cfg.rope_scaling
    if s and s.get("mscale_all_dim", 0):
        scale *= _yarn_mscale(s, s["mscale_all_dim"]) ** 2
    return scale


def rope_frequencies(cfg, rope=None):
    """(the R/2 rotary frequencies, float32; the factor on cos and sin) of
    a rotated width `rope` (MLA's `qk_rope_head_dim` unless given).
    Plain rotary unless the configuration declares `rope_scaling` (YaRN:
    factor s, original length L, beta_fast, beta_slow, mscale,
    mscale_all_dim): the pairs that turn fewer than beta_slow times over L
    positions are slowed by s, those that turn more than beta_fast times
    keep their frequency, a linear ramp between; cos and sin carry
    m(mscale) / m(mscale_all_dim)."""
    rope, theta = rope or cfg.qk_rope_head_dim, cfg.rope_theta
    half = rope // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    s = cfg.rope_scaling
    if not s:
        return inv, 1.0

    def pair_of(turns):
        return rope * math.log(s["original_max_position_embeddings"]
                               / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(s["beta_fast"])), 0)
    high = min(math.ceil(pair_of(s["beta_slow"])), rope - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / s["factor"] * ramp
    return inv, _yarn_mscale(s, s.get("mscale", 1)) \
        / _yarn_mscale(s, s.get("mscale_all_dim", 0))


def rotary(x, positions, cfg):
    """x [..., T, (heads,) R] at `positions` [..., T], half-split pairs."""
    half = x.shape[-1] // 2
    inv, factor = rope_frequencies(cfg, x.shape[-1])
    ang = positions.astype(jnp.float32)[..., None] * inv
    if x.ndim == ang.ndim + 1:                                # a heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla_project(x, w, cfg, positions):
    """(q_n [..., T, n, nope], rotated q_r [..., T, n, rope], the latent row
    [..., T, rank + rope] = [RMSNorm(c), rotated k_r] as cached, the
    head-wise gate [..., T, n] or None). Queries come through the
    bottleneck `W_qb RMSNorm(W_qa x)` where the configuration declares
    `q_lora_rank`, else straight from `W_q x`; the gate is there unless
    `mla_gate` is off."""
    n = cfg.num_heads
    nope, rope, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = mm("...c,cd->...d",
               rms_norm(mm("...h,hc->...c", x, w["wq_a"]), w["qnorm"],
                        cfg.rms_norm_eps), w["wq_b"])
    else:
        q = mm("...h,hc->...c", x, w["wq"])
    q = q.reshape(x.shape[:-1] + (n, nope + rope))
    q_n, q_r = q[..., :nope], rotary(q[..., nope:], positions, cfg)
    a = mm("...h,hc->...c", x, w["wa"])
    latent = jnp.concatenate(
        [rms_norm(a[..., :rank], w["cnorm"], cfg.rms_norm_eps),
         rotary(a[..., rank:], positions, cfg)], -1)
    gate = jax.nn.sigmoid(mm("...h,hn->...n", x, w["wgate"])) \
        if cfg.mla_gate else None
    return q_n, q_r, latent.astype(jnp.bfloat16), gate


# the float32 scores one block of prefill queries may take: [n, block, keys]
MLA_SCORE_BYTES = 1 << 29


def mla_prefill_block(n, t):
    """Queries a block of the prefill attention takes, so that its scores
    against up to `t` keys stay within MLA_SCORE_BYTES: `t` (one block, the
    whole square) where that fits, else a multiple of 128."""
    rows = MLA_SCORE_BYTES // (4 * n * t)
    return t if rows >= t else max(128, rows // 128 * 128)


def mla_prefill(q_n, q_r, latent, gate, w, cfg):
    """Expanded form over one request's own tokens [T] (a request starts at
    position 0: no earlier rows to read). Padding sits after the real
    tokens, so causality keeps it out of every row that is read. Queries go
    a block at a time (`mla_prefill_block`) against the keys up to the
    block's last row: the triangle's work, and scores that fit whatever
    `n * T * T` is."""
    with jax.named_scope("mla_attn"):
        n, nope, vd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        rank = cfg.kv_lora_rank
        t = latent.shape[0]
        kv = mm("tr,rc->tc", latent[:, :rank], w["wkvb"]) \
            .reshape(t, n, nope + vd)
        scale = mla_scale(cfg)
        block = mla_prefill_block(n, t)

        def attend(lo, hi):
            """Queries [lo, hi) against the keys before `hi`."""
            scores = (mm("qnd,knd->nqk", q_n[lo:hi], kv[:hi, :, :nope])
                      + mm("qnd,kd->nqk", q_r[lo:hi], latent[:hi, rank:])) \
                * scale
            # row i is position lo + i; a first block's mask is spelled as
            # it always was, so the one-block program is the same program
            seen = jnp.ones((hi - lo, hi), bool)
            scores = jnp.where(jnp.tril(seen, lo) if lo else jnp.tril(seen),
                               scores, -jnp.inf)
            return mm("nqk,knd->qnd", jax.nn.softmax(scores, -1),
                      kv[:hi, :, nope:])

        o = [attend(lo, min(lo + block, t)) for lo in range(0, t, block)]
        o = o[0] if len(o) == 1 else jnp.concatenate(o)
        if gate is not None:
            o = o * gate[..., None]
        return mm("tc,ch->th", o.reshape(t, n * vd), w["wo"])


def mla_decode(q_n, q_r, rows, pos, gate, w, cfg):
    """Absorbed form, one token a slot: q_n [S, n, nope], q_r [S, n, rope],
    `rows` [S, L, rank + rope] the slot's latent rows (its own new row
    written), `pos` [S] the new token's position. `q_n W_b^K` meets `c`
    directly, and the softmax-weighted `c` goes through `W_b^V`."""
    with jax.named_scope("mla_attn"):
        n, nope, vd = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        rank = cfg.kv_lora_rank
        wkvb = w["wkvb"].reshape(rank, n, nope + vd)
        q_c = mm("snd,rnd->snr", q_n, wkvb[..., :nope])
        scores = (mm("snr,slr->snl", q_c, rows[..., :rank])
                  + mm("snd,sld->snl", q_r, rows[..., rank:])) \
            * mla_scale(cfg)
        seen = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
        scores = jnp.where(seen[:, None, :], scores, -jnp.inf)
        o_c = mm("snl,slr->snr", jax.nn.softmax(scores, -1),
                 rows[..., :rank])
        o = mm("snr,rnd->snd", o_c, wkvb[..., nope:])
        if gate is not None:
            o = o * gate[..., None]
        return mm("sc,ch->sh", o.reshape(o.shape[0], n * vd), w["wo"])


# --------------------------------------------------------------- Mamba-2

def mamba2_project(x, w, cfg, dtype):
    """`W_in x` of the normed input `x` [..., T, H], split: the gate z
    [..., T, I], the convolution's input xBC [..., T, I + 2GN] in the type
    the cache keeps its last rows in, the step's input dt [..., T, heads]."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    chan = inner + 2 * cfg.ssm_groups * cfg.ssm_state_size
    z, xbc, dt = jnp.split(mm("...h,hc->...c", x, w["w_in"]),
                           [inner, inner + chan], axis=-1)
    return z, xbc.astype(dtype), dt


def mamba2_inputs(xbc, dt, w, cfg, history):
    """What the recurrence consumes: x [..., T, heads, P], B and C
    [..., T, G, N] out of `SiLU(conv(xBC) + b)`, the step `softplus(dt +
    dt_bias)` [..., T, heads] and the decay's rate a = -exp(A_log) [heads].
    `history` [..., K-1, C] are the rows of xBC before these (zeros at the
    start of a request): the cache keeps the last K-1 rows of their
    concatenation."""
    heads, p = cfg.ssm_heads, cfg.ssm_head_dim
    g, n = cfg.ssm_groups, cfg.ssm_state_size
    conv = jax.nn.silu(
        short_conv(history, xbc, w["conv_w"].astype(jnp.float32))
        + w["conv_b"].astype(jnp.float32))
    xs, b, c = jnp.split(conv, [heads * p, heads * p + g * n], axis=-1)
    lead = conv.shape[:-1]
    return (xs.reshape(lead + (heads, p)), b.reshape(lead + (g, n)),
            c.reshape(lead + (g, n)),
            jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["a_log"]))


def mamba2_output(y, xs, z, w, cfg):
    """The skip `D x`, the gate before the grouped RMSNorm (G groups of
    I/G, one weight of I), W_out. y, xs [..., heads, P], z [..., I]."""
    g = cfg.ssm_groups
    y = (y + w["d_skip"][:, None] * xs).reshape(z.shape) * jax.nn.silu(z)
    y = rms_norm(y.reshape(z.shape[:-1] + (g, -1)), 1.0, cfg.rms_norm_eps) \
        .reshape(z.shape) * w["ssm_norm"]
    return mm("...c,ch->...h", y, w["w_out"])


def mamba2_recurrent_step(state, xs, b, c, dt, a):
    """One token for every slot: state [S, heads, P, N] float32; xs
    [S, heads, P]; b, c [S, G, N]; dt [S, heads]; a [heads]. Returns (new
    state, y [S, heads, P])."""
    with jax.named_scope("ssm_state"):
        rep = state.shape[1] // b.shape[1]
        b, c = jnp.repeat(b, rep, axis=1), jnp.repeat(c, rep, axis=1)
        state = jnp.exp(dt * a)[..., None, None] * state \
            + (dt[..., None] * xs)[..., None] * b[:, :, None, :]
        return state, f32mm("shpn,shn->shp", state, c)


def mamba2_chunked(xs, b, c, dt, a, valid, chunk):
    """The same recurrence over one sequence from a zero state, `chunk`
    tokens at a time (the SSD form): xs [T, heads, P], b, c [T, G, N], dt
    [T, heads], a [heads], `valid` [T] bool (bucket padding is False: its
    step is 0, so it neither decays nor writes). Returns (y [T, heads, P],
    final state [heads, P, N]).

    With L_t the cumulative log-decay `sum dt a` inside a chunk:
    `y_t = sum_{s<=t} e^{L_t - L_s} (C_t.B_s) dt_s x_s + e^{L_t} S0 C_t` and
    `S_end = e^{L_end} S0 + sum_s e^{L_end - L_s} dt_s x_s (outer) B_s`;
    only `S0 -> S_end` runs chunk after chunk. Every exponent is a
    difference `L_t - L_s <= 0` taken before `exp`, never a product of
    `e^{L_t}` and `e^{-L_s}`: a chunk of fast heads decays past what
    float32 holds."""
    t, heads, p = xs.shape
    g, n = b.shape[1:]
    size = min(chunk, t)
    pad = -t % size
    dt = jnp.where(valid[:, None], dt, 0.0)
    if pad:
        xs, b, c, dt = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                        for v in (xs, b, c, dt))
    nc = (t + pad) // size

    def chunks(v):                       # [T, ...] -> [N, C, ...]
        return v.reshape((nc, size) + v.shape[1:])

    with jax.named_scope("ssm_state"):
        xs, b, c, dt = map(chunks, (xs, b, c, dt))
        cum = jnp.cumsum(dt * a, axis=1)                     # [N, C, heads]
        xdt = xs * dt[..., None]
        # within a chunk: (C_t.B_s) e^{L_t - L_s} on and below the diagonal
        seen = jnp.tril(jnp.ones((size, size), bool))
        decay = jnp.exp(jnp.where(
            seen[None, :, :, None],
            cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        cb = f32mm("ntgk,nsgk->ntsg", c, b)
        weight = jnp.repeat(cb, heads // g, axis=-1) * decay  # [N,C,C,heads]
        y = f32mm("ntsh,nshp->nthp", weight, xdt)
        # what a chunk adds to the state it hands on, and its whole decay
        to_end = jnp.exp(cum[:, -1:, :] - cum)               # [N, C, heads]
        b_h = jnp.repeat(b, heads // g, axis=2)
        added = f32mm("nshp,nshk->nhpk", xdt * to_end[..., None], b_h)
        total = jnp.exp(cum[:, -1, :])                       # [N, heads]

        def carry(state, xs_c):
            add_c, total_c = xs_c
            return total_c[:, None, None] * state + add_c, state

        state, starts = jax.lax.scan(
            carry, jnp.zeros((heads, p, n), jnp.float32), (added, total))
        c_h = jnp.repeat(c, heads // g, axis=2)
        y = y + f32mm("nthk,nhpk->nthp", c_h * jnp.exp(cum)[..., None],
                      starts)
        return y.reshape(t + pad, heads, p)[:t], state


# ------------------------------------------------- grouped-query attention

def gqa_project(x, w, cfg, positions=None, dtype=jnp.bfloat16):
    """(q [..., T, n, d], the paged row [..., T, 2 kv d] = [k, v] of the
    kv key/value heads as cached, in `dtype`). What the configuration
    declares, in this order: a per-head RMSNorm of q and k (`qk_norm`),
    rotary over the whole head at `positions` [..., T] (`gqa_rope`). Neither
    where positions come from elsewhere (the state-space blocks)."""
    q = mm("...h,hc->...c", x, w["wq"])
    if not (cfg.qk_norm or cfg.gqa_rope):
        row = jnp.concatenate([mm("...h,hc->...c", x, w["wk"]),
                               mm("...h,hc->...c", x, w["wv"])], -1)
        return q.reshape(x.shape[:-1] + (cfg.num_heads, cfg.head_dim)), \
            row.astype(dtype)
    lead, d = x.shape[:-1], cfg.head_dim
    q = q.reshape(lead + (cfg.num_heads, d))
    k = mm("...h,hc->...c", x, w["wk"]).reshape(lead + (cfg.num_kv_heads, d))
    if cfg.qk_norm:
        q = rms_norm(q, w["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, w["k_norm"], cfg.rms_norm_eps)
    if cfg.gqa_rope:
        q, k = rotary(q, positions, cfg), rotary(k, positions, cfg)
    row = jnp.concatenate([k.reshape(lead + (-1,)),
                           mm("...h,hc->...c", x, w["wv"])], -1)
    return q, row.astype(dtype)


def _gqa_attend(q, row, seen, w, cfg):
    """q [B, Q, n, d] over rows [B, L, 2 kv d] where `seen` [B, Q, L]:
    query head j reads key/value head j // (n / kv)."""
    with jax.named_scope("gqa_attn"):
        n, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        lead = q.shape[:2]
        k, v = (part.reshape(row.shape[:2] + (kv, d))
                for part in jnp.split(row, 2, axis=-1))
        q = q.reshape(lead + (kv, n // kv, d))
        scores = mm("bqkgd,blkd->bkgql", q, k) * d ** -0.5
        scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
        o = mm("bkgql,blkd->bqkgd", jax.nn.softmax(scores, -1), v)
        return mm("...c,ch->...h", o.reshape(lead + (n * d,)), w["wo"])


def gqa_prefill(q, row, w, cfg):
    """One request's own tokens [T] from position 0: the causal softmax.
    Padding sits after the real tokens, so causality keeps it out."""
    t = q.shape[0]
    return _gqa_attend(q[None], row[None],
                       jnp.tril(jnp.ones((t, t), bool))[None], w, cfg)[0]


def gqa_decode(q, rows, pos, w, cfg):
    """One token a slot: q [S, n, d], `rows` [S, L, 2 kv d] the slot's
    rows (its own new row written), `pos` [S] the new token's position."""
    seen = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    return _gqa_attend(q[:, None], rows, seen[:, None], w, cfg)[:, 0]


# ------------------------------------------------ learned sparse attention
#
# DeepSeek Sparse Attention's selection in front of grouped-query attention:
# an indexer of m heads of e dims over ONE cached key a token scores every
# earlier position, `I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`, and
# the query at t attends only to the `index_topk` positions s <= t of
# largest I (all of them while there are fewer), ties to the lower
# position, one set for every query head.

def layer_norm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def dsa_index_project(x, w, cfg, positions, dtype):
    """(q_I [..., T, m, e] float32, the heads' weights [..., T, m] float32
    with the scale `(m e)^-1/2` in them, k_I [..., T, e] as cached, in
    `dtype`): queries straight from the normed input, the key through a
    LayerNorm, rotary on the first half of every indexer head at
    `positions` [..., T]."""
    m, e = cfg.index_heads, cfg.index_head_dim
    half = e // 2

    def rope(a):
        return jnp.concatenate([rotary(a[..., :half], positions, cfg),
                                a[..., half:]], -1)

    q = rope(mm("...h,hc->...c", x, w["wq_idx"]).reshape(
        x.shape[:-1] + (m, e)))
    k = rope(layer_norm(mm("...h,hc->...c", x, w["wk_idx"]),
                        w["k_idx_norm"], w["k_idx_bias"], cfg.rms_norm_eps))
    wts = mm("...h,hm->...m", x, w["w_idx"]) * (m * e) ** -0.5
    return q, wts, k.astype(dtype)


def dsa_index_scores(q, wts, keys):
    """I [..., Q, L] float32 of queries q [..., Q, m, e] with weights
    [..., Q, m] against `keys` [..., L, e]: bfloat16 operands, float32
    products, the sum over the m heads elementwise (a float32 matmul would
    be one bfloat16 pass on a TPU). A zero is +0, so that one order of the
    scores serves comparison and bits alike."""
    with jax.named_scope("dsa_index"):
        s = mm("...qme,...le->...qml", q, keys)
        out = jnp.sum(jax.nn.relu(s) * wts[..., None], -2)
        return jnp.where(out == 0, 0.0, out)


def _order_key(x):
    """float32 -> uint32 in the same order (-inf lowest)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _kth_key(key, k):
    """uint32 [...]: the largest t with at least k of each row of order
    keys `key` [..., L] at or above it, found exactly by bisection on the
    bits: 32 counting passes over the row, a bit each."""
    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(key >= cand[..., None], -1) >= k,
                         cand, kth)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(key.shape[:-1], jnp.uint32))


def dsa_select_mask(scores, k):
    """[..., L] bool: the k entries of largest `scores` [..., L] float32 of
    each row, ties to the lower index (a row's -inf entries count as the
    lowest, so a row with fewer than k finite ones keeps all of those and
    some of the others, for the caller's causal mask to drop). The k-th
    largest is `_kth_key` of the scores' order keys; positions in order
    break ties only when a row has more at the k-th value than places left.
    Prefill finds the k-th largest with the `row_threshold` kernel and
    comes here for a block with such ties (`dsa_block_threshold`). On a
    v5e the 32 passes over a [512, 32 768] block take 0.80 ms of device
    time and the kernel 0.37 over its causal columns (`tools/op_bench.py
    --dsa-select`, the last block of each bucket)."""
    with jax.named_scope("dsa_select"):
        k = min(k, scores.shape[-1])
        key = _order_key(scores)
        kth = _kth_key(key, k)
        above, at = key > kth[..., None], key == kth[..., None]
        room = k - jnp.sum(above, -1, keepdims=True)
        return jax.lax.cond(
            jnp.any(jnp.sum(at, -1, keepdims=True) > room),
            lambda: above | (at & (jnp.cumsum(at, -1) <= room)),
            lambda: above | at)


def dsa_select_decode(q_idx, wts, keys, pos, cfg):
    """The positions one token a slot attends to: q_idx [S, m, e], wts
    [S, m], `keys` [S, L, e] the slot's indexer keys in position order (its
    own new one written), `pos` [S]. Returns int32 [S, K], K =
    min(index_topk, L): the selected positions, those past `pos` standing
    for nothing (a slot with fewer than K positions)."""
    scores = dsa_index_scores(q_idx[:, None], wts[:, None], keys)[:, 0]
    seen = jnp.arange(keys.shape[1])[None, :] <= pos[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    with jax.named_scope("dsa_select"):
        return jax.lax.top_k(scores, min(cfg.index_topk, keys.shape[1]))[1]


def dsa_decode(q, rows, picked, pos, w, cfg):
    """One token a slot over the K/V rows it selected: q [S, n, d], `rows`
    [S, K, 2 kv d] the rows at the positions `picked` [S, K]."""
    with jax.named_scope("dsa_attn"):
        seen = picked <= pos[:, None]
        return _gqa_attend(q[:, None], rows, seen[:, None], w, cfg)[:, 0]


def dsa_block_threshold(scores, lo, k):
    """What the prefill's query block at position `lo` keeps, from its
    index scores [blk, L] float32 (none read past its own key block): (kth
    uint32 [blk], last int32 [blk], tie bool scalar), for `dsa_kept`. A
    block no wider than k keeps every causal position (kth 0); a wider one
    takes each row's k-th largest order key over its causal columns from
    the `row_threshold` kernel. Where some row holds more keys at its kth
    than the k - above places left, the block breaks the ties as
    `dsa_select_mask` does, lower positions first (`tie`), and `last` is
    the position of the last tie a row takes; else every tie is kept."""
    blk, width = scores.shape
    with jax.named_scope("dsa_select"):
        kth, above, at = jax.lax.cond(
            lo + blk > k,
            lambda: row_threshold(scores, lo + blk, k),
            lambda: (jnp.zeros((blk,), jnp.uint32),
                     jnp.zeros((blk,), jnp.int32),
                     jnp.zeros((blk,), jnp.int32)))
        tie = jnp.any(at > k - above)

        def last_tie():
            pos = jnp.arange(width)
            causal = pos[None, :] <= lo + jnp.arange(blk)[:, None]
            took = dsa_select_mask(jnp.where(causal, scores, -jnp.inf),
                                   k) & causal
            took &= _order_key(scores) == kth[:, None]
            return jnp.max(jnp.where(took, pos, -1), -1)

        last = jax.lax.cond(tie, last_tie,
                            lambda: jnp.full((blk,), width, jnp.int32))
        return kth, last, tie


def dsa_kept(scores, at, kth, last):
    """bool: the scores [blk, K] at positions `at` that a block's rows keep
    by their (kth, last) of `dsa_block_threshold` (causality aside)."""
    key = _order_key(scores)
    return (key > kth[:, None]) \
        | ((key == kth[:, None]) & (at <= last[:, None]))


def dsa_prefill(q, row, q_idx, wts, k_idx, w, cfg):
    """One request's own tokens [T] from position 0 (q [T, n, d], its rows
    [T, 2 kv d], the indexer's q_idx [T, m, e], wts [T, m], k_idx [T, e]):
    queries go `index_block` at a time. For a block, the index scores
    against the key blocks up to its own (the triangle), the selection,
    then attention over the selected keys, masked dense a key block at a
    time, each key block read once: the softmax is shifted by the running
    maximum of the selected scores, and what is already summed is rescaled
    when a block raises it, so no score lies above its shift and every
    row's largest term is exactly 1. Padding sits after the real tokens, so
    causality keeps it out of every row that is read.

    The selection is `dsa_block_threshold`'s: a key block's part of it is
    then a comparison with each row's threshold (`dsa_kept`). Returns (the
    layer's output [T, H], the query blocks that broke ties, an int32
    scalar)."""
    n, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t, blk, topk = q.shape[0], cfg.index_block, cfg.index_topk
    tp = -(-t // blk) * blk

    def pad(a):
        return jnp.pad(a, ((0, tp - t),) + ((0, 0),) * (a.ndim - 1))

    # q and the heads' outputs are kept as the rows are (bfloat16 as the
    # matmuls take them): half the bytes of float32 at 32k tokens
    q = pad(q.astype(row.dtype)).reshape(tp, kv, n // kv, d)
    k, v = (pad(part).reshape(tp, kv, d) for part in jnp.split(row, 2, -1))
    q_idx, wts, k_idx = pad(q_idx), pad(wts), pad(k_idx)
    # the shift before a query's first selected key: finite, so that a
    # block with nothing selected rescales by exp(0) and adds exp(-inf)
    floor = jnp.finfo(jnp.float32).min
    rows = jnp.arange(blk)

    def block(i, carry):
        out, ties, scores = carry
        lo = i * blk
        q_i = jax.lax.dynamic_slice_in_dim(q_idx, lo, blk)
        w_i = jax.lax.dynamic_slice_in_dim(wts, lo, blk)

        def score(j, acc):
            s = dsa_index_scores(q_i, w_i, jax.lax.dynamic_slice_in_dim(
                k_idx, j * blk, blk))
            return jax.lax.dynamic_update_slice_in_dim(acc, s, j * blk, 1)

        # the block's row of scores is written over the last block's: what
        # lies past its own key block is never read
        scores = jax.lax.fori_loop(0, i + 1, score, scores)
        kth, last, tie = dsa_block_threshold(scores, lo, topk)
        q_b = jax.lax.dynamic_slice_in_dim(q, lo, blk)

        def attend(j, carry):
            top, total, acc = carry
            k_j, v_j = (jax.lax.dynamic_slice_in_dim(a, j * blk, blk)
                        for a in (k, v))
            at_j = j * blk + rows[None, :]
            seen = dsa_kept(jax.lax.dynamic_slice_in_dim(scores, j * blk,
                                                         blk, 1),
                            at_j, kth, last) & (at_j <= lo + rows[:, None])
            s = jnp.where(seen, mm("qkgd,lkd->kgql", q_b, k_j) * d ** -0.5,
                          -jnp.inf)
            new = jnp.maximum(top, jnp.max(s, -1))
            p = jnp.exp(s - new[..., None])
            scale = jnp.exp(top - new)
            return new, total * scale + jnp.sum(p, -1), \
                acc * scale[..., None] + mm("kgql,lkd->kgqd", p, v_j)

        with jax.named_scope("dsa_attn"):
            heads = (kv, n // kv, blk)
            _, total, acc = jax.lax.fori_loop(0, i + 1, attend, (
                jnp.full(heads, floor, jnp.float32),
                jnp.zeros(heads, jnp.float32),
                jnp.zeros(heads + (d,), jnp.float32)))
            o = (acc / total[..., None]).transpose(2, 0, 1, 3)
        return jax.lax.dynamic_update_slice_in_dim(
            out, o.reshape(blk, n * d).astype(out.dtype), lo, 0), \
            ties + tie.astype(jnp.int32), scores

    o, ties, _ = jax.lax.fori_loop(0, tp // blk, block, (
        jnp.zeros((tp, n * d), row.dtype), jnp.int32(0),
        jnp.zeros((blk, tp), jnp.float32)))
    return mm("tc,ch->th", o[:t], w["wo"]), ties


# ------------------------------------------------------------------- EVA

# the float32 scores one block of prefill queries may take: [n, block, keys]
EVA_SCORE_BYTES = 1 << 29


def eva_project(x, w, cfg, positions, dtype):
    """(q [..., T, n, d] rotated, the cached row [..., T, 2 n d] = [rotated
    k, v] of all n heads, in the cache's `dtype`). Rotary over the whole
    head at the absolute `positions` [..., T]."""
    n, d = cfg.num_heads, cfg.head_dim
    q, k, v = (mm("...h,hc->...c", x, w[name]).reshape(
        x.shape[:-1] + (n, d)) for name in ("wq", "wk", "wv"))
    q, k = rotary(q, positions, cfg), rotary(k, positions, cfg)
    row = jnp.concatenate([k, v], -2)                    # [..., T, 2n, d]
    return q, row.reshape(x.shape[:-1] + (2 * n * d,)).astype(dtype)


def eva_summaries(rows, live, w, cfg):
    """The summary row of each chunk from its tokens' cached rows: `rows`
    [..., m, C, 2 n d] (C = `eva_chunk`), `live` [..., m, C] bool the tokens
    that exist -> [..., m, 2 n d] = [kbar, vbar] in the rows' type, with
    the layer's per-head `phi` and `mu` [n, d] float32: `a = softmax_j(s
    <k_j, phi>)` over the chunk's live tokens, `kbar = sum a k + mu`,
    `vbar = sum a v`.
    A chunk with no live token gives `[mu, 0]`. Float32 throughout: the
    rows are read as cached, so prefill and decode pool the same numbers."""
    with jax.named_scope("eva_summary"):
        n, d = cfg.num_heads, cfg.head_dim
        kv = rows.astype(jnp.float32).reshape(rows.shape[:-1] + (2, n, d))
        k, v = kv[..., 0, :, :], kv[..., 1, :, :]        # [..., m, C, n, d]
        score = jnp.sum(k * w["phi"], -1) * d ** -0.5    # [..., m, C, n]
        score = jnp.where(live[..., None], score, -1e30)
        a = jnp.where(live[..., None], jax.nn.softmax(score, -2), 0.0)
        kbar = jnp.sum(a[..., None] * k, -3) + w["mu"]
        vbar = jnp.sum(a[..., None] * v, -3)
        out = jnp.concatenate([kbar, vbar], -2)
        return out.reshape(out.shape[:-2] + (2 * n * d,)) \
            .astype(rows.dtype)


def _eva_attend(q, rows, seen, w, cfg):
    """q [B, Q, n, d] over `rows` [B, L, 2 n d] (token rows and summary
    rows alike: [k, v] or [kbar, vbar]) where `seen` [B, Q, L]: ONE softmax
    over all of them, float32 scores of bfloat16 operands."""
    with jax.named_scope("eva_attn"):
        n, d = cfg.num_heads, cfg.head_dim
        kv = rows.reshape(rows.shape[:2] + (2, n, d))
        scores = mm("bqnd,blnd->bnql", q, kv[:, :, 0]) * d ** -0.5
        scores = jnp.where(seen[:, None], scores, -jnp.inf)
        o = mm("bnql,blnd->bqnd", jax.nn.softmax(scores, -1), kv[:, :, 1])
        return mm("...c,ch->...h", o.reshape(q.shape[:2] + (n * d,)),
                  w["wo"])


def eva_prefill_block(n, window, keys):
    """Queries a block of the prefill attention takes so that its scores
    against up to `keys` rows stay within EVA_SCORE_BYTES: the window, or
    the largest halving of it that fits (never under 128 where the window
    is larger)."""
    block = window
    while block > 128 and 4 * n * block * keys > EVA_SCORE_BYTES:
        block //= 2
    return block


def eva_prefill(q, row, summaries, w, cfg):
    """One request's own tokens [T] from position 0. Queries go a block of
    one window (or a halving of it, `eva_prefill_block`) at a time: against
    the token rows of their own window up to the block's end, causally, and
    the summaries of every chunk of the windows closed before it; never
    `T x T`. Padding sits after the real tokens, so causality keeps it out
    of every row that is read, and a window the real tokens never reach
    closes on nobody."""
    t = q.shape[0]
    win, per = cfg.eva_window, cfg.eva_window // cfg.eva_chunk
    block = eva_prefill_block(cfg.num_heads, win,
                              win + (t - 1) // win * per)
    out = []
    for lo in range(0, t, block):
        hi, start = min(lo + block, t), lo // win * win
        closed = start // cfg.eva_chunk
        keys = jnp.concatenate([summaries[:closed], row[start:hi]])
        seen = jnp.concatenate(
            [jnp.ones((hi - lo, closed), bool),
             jnp.tril(jnp.ones((hi - lo, hi - start), bool), lo - start)],
            1)
        out.append(_eva_attend(q[None, lo:hi], keys[None], seen[None], w,
                               cfg)[0])
    return out[0] if len(out) == 1 else jnp.concatenate(out)


def eva_decode(q, rows, pos, w, cfg):
    """One token a slot: q [S, n, d], `rows` [S, L, 2 n d] the slot's dense
    view (`eva_window` ring rows, its own new row written, then one row a
    chunk), `pos` [S] the new token's position. Visibility is by position:
    ring rows up to `pos % window`, the summaries of the closed windows.

    The view is read as it lies, rows of 2 n d values: splitting a row into
    heads would make the compiler copy the whole view into another tiling
    first. So the heads stay inside the row and the query and the
    probabilities are spread instead: scores are `rows @ Q`, with Q [2 n d,
    n] holding head j's query in column j at head j's key entries and
    zeros elsewhere, and the weighted rows are `P^T @ rows` [n, 2 n d], of
    which head j's value entries of row j are kept. Two passes over the
    view, both matmuls batched over slots alone; the zeros add nothing, so
    the numbers are `_eva_attend`'s."""
    with jax.named_scope("eva_attn"):
        n, d = cfg.num_heads, cfg.head_dim
        s = q.shape[0]
        win, per = cfg.eva_window, cfg.eva_window // cfg.eva_chunk
        ring = jnp.arange(win)[None, :] <= (pos % win)[:, None]
        chunks = jnp.arange(rows.shape[1] - win)[None, :] \
            < (pos // win * per)[:, None]
        seen = jnp.concatenate([ring, chunks], 1)                 # [S, L]
        own = jnp.eye(n, dtype=q.dtype)                  # head j, column j
        spread = (q[:, :, :, None] * own[:, None, :]).reshape(s, n * d, n)
        spread = jnp.concatenate([spread, jnp.zeros_like(spread)], 1)
        scores = mm("slc,scn->sln", rows, spread) * d ** -0.5
        scores = jnp.where(seen[..., None], scores, -jnp.inf)
        every = mm("sln,slc->snc", jax.nn.softmax(scores, 1), rows)
        o = every[:, :, n * d:].reshape(s, n, n, d)      # [S, j, head, d]
        o = jnp.sum(o * own[None, :, :, None], 1)        # its own head's
        return mm("sc,ch->sh", o.reshape(s, n * d), w["wo"])


# ------------------------------------------------------------- experts

COUNTERS = ("moe_pairs_total", "moe_pairs_local", "moe_experts_hit",
            "moe_expert_max")

# the float32 hidden activations [E, T, f] of the held experts one pass of
# the expert share may take
MOE_HIDDEN_BYTES = 1 << 30


def moe_token_block(e, t, f):
    """Tokens a pass of the expert share takes so that E x tokens x f
    float32 stay within MOE_HIDDEN_BYTES: `t` (one pass) where that fits,
    else the largest power of two that does."""
    if 4 * e * t * f <= MOE_HIDDEN_BYTES:
        return t
    return 1 << int(math.log2(MOE_HIDDEN_BYTES // (4 * e * f)))


def route(x, w, cfg):
    """Sigmoid scores over all routed experts in float32; the choice on
    score + bias, group-limited; weights the chosen scores normalised, times
    the scaling factor. Where the configuration declares `router_score`
    "softmax": a softmax over all routed experts in float32, the top k, their
    probabilities normalised to sum 1 (no groups, no bias, no factor).
    Returns (ids [T, k] global, weights [T, k])."""
    if cfg.router_score == "softmax":
        p = jax.nn.softmax(f32mm("th,hr->tr", x.astype(jnp.float32),
                                 w["router"]), -1)
        picked, ids = jax.lax.top_k(p, cfg.experts_per_tok)
        return ids, picked / jnp.sum(picked, -1, keepdims=True)
    r, groups = cfg.n_routed_experts, cfg.n_group
    s = jax.nn.sigmoid(f32mm("th,hr->tr", x.astype(jnp.float32),
                             w["router"]))
    biased = s + w["router_bias"]
    group_score = jnp.sum(jax.lax.top_k(
        biased.reshape(-1, groups, r // groups), 2)[0], -1)
    _, keep = jax.lax.top_k(group_score, cfg.topk_group)
    group_ok = jnp.any(keep[:, :, None] == jnp.arange(groups), 1)
    masked = jnp.where(jnp.repeat(group_ok, r // groups, axis=1), biased,
                       -jnp.inf)
    _, ids = jax.lax.top_k(masked, cfg.experts_per_tok)
    picked = jnp.take_along_axis(s, ids, -1)
    return ids, picked / jnp.sum(picked, -1, keepdims=True) \
        * cfg.routed_scaling_factor


def moe_share(x, w, cfg, live):
    """The part of the expert layer this chip gives for tokens `x` [T, H]:
    it routes over all `n_routed_experts`, computes the `num_experts` it
    holds (global ids from `experts_first`) for the tokens that chose them,
    and adds the shared expert where the configuration has one (a
    `shared_intermediate_size`). What the absent experts would add is left
    out; nothing stands in for them. `live` [T] bool marks the rows that are
    someone's token (counters only). An expert is SwiGLU or, where the
    configuration declares `moe_act` "relu2", `W_down relu(W_up x)^2`,
    routed and shared alike. Every held expert runs over every token,
    weighted by the picks; where that would hold more than MOE_HIDDEN_BYTES
    of one float32 [E, T, f] it runs over the tokens a block at a time
    (`moe_token_block`). Returns (y [T, H], counters int32 [4] in the order
    of COUNTERS)."""
    with jax.named_scope("moe_experts"):
        e = cfg.num_experts
        ids, weights = route(x, w, cfg)
        local_id = ids - cfg.experts_first
        local = (local_id >= 0) & (local_id < e)
        t = x.shape[0]
        dense_w = jnp.zeros((t, e + 1), jnp.float32).at[
            jnp.arange(t)[:, None], jnp.where(local, local_id, e)
        ].add(weights)[:, :e]
        relu2 = cfg.moe_act == "relu2"   # W_down relu(W_up x)^2, no gate

        def held(x, dense_w):
            hid = jnp.square(jax.nn.relu(mm("th,ehf->etf", x, w["we_up"]))) \
                if relu2 else \
                jax.nn.silu(mm("th,ehf->etf", x, w["we_gate"])) \
                * mm("th,ehf->etf", x, w["we_up"])
            return mm("etf,efh->th", hid * dense_w.T[:, :, None],
                      w["we_down"])

        block = moe_token_block(e, t, w["we_up"].shape[-1])
        if block == t:
            routed = held(x, dense_w)
        else:
            nb = -(-t // block)
            pad = ((0, nb * block - t), (0, 0))
            routed = jax.lax.map(
                lambda a: held(*a),
                (jnp.pad(x, pad).reshape(nb, block, -1),
                 jnp.pad(dense_w, pad).reshape(nb, block, e))
            ).reshape(nb * block, -1)[:t]
        y = routed
        if cfg.shared_intermediate_size:
            y = y + (relu2_mlp(x, w["ws_up"], w["ws_down"]) if relu2 else
                     swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"]))
        counted = local & live[:, None]
        per_expert = jnp.zeros((e + 1,), jnp.int32).at[
            jnp.where(counted, local_id, e).reshape(-1)].add(1)[:e]
        counters = jnp.stack([
            jnp.sum(live) * cfg.experts_per_tok, jnp.sum(counted),
            jnp.sum(per_expert > 0), jnp.max(per_expert)]).astype(jnp.int32)
        return y, counters
