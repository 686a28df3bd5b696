"""Speculative multi-token decode over the paged serving engine.

The one-token decode loop pays one full target forward per emitted
token; at decode shapes that forward is bandwidth-bound on weights and
KV, so its cost is nearly independent of how many tokens ride in it.
Speculative decoding [Leviathan '23] buys several tokens per target
forward: a cheap DRAFT model proposes γ tokens autoregressively, then
ONE target forward over the (γ+1)-token window verifies them with the
accept/resample rule (`sampling.greedy_verify`). Under greedy decoding
the emitted stream is **bit-identical** to the one-token loop — the
draft only changes how many loop iterations each verify buys, never
what they emit.

Executable discipline (the PR 3 contract, extended):

  * ONE draft decode executable (single-token, draft's own dense cache),
  * ONE target verify executable (fixed [slots, γ+1] window — the
    "second fixed-shape decode executable"),
  * draft prefill compiles per full-prompt bucket (bounded by the
    ladder, like target prefill per suffix bucket),

all counted in `trace_counts` so tests assert the bound.

Cache protocol (the invariant is: at every round boundary the draft's
dense cache and the target's paged pool hold the SAME committed tokens,
and `draft_pos == target_pos`):

  1. draft proposes d_1..d_γ with γ single-token decodes (writing t0,
     d_1..d_{γ-1} into its cache), plus ONE extra feed of d_γ so a
     fully-accepted window leaves the draft cache complete — its
     proposal is discarded;
  2. the target verify forward writes K/V for all γ+1 window tokens
     through the slot's block table (lazy block growth provisioned by
     `ensure_slot_capacity(tokens=γ+1)` before the step — the scheduler
     preempts under pressure exactly as for one-token growth);
  3. REJECTION IS A POSITION ROLLBACK: pos (both engines') advances by
     n_accepted+1 instead of γ+1. Rejected-draft K/V beyond the new pos
     stays physically in already-owned blocks — position masking makes
     it invisible, the next round overwrites it, and NO block reference
     moves, so shared prefix blocks are never freed or COW-broken by a
     rejection.

Preemption/restart needs no new machinery: `reset_slot` clears both
caches and the scheduler's recompute requeue replays prompt+generated
through `prefill` (which prefills the draft too), so a preempted
request resumes bit-identically mid-stream.

The draft is either a caller-supplied small GPT from the same artifact
family (same vocab) or `truncated_draft` — the target's own first K
layers sharing the target's parameter arrays (no second weight copy).

Acceptance rate, draft/verify wall-time histograms and tokens/sec flow
into the unified metrics registry; `tools/serve_report.py` carries
per-request spec_proposed/spec_accepted and `tools/metrics_report.py
--compare` treats an acceptance-rate drop as a failure-class regression.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.layer.layers import functional_call, functional_state
from ..observability import faults as _faults
from ..observability import metrics as _metrics
from ..observability import numerics as _numerics
from ..profiler import RecordEvent, TracerEventType
from . import blocks
from . import kv_cache as kvc
from . import sampling
from .engine import PagedEngineConfig, PagedGenerationEngine

__all__ = ["SpecDecodeConfig", "SpeculativeEngine", "truncated_draft"]

_M_DRAFT_SECONDS = _metrics.histogram(
    "serving_spec_draft_seconds",
    "Wall time of one speculative round's draft proposal loop")
_M_VERIFY_SECONDS = _metrics.histogram(
    "serving_spec_verify_seconds",
    "Wall time of one speculative round's target verify forward")


def truncated_draft(model, num_layers):
    """A draft GPT = the target's first `num_layers` blocks, sharing the
    target's parameter arrays (embeddings, the kept blocks, final LN —
    no second weight copy). The truncation is a quality knob only:
    correctness never depends on the draft, acceptance rate does."""
    from ..text.models.gpt import GPT
    num_layers = int(num_layers)
    if not 1 <= num_layers <= model.cfg.num_layers:
        raise ValueError(
            f"draft_layers={num_layers} must be in 1..target layers "
            f"({model.cfg.num_layers})")
    draft = GPT(dataclasses.replace(model.cfg, num_layers=num_layers))
    draft.eval()
    own = set(draft.state_dict())
    state = {k: v for k, v in model.state_dict().items() if k in own}
    draft.set_state_dict(state)
    return draft


class SpecDecodeConfig(PagedEngineConfig):
    """PagedEngineConfig plus the speculative knobs. gamma: draft tokens
    proposed per round (each round emits 1..gamma+1 tokens).
    draft_layers: layer count of the auto-built truncated draft (ignored
    when an explicit draft model is passed to the engine). Greedy only:
    the stochastic accept/resample needs the draft's probabilities,
    which the greedy-exact pipeline deliberately never materializes."""

    def __init__(self, gamma=4, draft_layers=1, **kwargs):
        super().__init__(**kwargs)
        if self.decode_strategy != "greedy":
            raise ValueError(
                "speculative decode is greedy-only (got decode_strategy="
                f"{self.decode_strategy!r}); the sampling path needs "
                "draft probabilities for the stochastic accept rule")
        self.gamma = int(gamma)
        if self.gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        self.draft_layers = int(draft_layers)
        if self.capture_logits:
            raise ValueError(
                "capture_logits is not supported on the speculative "
                "engine: its decode path is the verify window, which "
                "never threads last-token logits out — point quality "
                "harnesses at a PagedGenerationEngine instead")

    _DICT_FIELDS = PagedEngineConfig._DICT_FIELDS + ("gamma", "draft_layers")


class SpeculativeEngine(PagedGenerationEngine):
    """PagedGenerationEngine whose decode step is a speculative round.

    Public contract additions over the paged engine: `decode_many()`
    returns (tokens [slots, gamma+1], n_emit [slots]) — the scheduler
    appends the first n_emit[s] tokens of slot s's row (truncating at
    eos / max_new_tokens); `decode_write_tokens` widens slot growth to
    the whole verify window. The inherited one-token `decode()` remains
    available but untraced unless called."""

    def __init__(self, model, config=None, draft=None, **kwargs):
        config = config or SpecDecodeConfig(**kwargs)
        if not isinstance(config, SpecDecodeConfig):
            raise TypeError("SpeculativeEngine needs a SpecDecodeConfig")
        super().__init__(model, config)
        from ..text.models.gpt import GPT, GPTForGeneration
        if draft is None:
            draft = truncated_draft(self._model, config.draft_layers)
        if isinstance(draft, GPTForGeneration):
            draft = draft.gpt
        if not isinstance(draft, GPT):
            raise TypeError("draft must be a GPT/GPTForGeneration")
        if draft.cfg.vocab_size != self._model.cfg.vocab_size:
            raise ValueError(
                "draft and target must share a vocabulary (same artifact "
                f"family): {draft.cfg.vocab_size} vs "
                f"{self._model.cfg.vocab_size}")
        if config.max_len > draft.cfg.max_position_embeddings:
            raise ValueError(
                f"max_len={config.max_len} exceeds the draft's "
                f"max_position_embeddings="
                f"{draft.cfg.max_position_embeddings}")
        self.draft_model = draft
        self._draft_params, self._draft_buffers = functional_state(draft)
        dcfg = draft.cfg
        dkv = kvc.alloc_cache(
            dcfg.num_layers, config.slots, config.max_len, dcfg.num_heads,
            dcfg.hidden_size // dcfg.num_heads,
            self._draft_params["wte.weight"].dtype)
        self._draft_kv = self._place_draft_kv(dkv.layers)
        self._draft_pos = np.zeros((config.slots,), np.int32)
        self.trace_counts["draft_decode"] = 0
        self.trace_counts["spec_verify"] = 0
        self.trace_counts["draft_prefill"] = {}
        # weight_dtype="int8" composes: the draft's decode matmuls run
        # from the same quantized representation as the target's verify
        # (the truncated draft SHARES the target arrays, so its codes
        # quantize from the identical weights)
        self._build_draft_decode_params()
        # cached through the same persistent tier as the target's
        # executables; the compile signature now includes the draft's
        # config (set above), so draft-shape changes can never alias
        self._draft_decode = self._cached(self._draft_decode_fn,
                                          "draft_decode")
        self._spec_verify = self._cached(self._spec_verify_fn, "spec_verify")
        self._draft_prefill = {}
        self.last_spec_stats = {}

    def _compile_signature(self):
        """The paged signature plus the draft model's config. During
        `super().__init__` (decode/prefill construction) the draft does
        not exist yet — those executables run the TARGET model only, so
        their signature correctly omits it."""
        sig = super()._compile_signature()
        draft = getattr(self, "draft_model", None)
        if draft is not None:
            sig["draft"] = dataclasses.asdict(draft.cfg)
        return sig

    @property
    def decode_write_tokens(self):
        """A verify forward writes the whole γ+1 window per slot."""
        return self.config.gamma + 1

    def _weight_sources(self):
        """HBM accounting (ISSUE 13): the draft's weights are resident
        too. The id-dedup in the base walk keeps truncated-draft arrays
        that IDENTITY-share the target's (the no-second-copy contract)
        counted once — only genuinely distinct draft buffers add."""
        return super()._weight_sources() + [self._draft_params,
                                            self._draft_decode_params]

    def _kv_arrays(self):
        """The draft's dense KV cache is resident serving state next to
        the target's paged pools."""
        return super()._kv_arrays() + \
            [x for l in self._draft_kv for x in (l.k, l.v)]

    def _build_draft_decode_params(self):
        """Draft params that IDENTITY-share a target array (the
        truncated-draft no-second-copy contract) reuse the target's
        already-quantized `_decode_params` entry — one quantization per
        shared array per build/hot-swap, not two."""
        if self.config.weight_dtype != "int8":
            self._draft_decode_params = self._draft_params
            return
        out, fresh = {}, {}
        for name, arr in self._draft_params.items():
            if arr is self._params.get(name):
                out[name] = self._decode_params[name]
            else:
                fresh[name] = arr
        out.update(self._quantize_params(fresh))
        self._draft_decode_params = out

    def swap_params(self, new_params):
        """Hot-swap (ISSUE 10) for the speculative pair: the target
        swaps like any paged engine, then every draft param that SHARED
        the old target's array (the truncated-draft no-second-copy
        contract) is re-pointed at the new one — target and draft flip
        in the same between-steps window, so acceptance never degrades
        against a stale draft. An independently-weighted draft keeps its
        own arrays (it only ever affects acceptance rate, not output)."""
        old_target = dict(self._params)
        n = super().swap_params(new_params)
        for name, arr in list(self._draft_params.items()):
            if name in old_target and arr is old_target[name]:
                self._draft_params[name] = self._params[name]
        self._build_draft_decode_params()      # re-quantize the new draft
        return n

    # -- draft placement hooks (identity here; the pipeline-parallel
    # composition pins the whole draft onto stage 0's mesh) ------------------
    def _place_draft_kv(self, layers):
        """Where the draft's dense KV cache lives — the default device
        here; `PipelineParallelSpeculativeEngine` overrides to place it
        on the first stage's mesh (draft-on-first-stage)."""
        return layers

    def _draft_feed(self, vec):
        """Placement of a per-slot host vector (the round's t0 tokens,
        the draft positions) before it enters the draft decode
        executable: every call must see it where the executable's own
        outputs live, or the first call traces a second program."""
        return vec

    # -- draft functional forward -------------------------------------------
    def _run_draft(self, params, lk, lv, pos, ids):
        cache = kvc.DecodeCache(
            tuple(kvc.LayerKV(Tensor(k), Tensor(v))
                  for k, v in zip(lk, lv)),
            Tensor(pos))
        out, _ = functional_call(
            self.draft_model, params, self._draft_buffers,
            args=(Tensor(ids),), kwargs={"cache": cache}, train=False)
        logits, new_cache = out
        return (logits._data,
                [l.k._data for l in new_cache.layers],
                [l.v._data for l in new_cache.layers])

    # -- the three executables ----------------------------------------------
    def _draft_decode_fn(self, params, lk, lv, pos, tokens):
        self.trace_counts["draft_decode"] += 1     # trace-time only
        logits, nk, nv = self._run_draft(self._dequant_params(params),
                                         lk, lv, pos, tokens[:, None])
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return nxt, nk, nv, jnp.minimum(pos + 1, self.config.max_len - 1)

    def _spec_verify_fn(self, params, pool, tables, pos, window, *extra):
        self.trace_counts["spec_verify"] += 1      # trace-time only
        # per-tenant adapters (ISSUE 17) ride the VERIFY forward — the
        # target chooses every emitted token (greedy_verify emits the
        # target's choices), so adapted output is exact; the draft stays
        # base and only pays in acceptance rate on adapted slots
        adapters, _ = self._split_extra(extra)
        with self._numerics_scope() as sink:
            logits, npool = self._run_model_paged(
                self._dequant_params(params), pool, tables, pos, window,
                adapters=adapters)
            choices, n_acc, last = sampling.greedy_verify(logits, window)
            # the verify window's logit rows are where a quantized
            # target's corruption first meets emitted tokens
            _numerics.tap("spec.verify_logits", logits)
        npool = self._constrain_pools(npool)
        # advance by accepted+1; rejected-tail K/V stays beyond pos,
        # invisible and overwritten next round (rollback by position).
        # int8 pools: the verify write cannot mask the not-yet-known
        # rejected tail (valid would need n_acc before the forward
        # emits logits), so rejected tokens ride the touched block's
        # abs-max scale for this ONE write — resident tokens in that
        # block re-round once against the inflated scale. The scale
        # itself self-corrects on the next write (rollback puts pos
        # before the block end, so it is re-gathered and its abs-max
        # recomputed over real positions only), and reads are always
        # consistent (code*scale, tail masked by pos) — the residual
        # is bounded extra rounding noise, priced by the spec-quant
        # composition test's 0.9 stream-agreement bar.
        pos_next = jnp.minimum(pos + n_acc + 1, self.config.max_len - 1)
        if sink is None:
            return choices, n_acc, last, npool, pos_next
        return choices, n_acc, last, npool, pos_next, sink

    def _make_draft_prefill(self, bucket):
        def fn(params, lk, lv, pos, slot, ids, length):
            self.trace_counts["draft_prefill"][bucket] = \
                self.trace_counts["draft_prefill"].get(bucket, 0) + 1
            dcfg = self.draft_model.cfg
            local_pos = jnp.zeros((1,), jnp.int32)
            fresh = [kvc.alloc_kv(1, bucket, dcfg.num_heads,
                                  dcfg.hidden_size // dcfg.num_heads,
                                  k.dtype)
                     for k in lk]
            _, nk, nv = self._run_draft(params, [f.k for f in fresh],
                                        [f.v for f in fresh], local_pos,
                                        ids[None, :])
            slot = slot.astype(jnp.int32)
            lk = [jax.lax.dynamic_update_slice(g, n, (slot, 0, 0, 0))
                  for g, n in zip(lk, nk)]
            lv = [jax.lax.dynamic_update_slice(g, n, (slot, 0, 0, 0))
                  for g, n in zip(lv, nv)]
            pos = jax.lax.dynamic_update_slice(
                pos, length[None].astype(pos.dtype), (slot,))
            return lk, lv, pos
        return self._cached(fn, f"draft_prefill[{bucket}]")

    # -- AOT warmup ----------------------------------------------------------
    def executable_names(self):
        return super().executable_names() + \
            ["draft_decode", "spec_verify"] + \
            [f"draft_prefill[{b}]" for b in self.config.prefill_buckets]

    def precompile(self):
        """Target set (paged precompile) plus the speculative set: the
        draft decode/prefill executables and the [slots, γ+1] verify."""
        out = super().precompile()
        c = self.config
        dk = [l.k for l in self._draft_kv]
        dv = [l.v for l in self._draft_kv]
        dpos = jnp.asarray(self._draft_pos)
        out["draft_decode"] = self._draft_decode.warm(
            self._draft_decode_params, dk, dv, dpos,
            jnp.zeros((c.slots,), jnp.int32))
        with blocks.attention_impl(self.attention_impl):
            out["spec_verify"] = self._spec_verify.warm(
                self._decode_params, self._pool,
                jnp.asarray(self._tables), jnp.asarray(self._pos),
                jnp.zeros((c.slots, c.gamma + 1), jnp.int32),
                *self._adapter_args())
        for b in c.prefill_buckets:
            if b not in self._draft_prefill:
                self._draft_prefill[b] = self._make_draft_prefill(b)
            out[f"draft_prefill[{b}]"] = self._draft_prefill[b].warm(
                self._draft_params, dk, dv, dpos, jnp.asarray(0, jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.asarray(1, jnp.int32))
        return out

    # -- public compute API --------------------------------------------------
    def prefill(self, slot, prompt_ids, rng=None, namespace=None):
        """Target prefill (prefix cache, suffix bucket, first token) plus
        the draft prefill of the FULL prompt into its dense cache — the
        draft has no prefix sharing, so its bucket is over the whole
        prompt length. Draft state moves only after the target prefill
        sticks, so an allocation failure leaves both sides untouched."""
        first = super().prefill(slot, prompt_ids, rng=rng,
                                namespace=namespace)
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        bucket = self.bucket_for(prompt.size)
        padded = np.zeros((bucket,), np.int32)
        padded[:prompt.size] = prompt
        if bucket not in self._draft_prefill:
            self._draft_prefill[bucket] = self._make_draft_prefill(bucket)
        with RecordEvent("serving::draft_prefill",
                         TracerEventType.UserDefined,
                         {"bucket": bucket, "length": int(prompt.size),
                          "slot": int(slot)}):
            lk, lv, dpos = self._draft_prefill[bucket](
                self._draft_params, [l.k for l in self._draft_kv],
                [l.v for l in self._draft_kv],
                jnp.asarray(self._draft_pos),
                jnp.asarray(slot, jnp.int32), jnp.asarray(padded),
                jnp.asarray(prompt.size, jnp.int32))
        self._draft_kv = tuple(kvc.LayerKV(k, v) for k, v in zip(lk, lv))
        self._draft_pos = np.array(dpos, np.int32)
        return first

    def reset_slot(self, slot):
        super().reset_slot(slot)
        self._draft_pos[int(slot)] = 0

    def _draft_propose(self):
        """The γ-proposal draft loop of one speculative round: γ
        single-token draft decodes plus the cache-completing extra feed
        of d_γ (its proposal discarded). Returns (window [S, γ+1] device
        array, dk, dv, dpos) — the caller commits the draft cache only
        after the verify sticks. Shared verbatim by the single-device
        and pipeline-parallel verify paths."""
        dk = [l.k for l in self._draft_kv]
        dv = [l.v for l in self._draft_kv]
        dpos = self._draft_feed(jnp.asarray(self._draft_pos))
        feed = self._draft_feed(jnp.asarray(self._last_tokens))
        # the window stays ON DEVICE: fetching each proposal to host
        # would serialize the γ draft dispatches on a round-trip sync
        # apiece; stacked device columns let them pipeline and defer
        # the only host sync of the round to the verify output
        cols = [feed]
        for _ in range(self.config.gamma):
            feed, dk, dv, dpos = self._draft_decode(
                self._draft_decode_params, dk, dv, dpos, feed)
            cols.append(feed)
        # the extra feed writes d_γ's K/V so a fully-accepted window
        # leaves the draft cache complete; its proposal is discarded
        _, dk, dv, dpos = self._draft_decode(
            self._draft_decode_params, dk, dv, dpos, feed)
        return jnp.stack(cols, axis=1), dk, dv, dpos  # window [S, γ+1]

    def decode_many(self):
        """One speculative round for every slot: γ draft proposals, one
        target verify, position rollback. Returns (tokens [S, γ+1],
        n_emit [S]) — slot s emitted tokens[s, :n_emit[s]], and free
        slots round-trip garbage harmlessly exactly as in the one-token
        loop."""
        _faults.fire("serving.decode_step")
        self._fire_kv_quant_chaos()
        self._fire_numerics_chaos()
        self.ensure_decode_capacity()
        c = self.config
        gamma = c.gamma
        t0 = time.perf_counter()
        with RecordEvent("serving::spec_draft", TracerEventType.UserDefined,
                         {"gamma": gamma, "slots": c.slots}):
            window, dk, dv, dpos = self._draft_propose()
        draft_s = time.perf_counter() - t0
        _M_DRAFT_SECONDS.observe(draft_s)
        t1 = time.perf_counter()
        with RecordEvent("serving::spec_verify",
                         TracerEventType.UserDefined,
                         {"window": gamma + 1, "slots": c.slots,
                          "attend": self.attention_impl}), \
                blocks.attention_impl(self.attention_impl):
            vres = self._spec_verify(
                self._decode_params, self._pool,
                jnp.asarray(self._tables), jnp.asarray(self._pos), window,
                *self._adapter_args())
        if self._numerics_armed:
            choices, n_acc, last, pool, pos, sink = vres
            self._ingest_numerics(sink)
        else:
            choices, n_acc, last, pool, pos = vres
        verify_s = time.perf_counter() - t1
        _M_VERIFY_SECONDS.observe(verify_s)
        self._pool = pool
        self._pos = np.array(pos, np.int32)   # owned, writable copy
        self._draft_kv = tuple(kvc.LayerKV(k, v) for k, v in zip(dk, dv))
        # the rollback: both caches advance to committed+0 — the draft's
        # device-side pos (P+γ+1) is discarded for the verified value
        self._draft_pos = self._pos.copy()
        out = np.asarray(choices, np.int32)
        n_emit = np.asarray(n_acc, np.int32) + 1
        # keep the per-slot sampler counters stream-accurate even though
        # spec decode is greedy-only: a v3 handoff of this slot still
        # carries the right generation index
        self._slot_gen += n_emit
        self._last_tokens = np.asarray(last, np.int32).copy()
        self.last_spec_stats = {
            "proposed_per_slot": gamma,
            "draft_s": draft_s, "verify_s": verify_s}
        return out, n_emit
