"""Prefill/decode split: the executable layer of the serving engine.

Generation has two phases with opposite shapes: prefill consumes a whole
prompt (long S, once per request) and decode consumes one token (S=1,
every step, every slot). Compiling them separately is what keeps the hot
step hot:

  - ONE decode executable per (model, slot-config): all S slots advance
    one token through the static cache; its avals never change, so after
    the first call XLA replays the same executable forever. A python-side
    trace counter (incremented only when jax actually retraces) is the
    compile-once proof the tests assert on.
  - a LADDER of prefill executables, one per prompt-length bucket:
    prompts are right-padded to the nearest bucket, so arbitrary lengths
    compile at most `len(buckets)` times instead of once per length.
    Prefill writes the prompt's K/V straight into the chosen slot's rows
    of the global cache and returns the first generated token.

The engine is deliberately model-functional: it freezes the Layer's
params once (`functional_state`) and traces `GPT.forward(cache=...)`
through `functional_call`, so the same eager model object serves both
training and serving without a second weight copy.
"""
import contextlib
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..framework import compile_cache as _cc
from ..nn.layer.layers import functional_call, functional_state
from ..observability import faults as _faults
from ..observability import flight_recorder as _flight_recorder
from ..observability import kvledger as _kvl
from ..observability import numerics as _numerics
from ..profiler import RecordEvent, TracerEventType
from ..profiler import _tracer as _TRACER
from . import blocks
from . import kv_cache as kvc
from . import sampling
from .prefix_cache import PrefixCache


@functools.partial(jax.jit, static_argnums=(1,))
def _quantize_weight(w, axis):
    """One decode-matmul weight -> (int8 codes, broadcast-ready f32
    per-channel scales), entirely on device: abs-max over every axis but
    `axis` (the jnp mirror of `quantization.observers.channel_abs_max`,
    which the weight-quant tests pin it against) and the fake-quant
    round/clip. Jitted once per (shape, axis), so hot-swap
    re-quantization replays cached executables instead of paying a
    device_get -> numpy -> re-upload round-trip in the swap window."""
    w = w.astype(jnp.float32)
    red = tuple(i for i in range(w.ndim) if i != axis)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=red), 1e-30)
    shape = [1] * w.ndim
    shape[axis] = -1
    s_b = s.reshape(shape)
    return blocks.quantize_codes(w, s_b), s_b

__all__ = ["EngineConfig", "GenerationEngine", "PagedEngineConfig",
           "PagedGenerationEngine", "save_for_generation", "make_engine"]


def _span(name, attrs=None):
    """A `serving::*` span (the scheduler's phases use it too)."""
    return RecordEvent(name, TracerEventType.UserDefined, attrs)


class _CallPack:
    """Where each host-owned input of one executable lies in the call's ONE
    int32 upload: `fields` [(name, shape)] back to back. The layout is a
    function of the engine's configuration (and the bucket), so the host
    writes and the trace slices at the same static offsets; a `uint32`
    seed rides as its bits and is bit-cast back in the trace."""

    def __init__(self, fields):
        self.fields = {}
        off = 0
        for name, shape in fields:
            n = int(np.prod(shape, dtype=np.int64))
            self.fields[name] = (off, off + n, tuple(shape))
            off += n
        self.size = off

    def pack(self, **values):
        """The host half: one fresh int32 buffer holding every field."""
        buf = np.empty((self.size,), np.int32)
        for name, (lo, hi, _) in self.fields.items():
            v = np.asarray(values[name])
            if v.dtype == np.uint32:
                v = v.view(np.int32)
            buf[lo:hi] = v.reshape(-1)
        return buf

    def unpack(self, packed):
        """The trace half: {name: static slice of `packed`, reshaped}. An
        argument that is not this layout's array (an older calling
        convention's tables, say) fails here, before anything compiles."""
        if packed.shape != (self.size,) or packed.dtype != jnp.int32:
            raise TypeError(
                f"expected the call's packed int32[{self.size}] upload "
                f"({', '.join(self.fields)}), got "
                f"{packed.dtype}{list(packed.shape)}")
        return {name: packed[lo:hi].reshape(shape)
                for name, (lo, hi, shape) in self.fields.items()}


DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024)
GENCFG_SUFFIX = ".gencfg"


class EngineConfig:
    """Slot/bucket/strategy knobs for one GenerationEngine.

    `compile_cache_dir` attaches a PRIVATE persistent executable cache
    (framework/compile_cache.py) to the engine: prefill/decode (and the
    speculative engine's draft/verify) executables are served from disk
    when warm and committed there when cold, so a restarted process
    skips XLA compilation entirely. None falls back to the process-
    global cache (`compile_cache.attach`), or to plain jit when neither
    exists. The path is machine-local and deliberately NOT part of
    `as_dict()` — a saved artifact records WHAT to compile, each loader
    decides WHERE the executables live."""

    def __init__(self, slots=4, max_len=256, prefill_buckets=None,
                 decode_strategy="greedy", temperature=1.0, top_k=0,
                 top_p=1.0, eos_token_id=None, seed=0,
                 compile_cache_dir=None, numerics_taps=False):
        self.slots = int(slots)
        self.max_len = int(max_len)
        # the ladder always ends in a max_len-sized bucket so every prompt
        # the cache can hold has a prefill executable
        buckets = prefill_buckets or (
            [b for b in DEFAULT_BUCKETS if b < max_len] + [max_len])
        self.prefill_buckets = tuple(sorted(int(b) for b in buckets))
        self.decode_strategy = decode_strategy
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token_id = eos_token_id
        self.seed = int(seed)
        self.compile_cache_dir = compile_cache_dir
        # numerics_taps=True arms the in-trace sentinel plane
        # (observability.numerics): the traced bodies open a sink_scope
        # and return one fused [finite_frac, absmax, rms, sat_frac]
        # vector per tap site as an extra output, fed to the engine's
        # NumericsMonitor after each step. The capture_logits pattern:
        # a different traced program, still compiled exactly once, and
        # the disabled arm's traces are bit-identical to pre-tap code.
        self.numerics_taps = bool(numerics_taps)

    # field names that round-trip through the .gencfg serving record;
    # seed is INCLUDED (it only feeds RNG key VALUES, but recording it
    # keeps a rebuilt engine bit-identical to the saved one) while
    # compile_cache_dir stays machine-local
    _DICT_FIELDS = ("slots", "max_len", "prefill_buckets",
                    "decode_strategy", "temperature", "top_k", "top_p",
                    "eos_token_id", "seed", "numerics_taps")

    def as_dict(self):
        """JSON-serializable ctor kwargs: EngineConfig-family configs
        round-trip through `type(cfg)(**cfg.as_dict())` — the form the
        `.gencfg` serving record stores."""
        out = {}
        for f in self._DICT_FIELDS:
            v = getattr(self, f)
            out[f] = list(v) if isinstance(v, tuple) else v
        return out

    def compile_signature(self):
        """The static half of the persistent-cache key for this config:
        every knob that can change a traced program (strategy and
        sampling parameters are baked into the executables as python
        closures). Seed is EXCLUDED — it only selects RNG key values,
        which ride in as runtime inputs."""
        sig = self.as_dict()
        sig.pop("seed", None)
        return sig


class GenerationEngine:
    """Owns the global static cache + the prefill/decode executables for
    one model. Slot lifecycle (who occupies which slot, retirement,
    refill) belongs to scheduler.Scheduler; this layer only computes."""

    def __init__(self, model, config=None, **kwargs):
        from ..text.models.gpt import GPTForGeneration
        if isinstance(model, GPTForGeneration):
            model = model.gpt
        self._model = model
        # what each layer caches (serving/blocks.py has the protocol): what
        # the layout allows decides which engines and options serve it
        self._layout = blocks.CacheLayout(model.cache_layout())
        if type(self) is not PagedGenerationEngine \
                and not self._layout.allows("any_engine"):
            raise TypeError(
                f"the cache layout of {type(model).__name__} "
                f"({self._layout.kinds()}) is served by "
                f"PagedGenerationEngine, not by {type(self).__name__} (yet)")
        self.config = self._configure(config or EngineConfig(**kwargs))
        if self.config.max_len > model.cfg.max_position_embeddings:
            raise ValueError(
                f"max_len={self.config.max_len} exceeds the model's "
                f"max_position_embeddings={model.cfg.max_position_embeddings}")
        self._params, self._buffers = functional_state(model)
        self._rng = jax.random.key(self.config.seed)
        self._last_tokens = np.zeros((self.config.slots,), np.int32)
        # per-slot sampler RNG (ISSUE 13): slot s's n-th generated token
        # samples with fold_in(key(seed_s), n) — a pure function of the
        # REQUEST's (seed, generation index), never of the slot index,
        # the co-resident batch, or engine history. That is what makes a
        # sampled stream replayable on another slot, another engine, or
        # another host (the v3 KV-handoff RNG field): feed the same
        # (seed, gen) and the continuation is bit-identical. `_slot_gen`
        # holds the generation index of each slot's NEXT token.
        self._slot_seeds = np.zeros((self.config.slots,), np.uint32)
        self._slot_gen = np.zeros((self.config.slots,), np.int32)
        self._rng_nonce = 0
        # per-tenant LoRA adapters (ISSUE 17): the bank's stacked
        # [n_adapters, ...] arrays ride the decode executable as extra
        # runtime inputs (like the sampler rng args) and each slot's
        # int32 adapter id gathers its delta IN-trace — no bank attached
        # means no extra args, so adapter-off engines keep their exact
        # pre-tenancy traces and compile counts
        self._adapter_bank = None
        self._adapter_tree = None
        self._slot_adapter = np.zeros((self.config.slots,), np.int32)
        # trace counters: the python bodies below run ONLY when jax traces,
        # so these counts are the number of compilations, not of calls.
        # A warm persistent-cache load DESERIALIZES the executable and
        # never traces — these staying 0 is the zero-fresh-compiles proof.
        self.trace_counts = {"decode": 0, "prefill": {}}
        # numerics health plane (ISSUE 19): armed at build time like
        # capture_logits. The monitor classifies every step's sink;
        # `_last_decode_args` keeps the last step's inputs alive for the
        # bisection localizer to replay, so an ARMED engine's decode
        # donates nothing (`_decode_donate`): it already compiles
        # another program, the sink rides last, and pays a second pool
        # for the replay. Every other paged engine's decode takes its
        # pool in place. The probe flags route localizer re-traces of
        # `_decode_fn` away from the 'decode' counter.
        self.numerics_monitor = _numerics.NumericsMonitor(
            auto_bundle=False) if self._numerics_armed else None
        self.last_numerics = None
        self.last_localization = None
        self._last_decode_args = None
        self._numerics_probing = False
        self._numerics_probe_layers = None
        self.compile_cache = _cc.CompileCache(self.config.compile_cache_dir) \
            if self.config.compile_cache_dir else None
        self._alloc_state()                    # cache layout hook
        self._build_decode_params()            # weight-quant hook
        self._decode = self._cached(self._decode_fn, "decode",
                                    self._decode_donate)
        self._prefill = {}   # bucket -> cached-jitted fn

    def _configure(self, config):
        """The configuration as this engine serves the model: the paged
        engine sizes its tables by the layout and resolves what is left
        open."""
        return config

    # the arguments of `_decode_fn` its executable updates in place: none
    # for the dense per-slot buffers; the paged engine names its pool
    _decode_donate = ()

    def _cached(self, fn, name, donate_argnums=()):
        """cached_jit over the engine's persistent tier (engine-private
        cache first, process-global cache second, plain jit when
        neither). The static signature pins model + engine config, so
        avals alone can never alias two different programs.
        `donate_argnums` is part of the entry key and survives every
        cache tier (compile_cache.CompileCache.lookup)."""
        return _cc.cached_jit(
            fn, f"serving.{name}",
            static_sig=self._compile_signature(),
            cache=lambda: self.compile_cache,
            donate_argnums=donate_argnums)

    def _compile_signature(self):
        """Model config + engine config, the signature-mode key half
        shared by every executable of this engine."""
        return {"model": dataclasses.asdict(self._model.cfg),
                "engine": type(self).__name__,
                "config": self.config.compile_signature()}

    def _alloc_state(self):
        """Allocate the KV memory layout — dense per-slot buffers here;
        PagedGenerationEngine overrides with the block pool."""
        cfg = self._model.cfg
        self._cache = kvc.alloc_cache(
            cfg.num_layers, self.config.slots, self.config.max_len,
            cfg.num_heads, cfg.hidden_size // cfg.num_heads,
            self._params["wte.weight"].dtype)

    def _build_decode_params(self):
        """Derive the param set the DECODE-path executables consume.
        Identity here (decode serves the same float params as prefill);
        the paged engine overrides for weight_dtype="int8": quantized
        entries become {"q": int8 codes, "scale": broadcast-ready
        per-channel scales} and the decode trace dequantizes them —
        prefill always stays on `self._params`. Re-run after every
        weight hot-swap (`_after_param_swap`)."""
        self._decode_params = self._params

    def _after_param_swap(self):
        """Post-commit hook of `swap_params`: keep derived param views
        (the quantized decode set, a spec engine's shared-draft arrays)
        coherent with the freshly swapped weights."""
        self._build_decode_params()

    # -- functional forward -------------------------------------------------
    def _run_dense(self, params, layers_k, layers_v, pos, ids,
                   adapters=None):
        """GPT cached forward over the dense per-slot buffers (raw arrays)
        -> (logits, new k/v lists)."""
        cache = kvc.DecodeCache(
            tuple(kvc.LayerKV(Tensor(k), Tensor(v))
                  for k, v in zip(layers_k, layers_v)),
            Tensor(pos))
        kwargs = {"cache": cache}
        if adapters is not None:
            kwargs["adapters"] = adapters
        out, _ = functional_call(
            self._model, params, self._buffers, args=(Tensor(ids),),
            kwargs=kwargs, train=False)
        logits, new_cache = out
        return (logits._data,
                [l.k._data for l in new_cache.layers],
                [l.v._data for l in new_cache.layers])

    def _select(self, logits, key):
        c = self.config
        return sampling.select_tokens(
            logits, key=key, strategy=c.decode_strategy,
            temperature=c.temperature, top_k=c.top_k, top_p=c.top_p)

    # -- numerics health plane (ISSUE 19) ------------------------------------
    @property
    def _numerics_armed(self):
        return bool(getattr(self.config, "numerics_taps", False))

    def _numerics_scope(self):
        """sink_scope when the tap plane is armed, else a null scope —
        the disarmed traced body is literally the pre-tap body, so
        disabled engines keep bit-identical programs and trace counts.
        `_numerics_probe_layers` is non-None only while the bisection
        localizer traces a per-layer probe."""
        if not self._numerics_armed:
            return _numerics.null_scope()
        return _numerics.sink_scope(self._numerics_probe_layers)

    def _bump_decode_trace(self):
        """Trace-counter routing: localizer probes re-trace `_decode_fn`
        on purpose; they count under 'numerics_probe', never 'decode',
        so the compile-once assertions stay exact."""
        ctr = "numerics_probe" if self._numerics_probing else "decode"
        self.trace_counts[ctr] = self.trace_counts.get(ctr, 0) + 1

    def _probe_context(self):
        """Trace context wrapped around a localizer probe — identity
        here; the paged engine pins its attention impl so the probe
        traces the same program family as the live decode."""
        return _numerics.null_scope()

    def _ingest_numerics(self, sink):
        """Feed one step's sink through the engine monitor. The FIRST
        nonfinite anomaly triggers the bisection localizer on the saved
        step inputs and THEN the postmortem bundle — so detection,
        localization, and the bundle all land within the same scheduler
        step, and the bundle carries the localizer's annotation."""
        mon = self.numerics_monitor
        new = mon.observe_sink(sink)
        self.last_numerics = {
            site: _numerics.stats_dict(np.asarray(vec, np.float32))
            for site, vec in sink.items()}
        first_bad = next((site for site, kind in new
                          if kind == "nonfinite"), None)
        if first_bad is not None and mon.bundle_path is None:
            loc = self.localize_numerics()
            if loc is not None:
                self.last_localization = loc
                _flight_recorder.annotate("numerics_localization", loc)
            mon.bundle(f"numerics:{first_bad}:nonfinite")

    def localize_numerics(self, sat_frac_max=0.25):
        """NaN bisection localizer: replay the saved last decode step
        through progressively finer per-layer tap sets
        (sink_scope(layers=...)) to name the FIRST unhealthy layer.
        Corruption propagates forward through the stack, so per-layer
        health is monotone and O(log n_layers) probes suffice; each
        distinct probe layer is one extra jit, counted under
        trace_counts['numerics_probe']. Returns the localization record
        (annotated into the postmortem bundle), or None when no decode
        step has run yet."""
        args = self._last_decode_args
        if args is None:
            return None
        n_layers = self._model.cfg.num_layers
        probe_sinks = {}

        def probe_sink(k):
            if k not in probe_sinks:
                self._numerics_probing = True
                self._numerics_probe_layers = (k,)
                try:
                    fn = jax.jit(lambda *a: self._decode_fn(*a)[-1])
                    with self._probe_context():
                        probe_sinks[k] = fn(*args)  # traces HERE, while
                finally:                            # the filter is set
                    self._numerics_probing = False
                    self._numerics_probe_layers = None
            return probe_sinks[k]

        def unhealthy_at(k):
            vec = probe_sink(k).get(f"layer{k}.act")
            if vec is None:
                return False
            return _numerics.stats_unhealthy(
                np.asarray(vec, np.float32), sat_frac_max)

        first = _numerics.bisect_first_unhealthy(n_layers, unhealthy_at)
        rec = {"first_unhealthy_layer": first,
               "site": None if first is None else f"layer{first}.act",
               "stats": None, "probes": len(probe_sinks),
               "layers": n_layers}
        if first is not None:
            rec["stats"] = _numerics.stats_dict(np.asarray(
                probe_sink(first)[f"layer{first}.act"], np.float32))
        return rec

    def _fire_numerics_chaos(self):
        """`numerics.corrupt` chaos hook: poison ONE named decode tensor
        at rest. Caller-interpreted like truncate — fire() returns the
        spec, this hook does the damage, and the tap plane must detect
        AND localize it. nan/inf set one element of the named weight
        (one element of a quantized entry's scale); scale_zero zeroes a
        quantized entry's scale outright."""
        spec = _faults.fire("numerics.corrupt")
        if spec is None or spec.mode not in ("nan", "inf", "scale_zero"):
            return
        self._apply_numerics_corruption(spec.target, spec.mode)

    @staticmethod
    def _corrupt_entry(entry, mode):
        """Damage ONE decode-param entry per the numerics.corrupt mode;
        returns the poisoned entry, or None when the mode does not apply
        (scale_zero needs a quantized {"q","scale"} entry)."""
        if isinstance(entry, dict):                # quantized entry
            new = dict(entry)
            if mode == "scale_zero":
                new["scale"] = jnp.zeros_like(entry["scale"])
            else:
                val = jnp.float32(np.nan if mode == "nan" else np.inf)
                new["scale"] = entry["scale"].at[
                    (0,) * entry["scale"].ndim].set(val)
            return new
        if mode == "scale_zero":
            return None
        val = jnp.float32(np.nan if mode == "nan" else np.inf)
        return entry.at[(0,) * entry.ndim].set(val)

    def _apply_numerics_corruption(self, name, mode):
        """Where the damage lands — the flat decode param dict here; the
        pipeline engine overrides to find the stage holding `name`."""
        entry = self._decode_params.get(name) if name else None
        if entry is None:
            return
        entry = self._corrupt_entry(entry, mode)
        if entry is None:
            return
        # dict copy: decode sees the poisoned set, `_params` (prefill,
        # hot-swap masters) stays clean
        self._decode_params = dict(self._decode_params, **{name: entry})

    # -- decode: ONE executable --------------------------------------------
    def _decode_fn(self, params, gk, gv, pos, tokens, key, *extra):
        self._bump_decode_trace()            # trace-time only
        adapters, rng = self._split_extra(extra)
        with self._numerics_scope() as sink:
            logits, nk, nv = self._run_dense(params, gk, gv, pos,
                                             tokens[:, None],
                                             adapters=adapters)
            nxt = self._select_slots(logits[:, 0, :], key, *rng)
            _numerics.tap("decode.logits", logits[:, 0, :])
            if adapters is not None:
                _numerics.tap_tree("adapter.delta", adapters["layers"])
        # free slots keep decoding garbage harmlessly; clamp so their
        # position (and the wpe lookup) stays in-bounds forever
        new_pos = jnp.minimum(pos + 1, self.config.max_len - 1)
        if sink is None:
            return nxt, nk, nv, new_pos
        return nxt, nk, nv, new_pos, sink

    # -- prefill: one executable per bucket ---------------------------------
    def _make_prefill(self, bucket):
        def prefill_fn(params, gk, gv, pos, slot, ids, length, key):
            self.trace_counts["prefill"][bucket] = \
                self.trace_counts["prefill"].get(bucket, 0) + 1
            # run the prompt through a fresh local single-slot cache sized
            # to the bucket, then splice the rows into the global buffers
            local_pos = jnp.zeros((1,), jnp.int32)
            cfg = self._model.cfg
            fresh = [kvc.alloc_kv(1, bucket, cfg.num_heads,
                                  cfg.hidden_size // cfg.num_heads, k.dtype)
                     for k in gk]
            lk = [f.k for f in fresh]
            lv = [f.v for f in fresh]
            with self._numerics_scope() as sink:
                logits, nk, nv = self._run_dense(params, lk, lv, local_pos,
                                                 ids[None, :])
                slot = slot.astype(jnp.int32)
                gk = [jax.lax.dynamic_update_slice(g, n, (slot, 0, 0, 0))
                      for g, n in zip(gk, nk)]
                gv = [jax.lax.dynamic_update_slice(g, n, (slot, 0, 0, 0))
                      for g, n in zip(gv, nv)]
                pos = jax.lax.dynamic_update_slice(
                    pos, length[None].astype(pos.dtype), (slot,))
                last = jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                    keepdims=False)
                first_token = self._select(last[None, :], key)[0]
                _numerics.tap("prefill.logits", last[None, :])
            if sink is None:
                return first_token, gk, gv, pos
            return first_token, gk, gv, pos, sink
        return self._cached(prefill_fn, f"prefill[{bucket}]")

    def bucket_for(self, length):
        for b in self.config.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt length {length} exceeds the largest prefill bucket "
            f"{self.config.prefill_buckets[-1]} (max_len="
            f"{self.config.max_len})")

    def _next_key(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # -- per-slot sampler RNG (ISSUE 13) -------------------------------------
    @property
    def _sampling(self):
        return self.config.decode_strategy == "sampling"

    def _default_slot_seed(self):
        """Deterministic per-placement default when the caller carries no
        RNG state (single-engine serving, bundles without the v3 field):
        derived from the engine seed and a per-engine nonce, so replays
        of one engine are reproducible but two engines never correlate
        — and a failover without explicit state stays greedy-only."""
        self._rng_nonce += 1
        return np.uint32((self.config.seed * 2654435761
                          + self._rng_nonce * 40503) & 0x7FFFFFFF)

    def set_slot_rng(self, slot, seed, gen):
        """Arm slot's sampler state: its next token is generation index
        `gen` of the request seeded `seed`."""
        self._slot_seeds[int(slot)] = np.uint32(seed)
        self._slot_gen[int(slot)] = np.int32(gen)

    def slot_rng(self, slot):
        """(seed, gen) with gen = the generation index of the slot's
        NEXT token — exactly what a KV-handoff bundle must carry for the
        adopting host to continue a sampled stream bit-identically."""
        return (int(self._slot_seeds[int(slot)]),
                int(self._slot_gen[int(slot)]))

    def _slot_key(self, slot):
        """Host-side key for the slot's next token — the same
        fold_in(key(seed), gen) expression the decode executable
        computes in-trace, so prefill (restart) and decode (original)
        sample generation index n identically."""
        slot = int(slot)
        return jax.random.fold_in(
            jax.random.key(jnp.uint32(self._slot_seeds[slot])),
            int(self._slot_gen[slot]))

    def _rng_args(self):
        """Extra decode-executable inputs for the sampling strategy:
        per-slot seeds + generation counters (empty for greedy — the
        greedy executables keep their PR 3 signature and caches)."""
        if not self._sampling:
            return ()
        return (jnp.asarray(self._slot_seeds), jnp.asarray(self._slot_gen))

    # -- per-tenant LoRA adapters (ISSUE 17) ---------------------------------
    def attach_adapters(self, bank):
        """Attach a `tenancy.AdapterBank`: from the NEXT decode step the
        executables take the bank's stacked arrays + per-slot adapter
        ids as extra runtime inputs (one new trace per executable —
        adapters change the program once, tenants never do)."""
        if not self._layout.allows("adapters"):
            raise NotImplementedError(
                f"per-tenant adapters hook GPT's projections; "
                f"{type(self._model).__name__} has none of them yet")
        self._adapter_bank = bank
        self._refresh_adapters()

    @property
    def adapter_bank(self):
        """The attached tenancy.AdapterBank, or None — what the
        scheduler probes to bind slots to tenants at placement."""
        return self._adapter_bank

    def _refresh_adapters(self):
        """Re-mirror the bank's host masters to device (after attach and
        after every adapter swap)."""
        self._adapter_tree = self._place_adapter_tree(
            self._adapter_bank.device_tree())

    def _place_adapter_tree(self, tree):
        """Device placement hook for the adapter pytree — the TP engine
        overrides to replicate over its mesh; the PP engine shards each
        stage's layer slice with the stage."""
        return tree

    def set_slot_adapter(self, slot, idx):
        """Bind engine slot `slot` to adapter slot `idx` (0 = base).
        A host int32 write — the next decode gathers the new row."""
        self._slot_adapter[int(slot)] = np.int32(idx)

    def slot_adapter(self, slot):
        return int(self._slot_adapter[int(slot)])

    def swap_adapter(self, tenant, state):
        """Hot-load/replace ONE tenant's adapter between decode steps
        (ISSUE 17 registry piece; same atomic-failure contract as
        `swap_params`): the `serving.adapter_swap` chaos site fires
        first, then the bank validates EVERY tensor before writing a
        single row — any failure leaves the tenant's previous adapter
        (and every other tenant's) serving untouched. Base weights are
        never touched; no executable retraces (array values changed,
        never shapes). Returns the tenant's adapter slot."""
        if self._adapter_bank is None:
            raise ValueError("no adapter bank attached "
                             "(engine.attach_adapters)")
        _faults.fire("serving.adapter_swap")
        idx = self._adapter_bank.load(tenant, state)
        self._refresh_adapters()
        return idx

    def drop_adapter(self, tenant):
        """Zero a tenant's adapter row (its slots fall back to base)."""
        if self._adapter_bank is None:
            return None
        idx = self._adapter_bank.drop(tenant)
        if idx is not None:
            self._refresh_adapters()
        return idx

    def _adapter_args(self):
        """Extra decode-executable inputs for the adapter path: the
        placed bank pytree + per-slot adapter ids (empty with no bank —
        adapter-off executables keep their pre-tenancy signature and
        caches, exactly like the greedy/sampling rng split)."""
        if self._adapter_bank is None:
            return ()
        return (self._adapter_tree, jnp.asarray(self._slot_adapter))

    def _split_extra(self, extra):
        """Split a decode executable's trailing `*extra` args back into
        (model adapter view | None, rng args) — the trace-time mirror of
        `*self._adapter_args(), *self._rng_args()` at the call sites."""
        if self._adapter_bank is None:
            return None, extra
        tree, ids = extra[0], extra[1]
        return {"slot": ids, "layers": tree["layers"]}, extra[2:]

    def _select_slots(self, logits, key, seeds=None, gen=None):
        """Per-slot token selection: greedy (or a legacy shared-key
        call) routes through `_select`; sampling derives each row's key
        from its own (seed, gen) so the pick depends only on the
        request's stream position and its logits row."""
        if seeds is None or not self._sampling:
            return self._select(logits, key)
        c = self.config

        def one(row, s, n):
            k = jax.random.fold_in(jax.random.key(s), n)
            return sampling.select_tokens(
                row[None], key=k, strategy="sampling",
                temperature=c.temperature, top_k=c.top_k,
                top_p=c.top_p)[0]
        return jax.vmap(one)(logits, seeds, gen)

    def _warm_key(self):
        """A key with `_next_key`'s aval for AOT warmup — warmup must not
        consume the engine's RNG stream (token streams stay identical
        with or without a warmup pass)."""
        return jax.random.key(self.config.seed)

    # -- AOT warmup ----------------------------------------------------------
    def executable_names(self):
        """The full serving executable set of this engine — what
        `save_for_generation` records in the `.gencfg` sidecar and
        `precompile()` warms."""
        return ["decode"] + [f"prefill[{b}]"
                             for b in self.config.prefill_buckets]

    def precompile(self):
        """AOT-build every serving executable WITHOUT serving a request
        (lower/compile only — nothing executes, no engine state moves).
        With a persistent cache attached, warm entries deserialize (zero
        traces, trace_counts untouched) and cold ones compile and
        commit, so a later process starts warm. Returns
        {executable: "hit"|"miss"|"off"}."""
        gk = [l.k for l in self._cache.layers]
        gv = [l.v for l in self._cache.layers]
        pos = self._cache.pos
        key = self._warm_key()
        out = {"decode": self._decode.warm(
            self._params, gk, gv, pos,
            jnp.zeros((self.config.slots,), jnp.int32), key,
            *self._adapter_args(), *self._rng_args())}
        for b in self.config.prefill_buckets:
            if b not in self._prefill:
                self._prefill[b] = self._make_prefill(b)
            out[f"prefill[{b}]"] = self._prefill[b].warm(
                self._params, gk, gv, pos, jnp.asarray(0, jnp.int32),
                jnp.zeros((b,), jnp.int32), jnp.asarray(1, jnp.int32), key)
        return out

    # -- public compute API -------------------------------------------------
    def prefill(self, slot, prompt_ids, rng=None, namespace=None):
        """Write `prompt_ids` (1-D ints) into `slot`'s cache rows; returns
        the first generated token (host int). `rng=(seed, gen)` arms the
        slot's per-request sampler state (the first token is generation
        index `gen`); None draws a fresh deterministic seed at gen 0.
        `namespace` is accepted for interface parity with the paged
        engines (the dense cache has no shared blocks to isolate)."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        headroom = self.config.max_len - prompt.size
        if headroom < 1:
            raise ValueError(
                f"prompt length {prompt.size} leaves no decode headroom "
                f"(max_len={self.config.max_len})")
        seed, gen = rng if rng is not None \
            else (self._default_slot_seed(), 0)
        self.set_slot_rng(slot, seed, gen)
        bucket = self.bucket_for(prompt.size)
        padded = np.zeros((bucket,), np.int32)
        padded[:prompt.size] = prompt
        if bucket not in self._prefill:
            self._prefill[bucket] = self._make_prefill(bucket)
        with RecordEvent("serving::prefill", TracerEventType.UserDefined,
                         {"bucket": bucket, "length": int(prompt.size),
                          "slot": int(slot)}):
            out = self._prefill[bucket](
                self._params, [l.k for l in self._cache.layers],
                [l.v for l in self._cache.layers],
                self._cache.pos, jnp.asarray(slot, jnp.int32),
                jnp.asarray(padded), jnp.asarray(prompt.size, jnp.int32),
                self._slot_key(slot))
        if self._numerics_armed:
            first, gk, gv, pos, sink = out
            self._ingest_numerics(sink)
        else:
            first, gk, gv, pos = out
        self._set_cache(gk, gv, pos)
        self._slot_gen[int(slot)] += 1
        first = int(first)
        self.set_slot_token(slot, first)
        return first

    def decode(self):
        """Advance every slot one token; returns np.int32 [slots]."""
        # chaos hook: an injected raise here exercises the scheduler's
        # quarantine/reprobe path without touching the executable
        _faults.fire("serving.decode_step")
        self._fire_numerics_chaos()
        with RecordEvent("serving::decode_step",
                         TracerEventType.UserDefined,
                         {"slots": self.config.slots}):
            with _span("serving::decode.upload"):
                tokens = self._last_tokens
                # decode consumes _decode_params (identity == _params
                # here; the paged engine's weight-quant hook makes them
                # differ) so the hook's contract holds on every engine
                args = (
                    self._decode_params,
                    [l.k for l in self._cache.layers],
                    [l.v for l in self._cache.layers], self._cache.pos,
                    jnp.asarray(tokens), self._next_key(),
                    *self._adapter_args(), *self._rng_args())
            if self._numerics_armed:
                self._last_decode_args = args    # the localizer's replay
            with _span("serving::decode.dispatch"):
                res = self._decode(*args)
            with _span("serving::decode.wait"):
                out = np.asarray(res[0], np.int32)
        if self._numerics_armed:
            nxt, gk, gv, pos, sink = res
            self._ingest_numerics(sink)
        else:
            nxt, gk, gv, pos = res
        self._set_cache(gk, gv, pos)
        self._slot_gen += 1
        self._last_tokens = out.copy()
        return out

    def _set_cache(self, gk, gv, pos):
        self._cache = kvc.DecodeCache(
            tuple(kvc.LayerKV(k, v) for k, v in zip(gk, gv)), pos)

    def set_slot_token(self, slot, token):
        """Feed `token` as slot's next decode input (after prefill, or to
        overwrite a retired slot's lane with a harmless value)."""
        self._last_tokens[int(slot)] = np.int32(token)

    # -- zero-downtime weight hot-swap ---------------------------------------
    def swap_params(self, new_params):
        """Replace the serving weights IN PLACE between steps (ISSUE 10:
        the train->serve online-learning loop). Params are plain inputs
        to every executable, so swapping the dict is the whole operation:
        avals are validated to match exactly, which means NO executable
        retraces or recompiles and no in-flight request is dropped — the
        next decode step simply runs under the new weights. The swap is
        atomic: validation (and the `serving.weight_swap` chaos site)
        happens on a staged copy, and a failure of ANY key leaves the
        old weights serving untouched. Returns the number of swapped
        arrays. The eager Layer object is deliberately NOT updated — the
        engine froze it at construction; training owns it."""
        _faults.fire("serving.weight_swap")
        current = self._params
        missing = sorted(set(current) - set(new_params))
        if missing:
            raise ValueError(f"swap params missing {len(missing)} keys "
                             f"(first: {missing[:3]})")
        staged = {}
        for name, old in current.items():
            arr = new_params[name]
            if isinstance(arr, Tensor):
                arr = arr._data
            # validate on the RAW array: placement belongs to
            # _place_param, so an engine whose master copy is
            # host-resident (pipeline-parallel) never routes the whole
            # float model through the default device in the swap window
            if tuple(arr.shape) != tuple(old.shape):
                raise ValueError(
                    f"swap param {name!r} shape {tuple(arr.shape)} != "
                    f"serving shape {tuple(old.shape)} — a hot-swap can "
                    f"only replace values, never architecture")
            if arr.dtype != old.dtype:
                arr = arr.astype(old.dtype)   # ckpt round-trips may widen
            staged[name] = self._place_param(name, arr)
        # materialize before commit so a device placement error cannot
        # surface lazily from inside a later decode step (host-resident
        # leaves pass through untouched)
        jax.block_until_ready(list(staged.values()))
        self._params = staged                  # the commit point
        self._after_param_swap()
        return len(staged)

    def _place_param(self, name, arr):
        """Device placement hook for swapped-in params — the TP engine
        overrides to re-apply each param's mesh sharding; the PP engine
        keeps the master copy on HOST (stage placement happens in
        `_after_param_swap`, never through one device)."""
        return jnp.asarray(arr)

    def reset_slot(self, slot):
        """Mark a slot free: pos=0 so stale K/V rows are invisible."""
        pos = np.asarray(self._cache.pos, np.int32).copy()
        pos[int(slot)] = 0
        self._cache = kvc.DecodeCache(self._cache.layers,
                                      jnp.asarray(pos))
        self.set_slot_token(slot, 0)
        self.set_slot_rng(slot, 0, 0)
        self._slot_adapter[int(slot)] = 0

    def slot_positions(self):
        return np.asarray(self._cache.pos, np.int32)

    @property
    def slots(self):
        return self.config.slots

    @property
    def max_prompt_len(self):
        """Longest prompt prefill can serve AND still decode one token."""
        return min(self.config.prefill_buckets[-1], self.config.max_len - 1)

    @property
    def decode_write_tokens(self):
        """KV positions one decode step writes per slot — 1 for the
        one-token loop; the speculative engine overrides with its
        γ+1-token verify window so slot growth provisions the whole
        write."""
        return 1

    @property
    def kv_memory_tokens(self):
        """Token capacity of the KV memory this engine reserves — the
        budget figure the load harness equalizes across layouts."""
        return self.config.slots * self.config.max_len

    # -- per-device HBM accounting (ISSUE 13) --------------------------------
    def _weight_sources(self):
        """The param dicts whose arrays count as resident weight state
        — the engines override to add/replace sources (the speculative
        draft set, the pipeline stages' placed shards)."""
        return [self._params, getattr(self, "_decode_params", None) or {}]

    def _weight_arrays(self):
        """Every RESIDENT weight array this engine keeps on device —
        including both the float set (prefill always serves it) AND the
        int8 decode set when weight_dtype="int8". That double residency
        is the honest accounting the equal-HBM bench arms must use:
        int8 decode weights do NOT shrink the per-device weight bill to
        a quarter — the float shards stay for prefill, so the bill is
        float_shard + int8_shard (~1.25x the float shard). Identity-
        shared arrays (spec's truncated draft, decode==params) count
        once; quant entries contribute codes AND scales."""
        seen, out = set(), []
        for src in self._weight_sources():
            for v in src.values():
                for arr in ((v["q"], v["scale"]) if isinstance(v, dict)
                            else (v,)):
                    if isinstance(arr, np.ndarray):
                        continue      # host-resident master copies
                    if id(arr) not in seen:
                        seen.add(id(arr))
                        out.append(arr)
        return out

    def _kv_arrays(self):
        """Every resident KV-memory array (dense cache buffers here;
        the paged engines override with their pools + scales)."""
        return [x for l in self._cache.layers for x in (l.k, l.v)]

    def hbm_accounting(self):
        """Measured per-device byte footprint of the resident serving
        state, from the arrays' actual shards (`addressable_shards`) —
        never from dtype-width arithmetic. Returns {"per_device":
        {device: {"weights", "kv", "total"}}, "max_device_total",
        "weights_total", "kv_total"} — `max_device_total` is the
        per-host HBM figure the equal-HBM bench comparisons equalize
        (and what "a model bigger than one host" is measured against).

        Scope caveat: the figure covers ENGINE-owned state. The eager
        source Layer's own parameter arrays (materialized at model
        build, typically on the default device, and kept alive by the
        Layer for hot-swap/training callers) are NOT counted — on a
        real bigger-than-one-host pp deployment the worker must build
        the model host-side or free the eager device copies, which is
        the open ROADMAP item 4 deployment note."""
        per = {}

        def add(arr, kind):
            for s in arr.addressable_shards:
                d = per.setdefault(str(s.device),
                                   {"weights": 0, "kv": 0})
                d[kind] += int(s.data.nbytes)
        for arr in self._weight_arrays():
            add(jnp.asarray(arr), "weights")
        for arr in self._kv_arrays():
            add(jnp.asarray(arr), "kv")
        for d in per.values():
            d["total"] = d["weights"] + d["kv"]
        return {
            "per_device": per,
            "max_device_total": max((d["total"] for d in per.values()),
                                    default=0),
            "weights_total": sum(d["weights"] for d in per.values()),
            "kv_total": sum(d["kv"] for d in per.values())}


class PagedEngineConfig(EngineConfig):
    """EngineConfig plus the paged-pool knobs.

    block_size: tokens per KV block (the paging granularity; prefix
    sharing is full-block-granular, so smaller blocks share more but
    gather more). num_blocks: total pool size INCLUDING the reserved
    garbage block — `num_blocks * block_size` is the MEMORY the pool
    reserves (what `kv_memory_tokens` reports and the load harness
    equalizes against a dense engine's `slots * max_len`), while
    `(num_blocks - 1) * block_size` is the ALLOCATABLE capacity (block 0
    is never handed out). Budget comparisons at equal reserved memory
    are therefore conservative for paged by one block. Defaults to full
    provisioning plus the garbage block (every slot could hold max_len);
    the interesting deployments undersubscribe it and let the scheduler
    preempt."""

    def __init__(self, block_size=16, num_blocks=None,
                 enable_prefix_cache=True, attention_impl=None,
                 kv_dtype="float32", weight_dtype="float32",
                 capture_logits=False, enable_kv_tiers=False,
                 host_tier_blocks=64, host_tier_dtype="float32",
                 disk_tier_dir=None, disk_tier_blocks=256,
                 disk_tier_compact_threshold=0.5, **kwargs):
        super().__init__(**kwargs)
        self.block_size = int(block_size)
        self.max_blocks_per_slot = -(-self.max_len // self.block_size)
        self.num_blocks = int(num_blocks) if num_blocks is not None else \
            1 + self.slots * self.max_blocks_per_slot
        if self.num_blocks < 2:
            raise ValueError("num_blocks must leave at least one "
                             "allocatable block beyond the garbage block")
        self.enable_prefix_cache = bool(enable_prefix_cache)
        # "gather" = dense-view oracle; "kernel" = Pallas in-kernel
        # block-table walk (ops/pallas/paged_attention.py) — validated
        # here so a typo fails at config time, not mid-trace. None (the
        # default) leaves the choice to the engine, which resolves it at
        # construction from the model, the pools and the platform
        # (`PagedGenerationEngine.attention_impl`)
        if attention_impl not in (None, "gather", "kernel"):
            raise ValueError(f"attention_impl must be 'gather' or "
                             f"'kernel' (or None: the engine's choice), "
                             f"got {attention_impl!r}")
        self.attention_impl = attention_impl
        # quantized serving (ISSUE 11): kv_dtype="int8" stores the KV
        # pools as int8 codes + per-block per-head scales (2x the token
        # budget per HBM byte vs bf16, 4x vs these f32 pools);
        # weight_dtype="int8" runs the DECODE matmuls from int8 weights
        # with per-output-channel scales (prefill stays float — it is
        # compute-bound and runs once per request; decode is bandwidth-
        # bound and runs per token). Validated here, like attention_impl.
        # "bfloat16": weights (but the parameters a model pins to float32)
        # are cast once at construction and serve prefill and decode alike;
        # the pools (K/V, latent rows, convolution tails) are bfloat16.
        # "float32" keeps whatever type the model's parameters have.
        for knob, val in (("kv_dtype", kv_dtype),
                          ("weight_dtype", weight_dtype)):
            if val not in ("float32", "bfloat16", "int8"):
                raise ValueError(f"{knob} must be 'float32', 'bfloat16' or "
                                 f"'int8', got {val!r}")
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        # capture_logits=True makes the decode executable additionally
        # return the [slots, vocab] last-token logits (engine.last_logits)
        # — the quant-quality harness's logit-KL tap. A different traced
        # program, still compiled exactly once.
        self.capture_logits = bool(capture_logits)
        # KV memory hierarchy (ISSUE 18, serving.kv_tiers): evicted
        # prefix-cache leaves demote to a pinned host pool (optionally
        # int8-requantized) and cascade to an append-log disk tier
        # instead of being freed; a match against a demoted chain
        # promotes the blocks back. Default OFF: disabled tiering is
        # bit-identical to the pre-tier engine, asserted in tests.
        self.enable_kv_tiers = bool(enable_kv_tiers)
        self.host_tier_blocks = int(host_tier_blocks)
        if host_tier_dtype not in ("float32", "int8"):
            raise ValueError(f"host_tier_dtype must be 'float32' or "
                             f"'int8', got {host_tier_dtype!r}")
        self.host_tier_dtype = host_tier_dtype
        self.disk_tier_dir = disk_tier_dir
        self.disk_tier_blocks = int(disk_tier_blocks)
        self.disk_tier_compact_threshold = float(disk_tier_compact_threshold)

    _DICT_FIELDS = EngineConfig._DICT_FIELDS + (
        "block_size", "num_blocks", "enable_prefix_cache", "attention_impl",
        "kv_dtype", "weight_dtype", "capture_logits", "enable_kv_tiers",
        "host_tier_blocks", "host_tier_dtype", "disk_tier_dir",
        "disk_tier_blocks", "disk_tier_compact_threshold")


class PagedGenerationEngine(GenerationEngine):
    """GenerationEngine over the paged block pool (serving/blocks.py).

    Same public contract as the dense engine — prefill/decode/reset_slot,
    compile-once trace counters — plus block accounting: `block_pool`
    (refcounted allocator), `prefix_cache` (shared system-prompt blocks),
    and `ensure_slot_capacity` for the scheduler's preemption loop. The
    decode executable's avals (pools, tables, pos, tokens) never change,
    so it still compiles exactly once; prefill compiles per SUFFIX
    bucket — a prefix-cache hit shortens the suffix, it never adds an
    executable.

    The pool is donated to every executable that takes it and returns
    the new one (decode, prefill[bucket], adopt[bucket], the tier
    restore): the scatter of the step's K/V lands in the buffers that
    came in, not in a copy of all of them. So `self._pool` is rebound
    to the result right after each dispatch, and nothing may keep a
    pool tuple across a call: the one that went in is deleted. Each
    step's span says whether it engaged (`pool_donated`,
    docs/serving.md).

    A call is ONE upload, one enqueue and one fetch. Everything the
    host owns that an executable reads (decode: tables, positions, last
    tokens, with adapters the per-slot ids, under sampling the per-slot
    seeds and generation counters; prefill: the slot's table row, the
    slot, the suffix's length and start, the padded suffix, under
    sampling the slot's seed and generation index) goes up as one int32
    array (`_CallPack`, `_put`) and is sliced apart in the trace;
    weights, pool and adapter tree are resident and stay arguments of
    their own. Only the tokens come back (`_fetch`), the model's
    counters behind them where it counts: positions are the host's to
    advance. No eager device program runs on the path: a greedy
    executable takes no key, a sampling one derives `fold_in(key(seed),
    gen)` in the trace.

    A decode call's tokens also stay on the device: the next call takes
    the previous call's output beside its upload and reads a slot's
    token from it unless the host fed that slot since (`fresh`: a
    prefill, an adoption, a reset). So under `ahead()` `decode()`
    enqueues call n and then fetches call n-1 (ISSUE 39); positions and
    generation counters advance at the dispatch, and go back to the
    last fetched call if a fetch fails."""

    def __init__(self, model, config=None, **kwargs):
        config = config or PagedEngineConfig(**kwargs)
        self.last_counters = {}
        self.state_store = None
        self._packs = {}     # executable -> the layout of its one upload
        # puts and fetches issued through `_put` / `_fetch`, the call
        # path's only two: its upload and wait spans note how many fell
        # inside them
        self._transfers = 0
        self._fetches = 0
        # a slot's next decode input is the previous call's output, on the
        # device, unless the host fed it since (`_fresh`, kept by
        # `set_slot_token`): so a call can be enqueued before the one
        # before it is fetched (`ahead()`)
        self._fresh = np.ones((config.slots,), np.int32)
        self._calls = 0             # decode calls dispatched
        self._inflight = None       # the call `ahead()` left unfetched
        self._ahead = False
        super().__init__(model, config)
        # the counters the model's forward returns behind the logits
        self._counter_names = tuple(self._model.serving_counters)
        # what the first call reads as the output of the call before it:
        # tokens, then the model's counters, as every decode call returns
        self._prev_out = jax.device_put(
            np.zeros((config.slots + len(self._counter_names),), np.int32),
            self._upload_sharding)
        # KV-adopt executables (multi-host handoff sink, ISSUE 10): one
        # per prefill bucket, compiled on first use and counted like
        # every other executable
        self.trace_counts["adopt"] = {}
        self._adopt = {}

    def _configure(self, config):
        """A slot's table is as wide as the layout's blocks for max_len (a
        window layout's rows live one window and its summaries live on, so
        it sizes a slot itself), and full provisioning is that many blocks
        a slot. The attention the executables are traced with is the
        configured one, or the engine's own choice where the configuration
        leaves it open: this, not the spelled value, is what the
        executables' cache keys and the spans' `attend` carry."""
        per_slot = self._layout.table_blocks(config.max_len,
                                             config.block_size)
        if config.num_blocks == 1 + config.slots * config.max_blocks_per_slot:
            config.num_blocks = 1 + config.slots * per_slot
        config.max_blocks_per_slot = per_slot
        self.attention_impl = config.attention_impl \
            or self._default_attention_impl(config)
        return config

    def _default_attention_impl(self, config):
        """The paged attention an engine built without `attention_impl`
        traces: on a TPU, for a model that caches K and V in float pools,
        "decode_kernel" (`blocks.attention_impl`: one query a slot walks
        the block table in the Pallas decode kernel, prefill and verify
        windows gather); "gather" everywhere else: off the TPU the kernel
        would be interpreted, a layout that does not allow the kernel does
        not attend through `blocks.attend`, int8 pools have no decode
        kernel, and pools sharded over a mesh (`tp` > 1) meet a kernel that
        is one device's program."""
        if not self._layout.allows("kernel") or config.kv_dtype == "int8" \
                or getattr(config, "tp", 1) > 1 \
                or jax.default_backend() != "tpu":
            return "gather"
        return "decode_kernel"

    def _compile_signature(self):
        sig = super()._compile_signature()
        sig["config"]["attention_impl"] = self.attention_impl
        return sig

    @property
    def _decode_donate(self):
        """Decode takes its pool (argument 1 of `_decode_fn`) in place,
        unless numerics taps are armed: the localizer replays
        `_last_decode_args`, which must then outlive the call."""
        return () if self._numerics_armed else (1,)

    @staticmethod
    def _pool_donated(pool):
        """0/1 for a span's `pool_donated`, read right after a dispatch
        that was handed `pool`: 1 when the executable consumed it. 0 on
        an engine that should donate means an executable came back from
        some cache tier without its input aliasing, and copies the whole
        pool every call."""
        return int(jax.tree_util.tree_leaves(pool)[0].is_deleted())

    def _constrain_pools(self, pool):
        """Trace-time sharding hook on every new-pool output (decode,
        prefill, adopt): takes and returns the whole pool tuple (one
        (Quant)PagedLayerKV per layer). Identity here; the tensor-
        parallel engine pins the heads-sharded layout so executable
        input/output shardings stay fixed and the compile-once invariant
        survives the mesh."""
        return pool

    @property
    def kv_quantized(self):
        return self.config.kv_dtype == "int8"

    def _pool_dtype(self):
        """The pools' type: "bfloat16" and "int8" as spelled; "float32"
        reads as the weights are (the first parameter's type)."""
        if self.config.kv_dtype in ("bfloat16", "int8"):
            return jnp.dtype(self.config.kv_dtype)
        return jnp.dtype(next(iter(self._params.values())).dtype)

    def _alloc_state(self):
        c = self.config
        self._check_layout_config()
        if c.weight_dtype == "bfloat16":
            keep = self._model.float32_parameters()
            self._params = {
                n: a if n in keep or a.dtype != jnp.float32
                else a.astype(jnp.bfloat16)
                for n, a in self._params.items()}
        self._pool = blocks.alloc_layers(
            self._layout, c.num_blocks, c.block_size, c.slots,
            self._pool_dtype())
        _, slot_bytes = blocks.layout_bytes(self._layout, c.block_size,
                                            self._pool_dtype())
        if slot_bytes:
            self.state_store = blocks.SlotStateStore(c.slots, slot_bytes)
        self._alloc_host_state()

    def _check_layout_config(self):
        """What the model's cache layout does not allow raises here, at
        construction, and not in the middle of a request (serving/blocks.py
        says what each kind of layer allows)."""
        c = self.config
        asked = (("kv_dtype", c.kv_dtype, "int8", c.kv_dtype == "int8"),
                 ("weight_dtype", c.weight_dtype, "int8",
                  c.weight_dtype == "int8"),
                 ("attention_impl", self.attention_impl, "kernel",
                  self.attention_impl != "gather"),
                 ("enable_kv_tiers", c.enable_kv_tiers, "block_moves",
                  c.enable_kv_tiers),
                 ("numerics_taps", c.numerics_taps, "numerics_taps",
                  c.numerics_taps))
        bad = [f"{k}={v!r}" for k, v, what, on in asked
               if on and not self._layout.allows(what)]
        if bad:
            raise ValueError(
                f"the cache layout of {type(self._model).__name__} "
                f"({self._layout.kinds()}) cannot be served with "
                f"{', '.join(bad)}")

    def _require_kv_layout(self, what):
        if not self._layout.allows("block_moves"):
            raise NotImplementedError(
                f"{what} moves K/V blocks; the cache layout of "
                f"{type(self._model).__name__} ({self._layout.kinds()}) "
                f"keeps its blocks in the pool")

    def _alloc_host_state(self):
        """The mesh-oblivious host half of the paged state: per-slot
        positions/tables/activity, the block allocator, and the prefix
        cache. Factored out so the pipeline-parallel engine (which owns
        per-STAGE device pools) reuses it verbatim — block tables and
        the allocator are shared across stages by construction."""
        c = self.config
        # pos lives host-side (np) and only there: the block math
        # (ensure_slot_capacity, once per slot per decode step) reads it,
        # every call sends it up inside its one upload, and the host
        # advances it itself once the call's tokens are back
        self._pos = np.zeros((c.slots,), np.int32)
        self._tables = np.zeros((c.slots, c.max_blocks_per_slot), np.int32)
        self._slot_active = np.zeros((c.slots,), bool)
        # per-slot prefix namespace (ISSUE 17): remembered from prefill
        # so mid-decode block growth evicts under the same requester
        self._slot_namespace = {}
        self.block_pool = blocks.BlockPool(c.num_blocks, c.block_size)
        # block-identity prefix reuse is wrong for a layout that does not
        # allow it: its prefill runs a request's tokens from position 0 (it
        # attends over its own tokens only, and a per-slot state at the end
        # of a shared prefix is in no block). Its cache reports no hit and
        # counts the lookups
        self.prefix_cache = PrefixCache(
            self.block_pool, c.block_size,
            bypass=not self._layout.allows("prefix_sharing")) \
            if c.enable_prefix_cache else None
        # KV attribution ledger (observability.kvledger): because every
        # engine kind — paged, spec, tp, pp, spec_pp — funnels through
        # this host half, attaching here covers all of their pool
        # slices (the pp engine's per-stage pools share this ONE
        # allocator via the `_pool` property's whole-model view).
        # Construction-time opt-out is the zero-cost contract: disabled,
        # the pool/cache pay one `is None` check per operation.
        self.kv_block_bytes = self._kv_block_bytes()
        self.kv_ledger = None
        if _kvl.enabled():
            self.kv_ledger = _kvl.KVLedger(
                c.num_blocks, block_bytes=self.kv_block_bytes)
            self.block_pool.attach_ledger(self.kv_ledger)
            if self.prefix_cache is not None:
                self.prefix_cache.attach_ledger(self.kv_ledger)
        # KV tier store (ISSUE 18): plugged UNDER the prefix cache so
        # eviction demotes and match promotes. The store's device I/O is
        # the two eager callbacks below — host + transfer work only, so
        # the compile-once decode contract survives tiering untouched.
        self.kv_tiers = None
        if getattr(c, "enable_kv_tiers", False) \
                and self.prefix_cache is not None:
            from .kv_tiers import TieredBlockStore
            self.kv_tiers = TieredBlockStore(
                self._tier_read_block, self._tier_write_block,
                write_blocks=self._tier_write_blocks,
                host_blocks=c.host_tier_blocks,
                host_dtype=c.host_tier_dtype,
                disk_dir=c.disk_tier_dir,
                disk_blocks=c.disk_tier_blocks,
                disk_compact_threshold=c.disk_tier_compact_threshold)
            if self.kv_ledger is not None:
                self.kv_tiers.attach_ledger(self.kv_ledger)
            self.prefix_cache.attach_tier(self.kv_tiers)
            # the ONE compiled restore scatter (fixed lane count —
            # GARBAGE_BLOCK pads short runs); audited next to decode,
            # and like decode it takes the pool in place
            self._tier_writer = jax.jit(self._tier_writer_fn,
                                        donate_argnums=(0,))
            self.trace_counts["tier_restore"] = 0
        self.last_prefill_stats = {}
        self.last_logits = None

    def _kv_block_bytes(self):
        """HBM bytes one pool block pins across every layer, priced from
        the pool dtype (`blocks.layout_bytes`: int8 K/V blocks carry a
        4-byte-per-head scale row next to the codes) — what turns the
        ledger's per-tenant block counts into
        `serving_kv_bytes{tenant,kind}`."""
        return blocks.layout_bytes(self._layout, self.config.block_size,
                                   self._pool_dtype())[0]

    # -- KV tier device I/O (ISSUE 18) --------------------------------------
    # a K/V layer's arrays in a tier record, field by field: `k{layer}`,
    # `v{layer}`, and in int8 pools the scale rows `ks{layer}`, `vs{layer}`
    _TIER_NAMES = ("k", "v", "ks", "vs")

    def _tier_names(self, li, layer):
        return [f"{n}{li}" for n in self._TIER_NAMES[:len(layer)]]

    def _tier_read_block(self, blk):
        """TieredBlockStore's read callback: one physical block's
        whole-model KV as pool-NATIVE host numpy arrays — f32 slabs, or
        int8 codes + their scale rows for quantized pools (lossless
        either way). Eager gathers only; never a traced program."""
        blk = int(blk)
        arrays = {name: np.asarray(jax.device_get(x[blk]))
                  for li, layer in enumerate(self._pool)
                  for name, x in zip(self._tier_names(li, layer), layer)}
        return {"arrays": arrays, "quant": self.kv_quantized}

    def _tier_write_block(self, blk, arrays):
        """TieredBlockStore's write callback: scatter one block's
        pool-native arrays back into the live pool. All host->device
        transfers are issued FIRST (`jax.device_put` — the async
        prefetch that overlaps the caller's suffix prefill), then the
        per-layer eager `.at[blk].set` updates commit the pool. Eager
        ops only: tier promotion can never add a traced program, which
        is what keeps the decode compile count at exactly one."""
        blk = int(blk)
        dev = {n: jax.device_put(np.asarray(a))
               for n, a in arrays.items()}
        self._pool = tuple(
            type(layer)(*(x.at[blk].set(dev[name].astype(x.dtype))
                          for name, x in zip(self._tier_names(li, layer),
                                             layer)))
            for li, layer in enumerate(self._pool))

    def _tier_writer_fn(self, pool, idx, payload):
        """The batched tier-restore program: one fixed-shape scatter of
        a whole promoted chain run into every pool array. `idx` is
        padded to `max_blocks_per_slot` lanes with GARBAGE_BLOCK —
        writes there are discarded by contract (the same scratch row
        masked decode writes land in), so one compiled shape serves
        every run length and the program compiles exactly ONCE per
        engine (`trace_counts["tier_restore"]`)."""
        self.trace_counts["tier_restore"] = \
            self.trace_counts.get("tier_restore", 0) + 1  # trace-time only
        return tuple(type(layer)(*(x.at[idx].set(p.astype(x.dtype))
                                   for x, p in zip(layer, pl)))
                     for layer, pl in zip(pool, payload))

    def _tier_write_blocks(self, blks, arrays_list):
        """Batched tier restore for a whole chain run: pad the run to
        the fixed `max_blocks_per_slot` lane count (GARBAGE_BLOCK lanes
        absorb the padding) and commit it through ONE compiled scatter
        call — a cold chain of m blocks costs one dispatch, not
        O(m * layers) eager ops, which is what lets a host-tier restore
        beat recomputing the prefix even on CPU-dispatch-bound hosts.
        Runs longer than the lane count chunk."""
        lanes = max(int(self.config.max_blocks_per_slot), 1)
        for lo in range(0, len(blks), lanes):
            run = blks[lo:lo + lanes]
            arrs = arrays_list[lo:lo + lanes]
            m = len(run)
            idx = np.full((lanes,), blocks.GARBAGE_BLOCK, np.int32)
            idx[:m] = [int(b) for b in run]
            payload = []
            for li, layer in enumerate(self._pool):
                lanes_pl = []
                for n in self._tier_names(li, layer):
                    first = np.asarray(arrs[0][n])
                    pad = np.zeros((lanes,) + first.shape, first.dtype)
                    pad[:m] = [np.asarray(a[n]) for a in arrs]
                    lanes_pl.append(pad)
                payload.append(tuple(lanes_pl))
            self._pool = self._tier_writer(self._pool, idx,
                                           tuple(payload))

    # -- int8 decode weights (ISSUE 11) --------------------------------------
    def _weight_quant_axis(self, name, arr):
        """Per-channel quantization axis for a decode-matmul weight, or
        None to keep the param float. Quantized: every 2-D `.weight` —
        the qkv/out_proj/fc1/fc2 Linears (channel axis 1, the output
        column — reference fake_channel_wise_quantize_abs_max for
        Linear) and the tied `wte.weight` head matmul (channel axis 0,
        the vocab row). `wpe.weight` stays float: it is a position
        LOOKUP, not a decode matmul, and its read is one row per slot."""
        if arr.ndim != 2 or not name.endswith(".weight"):
            return None
        if "wpe" in name:
            return None
        return 0 if name.endswith("wte.weight") else 1

    def _build_decode_params(self):
        """weight_dtype="int8": re-express every decode-matmul weight as
        int8 codes + per-output-channel scales (`channel_abs_max`, the
        dormant PTQ subsystem's scale rule) for the decode/verify
        executables, which dequantize at trace time — XLA fuses the
        convert+scale into the matmul operand read, so the HBM bill of
        the bandwidth-bound decode step is the int8 bytes. The float
        params (`self._params`) are untouched: prefill keeps serving
        them. Scales ship broadcast-ready (reshaped to the weight's
        rank) so the pytree stays {name: array | {"q","scale"}} with no
        static metadata riding the executable arguments."""
        if self.config.weight_dtype != "int8":
            self._decode_params = self._params
            return
        self._decode_params = self._quantize_params(self._params)

    def _quantize_params(self, params):
        """int8-quantize every decode-matmul weight of a param dict
        (per-channel abs-max scales); non-matmul params pass through.
        Quantization runs ON DEVICE under jit (`_quantize_weight`) so a
        weight hot-swap re-quantizes without a host round-trip inside
        the between-steps swap window."""
        out = {}
        for name, arr in params.items():
            axis = self._weight_quant_axis(name, arr)
            if axis is None:
                out[name] = arr
                continue
            codes, s_b = _quantize_weight(arr, axis)
            out[name] = self._place_quant_weight(name, codes, s_b, axis)
        return out

    def _place_quant_weight(self, name, codes, scale_b, axis):
        """Device placement of one quantized decode weight — the TP
        engine re-applies the float param's mesh sharding (per-shard
        scales follow the split when the channel axis IS the sharded
        axis)."""
        return {"q": codes, "scale": scale_b}

    @staticmethod
    def _dequant_params(params):
        """Materialize a decode param dict inside the trace: quantized
        entries dequantize through the one canonical expression
        (`blocks.dequant_codes`), float entries pass through."""
        return {n: (blocks.dequant_codes(v["q"], v["scale"])
                    if isinstance(v, dict) else v)
                for n, v in params.items()}

    # -- block accounting ----------------------------------------------------
    def _blocks_for(self, n_tokens):
        """Blocks a slot of `n_tokens` tokens holds, as the layout says."""
        return self._layout.blocks_for(n_tokens, self.config.block_size)

    def _table_entries(self, first, last):
        """The table entries that tokens at positions first..last write,
        none beyond the table."""
        c = self.config
        return self._layout.entries(first, last, c.block_size,
                                    c.max_blocks_per_slot)

    def _alloc_blocks(self, n, requester=None):
        """Pool alloc with prefix-cache eviction as the pressure valve:
        only when eviction cannot cover the shortfall does
        BlockAllocError escape to the scheduler (whose next lever is
        preemption). `requester` is the allocating request's prefix
        namespace — quota-aware eviction drains the requester's OWN
        leaves first and never touches a within-quota foreign
        namespace's blocks (ISSUE 17)."""
        try:
            return self.block_pool.alloc(n)
        except blocks.BlockAllocError:
            if self.prefix_cache is not None:
                short = n - self.block_pool.available
                if self.prefix_cache.evict(short,
                                           requester=requester) >= short:
                    return self.block_pool.alloc(n)
            raise

    def ensure_slot_capacity(self, slot, tokens=None):
        """Make sure `slot` can absorb its next decode write (`tokens`
        K/V entries landing at positions pos[slot]..pos+tokens-1;
        defaults to the engine's per-step write width). Allocation is
        all-or-nothing across the needed blocks; raises BlockAllocError
        under pressure — the scheduler preempts and retries. Positions
        past max_len need no block (the write scatters them into the
        garbage block)."""
        slot = int(slot)
        if not self._slot_active[slot]:
            return
        if tokens is None:
            tokens = self.decode_write_tokens
        first = int(self._pos[slot])
        need = [lb for lb in self._table_entries(first,
                                                 first + int(tokens) - 1)
                if self._tables[slot, lb] == blocks.GARBAGE_BLOCK]
        if need:
            requester = self._slot_namespace.get(slot)
            for lb, b in zip(need,
                             self._alloc_blocks(len(need),
                                                requester=requester)):
                self._tables[slot, lb] = b

    def ensure_decode_capacity(self):
        for s in range(self.config.slots):
            self.ensure_slot_capacity(s)

    @property
    def kv_memory_tokens(self):
        """Reserved pool memory in tokens (garbage block included — this
        is the footprint figure comparable to dense `slots * max_len`)."""
        return self.config.num_blocks * self.config.block_size

    @property
    def kv_usable_tokens(self):
        """Allocatable capacity: the reserve minus the garbage block."""
        return (self.config.num_blocks - 1) * self.config.block_size

    def _kv_arrays(self):
        return [x for layer in self._pool for x in layer]

    # -- AOT warmup ----------------------------------------------------------
    def precompile(self):
        """Paged-engine warmup. The attention-impl trace context must
        wrap the warms exactly as it wraps the live calls — a kernel-
        config engine warmed outside the context would compile (and
        commit under the kernel key) the gather program."""
        out = {}
        with blocks.attention_impl(self.attention_impl):
            out["decode"] = self._decode.warm(*self._decode_args())
            for b in self.config.prefill_buckets:
                if b not in self._prefill:
                    self._prefill[b] = self._make_prefill(b)
                out[f"prefill[{b}]"] = self._prefill[b].warm(
                    *self._prefill_args(b, 0, np.zeros((b,), np.int32),
                                        1, 0))
        return out

    # -- the call's one upload and one fetch ----------------------------------
    # where `_put` places a call's upload: the default device here; the
    # tensor-parallel engine replicates it over its mesh
    _upload_sharding = None

    def _put(self, buf):
        """THE host-to-device transfer of a call (counted, so the upload
        span's `transfers` is what was issued, not what was meant)."""
        self._transfers += 1
        return jax.device_put(buf, self._upload_sharding)

    def _fetch(self, arr):
        """THE device-to-host fetch of a call: blocks until the
        executable is done; counted like `_put`."""
        self._fetches += 1
        return np.asarray(arr, np.int32)

    def _pack(self, bucket=None):
        """The layout of decode's upload (`bucket` None) or of the
        bucket's prefill: what the engine can see decides the fields
        (slots, blocks a slot, the bucket, sampling or greedy, adapters
        attached or not)."""
        key = (bucket, self._adapter_bank is not None)
        pack = self._packs.get(key)
        if pack is not None:
            return pack
        c = self.config
        per_slot = (c.slots,)
        if bucket is None:
            fields = [("tables", (c.slots, c.max_blocks_per_slot)),
                      ("pos", per_slot), ("tokens", per_slot),
                      ("fresh", per_slot)]
            if self._adapter_bank is not None:
                fields.append(("adapter", per_slot))
            if self._sampling:
                fields += [("seeds", per_slot), ("gen", per_slot)]
        else:
            fields = [("row", (1, c.max_blocks_per_slot)), ("slot", ()),
                      ("length", ()), ("start", ())]
            if self._sampling:
                fields += [("seed", ()), ("gen", ())]
            fields.append(("ids", (bucket,)))
        pack = self._packs[key] = _CallPack(fields)
        return pack

    def _decode_args(self):
        """The decode executable's arguments, the host's part of them put
        on the device in one transfer; beside it the previous call's
        output, already there, whose tokens feed every slot the host has
        not fed since."""
        host = {"tables": self._tables, "pos": self._pos,
                "tokens": self._last_tokens, "fresh": self._fresh}
        if self._adapter_bank is not None:
            host["adapter"] = self._slot_adapter
        if self._sampling:
            host["seeds"], host["gen"] = self._slot_seeds, self._slot_gen
        args = (self._decode_params, self._pool,
                self._put(self._pack().pack(**host)), self._prev_out)
        if self._adapter_bank is not None:
            args += (self._adapter_tree,)
        return args

    def _prefill_args(self, bucket, slot, padded, length, start):
        """The bucket executable's arguments for `slot`: its table row
        drives both the scatter of the new suffix K/V and the gather over
        the (possibly shared) prefix blocks; `start` = tokens already
        resident (prefix hit)."""
        host = {"row": self._tables[slot], "slot": slot, "length": length,
                "start": start, "ids": padded}
        if self._sampling:
            host["seed"] = self._slot_seeds[slot]
            host["gen"] = self._slot_gen[slot]
        return (self._params, self._pool,
                self._put(self._pack(bucket).pack(**host)))

    def _unpack_rng(self, seeds, gen):
        """The packed sampler state as `_select_slots` takes it: the
        seeds' bits read as the `uint32` they are."""
        return jax.lax.bitcast_convert_type(seeds, jnp.uint32), gen

    # -- functional forward (paged) -----------------------------------------
    def _run_model(self, params, pool, tables, pos, ids, valid=None,
                   slot=None, adapters=None):
        """THE cached forward of the served model over the pool pytree (one
        array set a layer of its layout, raw arrays) -> (logits, new pool,
        counters int32 [len(serving_counters)]). `valid` [S]: real tokens
        per slot in this write (a prefill's unpadded suffix length: bucket
        padding stays out of a quantized pool's block scales and out of a
        state); `slot`: the slot a prefill fills (its per-slot rows)."""
        cache = blocks.PagedDecodeCache(
            tuple(type(l)(*(Tensor(x) for x in l)) for l in pool),
            Tensor(tables), Tensor(pos),
            None if valid is None else Tensor(valid),
            None if slot is None else Tensor(slot))
        kwargs = {"cache": cache}
        if adapters is not None:
            kwargs["adapters"] = adapters
        out, _ = functional_call(
            self._model, params, self._buffers, args=(Tensor(ids),),
            kwargs=kwargs, train=False)
        logits, new_cache, counters = out
        return (logits._data,
                tuple(type(l)(*(x._data for x in l))
                      for l in new_cache.layers), counters._data)

    def _with_counters(self, tokens, counters):
        """A call's tokens with the model's counters behind them in ONE
        int32 array, where it counts anything: the call's one fetch brings
        both."""
        if not self._counter_names:
            return tokens
        return jnp.concatenate([tokens.astype(jnp.int32), counters])

    # -- decode: ONE executable ---------------------------------------------
    def _decode_fn(self, params, pool, packed, prev, adapter_tree=None):
        self._bump_decode_trace()            # trace-time only
        if (adapter_tree is None) != (self._adapter_bank is None):
            raise TypeError("the adapter tree rides the decode call exactly "
                            "when a bank is attached")
        host = self._pack().unpack(packed)
        tables, pos = host["tables"], host["pos"]
        # a slot's input: the token the host fed it, or the one the call
        # before produced, which never left the device
        tokens = jnp.where(host["fresh"] != 0, host["tokens"],
                           prev[:self.config.slots])
        # greedy takes no key at all; sampling derives each row's in the
        # trace from its packed (seed, gen)
        rng = self._unpack_rng(host["seeds"], host["gen"]) \
            if self._sampling else ()
        adapters = None if adapter_tree is None else \
            {"slot": host["adapter"], "layers": adapter_tree["layers"]}
        with self._numerics_scope() as sink:
            if self.kv_quantized:
                # fused health of the WHOLE quantized pool: scale
                # magnitudes plus the int8 code-saturation fraction
                # (codes pinned at +-127 mean the scale clipped)
                _numerics.tap_tree(
                    "kv.scale",
                    [x for l in pool for x in (l.k_scale, l.v_scale)])
                _numerics.tap_tree(
                    "kv.codes", [x for l in pool for x in (l.k, l.v)],
                    sat_threshold=127)
            quant = [v for v in params.values() if isinstance(v, dict)]
            if quant:
                _numerics.tap_tree("weights.scale",
                                   [w["scale"] for w in quant])
                _numerics.tap_tree("weights.q",
                                   [w["q"] for w in quant],
                                   sat_threshold=127)
            with blocks.attention_scope("decode_attn"):
                logits, npool, counters = self._run_model(
                    self._dequant_params(params), pool, tables, pos,
                    tokens[:, None], adapters=adapters)
            nxt = self._select_slots(logits[:, 0, :], None, *rng)
            _numerics.tap("decode.logits", logits[:, 0, :])
            if adapters is not None:
                _numerics.tap_tree("adapter.delta", adapters["layers"])
        # the positions are not returned: they are the host's to advance
        out = (self._with_counters(nxt, counters),
               self._constrain_pools(npool))
        if self.config.capture_logits:
            out = out + (logits[:, 0, :],)
        if sink is not None:
            out = out + (sink,)          # the sink rides LAST, always
        return out

    # -- prefill: one executable per SUFFIX bucket ---------------------------
    def _make_prefill(self, bucket):
        # a model may say that a long prompt hands back the last real
        # token's row of logits alone
        keeps_last = self._model.prefill_keeps_last_row(bucket)

        def prefill_fn(params, pool, packed):
            self.trace_counts["prefill"][bucket] = \
                self.trace_counts["prefill"].get(bucket, 0) + 1
            host = self._pack(bucket).unpack(packed)
            row, ids = host["row"], host["ids"]
            slot, length, start = host["slot"], host["length"], host["start"]
            # the key of the slot's next token, the expression
            # `_select_slots` computes in the decode trace: prefill (a
            # restart) and decode (the original) sample generation index
            # n identically. Greedy reads no key
            key = None
            if self._sampling:
                seed, gen = self._unpack_rng(host["seed"], host["gen"])
                key = jax.random.fold_in(jax.random.key(seed), gen)
            with self._numerics_scope() as sink:
                with blocks.attention_scope("prefill_attn"):
                    logits, npool, counters = self._run_model(
                        params, pool, row, start[None], ids[None, :],
                        valid=length[None], slot=slot)
                last = logits[0, 0] if keeps_last \
                    else jax.lax.dynamic_index_in_dim(logits[0], length - 1,
                                                      keepdims=False)
                first = self._select(last[None, :], key)
                _numerics.tap("prefill.logits", last[None, :])
            # the first token and the model's counters in one int32 array
            out = (self._with_counters(first, counters),
                   self._constrain_pools(npool))
            if sink is not None:
                out = out + (sink,)
            return out
        return self._cached(prefill_fn, f"prefill[{bucket}]",
                            donate_argnums=(1,))

    # -- public compute API --------------------------------------------------
    def prefill(self, slot, prompt_ids, rng=None, namespace=None):
        """Place `prompt_ids` into `slot`: match the prefix cache, alloc
        private blocks for the remainder, run the SUFFIX through the
        bucket executable (writes scatter into this slot's blocks), and
        return the first generated token. `last_prefill_stats` records
        the prefix hit for the scheduler's request metrics. `rng=(seed,
        gen)` arms the slot's per-request sampler state — the first
        token is generation index `gen` (a restart's delivered-token
        count), so a sampled stream resumes bit-identically.
        `namespace` (ISSUE 17) salts the prefix-cache keys — requests in
        different namespaces can never share blocks, and allocation
        pressure evicts the requester's own namespace first."""
        slot = int(slot)
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if self.config.max_len - prompt.size < 1:
            raise ValueError(
                f"prompt length {prompt.size} leaves no decode headroom "
                f"(max_len={self.config.max_len})")
        plen = int(prompt.size)
        bs = self.config.block_size
        cache = self.prefix_cache
        # the host's work around the executable is two siblings of
        # `serving::prefill`, which keeps its extent (the call and the
        # fetch): `admit` before it, `publish` after it
        with _span("serving::prefill.admit"):
            if self._slot_active[slot]:
                self.reset_slot(slot)
            cost = self._prefix_cost()
            # the prompt's chain keys, hashed once: `match` stops at its
            # first miss and `insert` goes on from there
            chain = None if cache is None \
                else cache.chain(prompt, namespace)
            # record=False: the hit/miss counters tick only when this
            # prefill STICKS — a BlockAllocError below means the scheduler
            # will retry and a per-attempt count would inflate the gated
            # hit rate
            # reserve = this prompt's total block need: tier promotion may
            # alloc to restore cold chain blocks, but never below the
            # headroom the suffix prefill is about to claim (ISSUE 18)
            shared_ids, nshared = ([], 0) if cache is None \
                else cache.match(
                    prompt, record=False, namespace=namespace,
                    reserve=self._blocks_for(plen), chain=chain)
            n_priv = self._blocks_for(plen) - nshared // bs
            try:
                priv = self._alloc_blocks(n_priv, requester=namespace) \
                    if n_priv else []
            except blocks.BlockAllocError:
                for b in shared_ids:          # give back the matched refs
                    self.block_pool.unref(b)
                raise
            finally:
                self._note_prefix_cost(cost, evict=True)
            row = np.zeros((self.config.max_blocks_per_slot,), np.int32)
            row[:len(shared_ids)] = shared_ids
            # the private ones where the layout puts the other positions:
            # behind the shared blocks, or a window layout's ring and
            # summary entries
            row[list(self._table_entries(nshared, plen - 1))] = priv
            self._tables[slot] = row
            self._slot_active[slot] = True
            self._slot_namespace[slot] = namespace
            if self.state_store is not None:
                self.state_store.acquire(slot)
            seed, gen = rng if rng is not None \
                else (self._default_slot_seed(), 0)
            self.set_slot_rng(slot, seed, gen)

            suffix = prompt[nshared:]
            bucket = self.bucket_for(suffix.size)
            padded = np.zeros((bucket,), np.int32)
            padded[:suffix.size] = suffix
        with RecordEvent("serving::prefill", TracerEventType.UserDefined,
                         {"bucket": bucket, "length": plen,
                          "slot": slot, "prefix_hit_tokens": nshared,
                          "paged": True, "kv_dtype": self.config.kv_dtype,
                          "attend": self.attention_impl}), \
                blocks.attention_impl(self.attention_impl):
            first = self._prefill_execute(slot, padded, int(suffix.size),
                                          nshared, bucket)
        with _span("serving::prefill.publish"):
            self._slot_gen[slot] += 1
            cost = self._prefix_cost()
            if cache is not None:
                # the prompt's fully-written blocks become shareable; the
                # matched prefix chain is already registered (touch only)
                cache.insert(prompt, row, (plen // bs) * bs,
                             namespace=namespace, chain=chain)
                cache.record_lookup(nshared > 0)
            self._note_prefix_cost(cost)
            tier_stats = cache.last_tier_stats if cache is not None \
                else {"promoted_blocks": 0, "restore_s": 0.0}
            self.last_prefill_stats = {
                "prefix_hit_tokens": nshared, "blocks_allocated": n_priv,
                "suffix_bucket": bucket,
                "tier_promoted_blocks": tier_stats["promoted_blocks"],
                "tier_restore_s": tier_stats["restore_s"]}
            first = int(first)
            self.set_slot_token(slot, first)
        return first

    def _prefix_cost(self):
        """What the prefix cache's bookkeeping has cost so far: (token
        updates fed to sha1, entries an eviction looked at)."""
        cache = self.prefix_cache
        return (0, 0) if cache is None \
            else (cache.hashed_tokens, cache.evict_visited)

    def _note_prefix_cost(self, since, evict=False):
        """Note on the open span what the bookkeeping cost since
        `since`, the way `pool_donated` is noted."""
        hashed, visited = self._prefix_cost()
        _TRACER.note("prefix_hashed_tokens", hashed - since[0])
        if evict:
            _TRACER.note("prefix_evict_visited", visited - since[1])

    def _prefill_execute(self, slot, padded, length, start, bucket):
        """Run the suffix through the bucket executable and commit the
        new pool/pos — the one device step of `prefill`, hook-shaped so
        the pipeline-parallel engine can stream the suffix through its
        stages in chunks instead. Returns the first token (host int)."""
        if bucket not in self._prefill:
            # a cold bucket compiles here, in `serving::prefill`'s own
            # time, ahead of the three children
            self._prefill[bucket] = self._make_prefill(bucket)
        # the three host phases of a prefill, as decode has them: the
        # host's arguments go up in one transfer; the bucket executable
        # is enqueued; the host blocks until the first token is back
        with _span("serving::prefill.upload"):
            before = self._transfers
            pool_in = self._pool
            args = self._prefill_args(bucket, slot, padded, length, start)
            _TRACER.note("transfers", self._transfers - before)
        with _span("serving::prefill.dispatch"):
            out = self._prefill[bucket](*args)
        # the pool that went in is gone: rebind before anything can raise
        first, self._pool = out[:2]
        _TRACER.note("pool_donated", self._pool_donated(pool_in))
        # what the layout's layers made of the prompt
        for name, n in self._layout.prefill_notes(bucket, length).items():
            _TRACER.note(name, n)
        with _span("serving::prefill.wait"):
            before = self._fetches
            # the model's counters ride behind the first token
            first = self._fetch(first).reshape(-1)
            _TRACER.note("fetches", self._fetches - before)
        # the one position that changed is the host's to write, once the
        # token is here
        self._pos[slot] = start + length
        if self._numerics_armed:
            self._ingest_numerics(out[2])
        # noted on `serving::prefill`, where the readers look for them
        for name, n in zip(self._counter_names, first[1:]):
            _TRACER.note(name, int(n))
        return int(first[0])

    # -- decode: the fetch one call behind ------------------------------------
    # `decode()` under `ahead()` enqueues its call and fetches the one
    # before; an engine that runs its decode another way says False
    _decodes_ahead = True

    @property
    def decodes_ahead(self):
        """Whether `decode()` can enqueue a call before the previous one is
        fetched: the engine can, and nothing on the host reads a call's
        results before the next one is enqueued (captured logits, armed
        numerics taps)."""
        return self._decodes_ahead and not self.config.capture_logits \
            and not self._numerics_armed

    @contextlib.contextmanager
    def ahead(self):
        """Within it, `decode()` enqueues its call, leaves it in flight and
        returns the tokens of the call before it (an empty array when none
        was in flight); `collect()` fetches the last one. Outside it, a
        call is enqueued and fetched in the same `decode()`."""
        if not self.decodes_ahead:
            raise RuntimeError(f"{type(self).__name__} fetches every decode "
                               f"call before the next one")
        self._ahead = True
        try:
            yield
        finally:
            self._ahead = False

    @property
    def decode_in_flight(self):
        """True while a call `ahead()` enqueued has not been fetched."""
        return self._inflight is not None

    def set_slot_token(self, slot, token):
        """Feed `token` as slot's next decode input, in place of what the
        previous call left on the device for it."""
        super().set_slot_token(slot, token)
        self._fresh[int(slot)] = 1
        if self._inflight is not None:
            self._inflight["fed"].add(int(slot))

    def _advance(self, call):
        """Advance the host's half of the slots past `call`, just
        enqueued: positions (clamped so free slots and slots at the end
        stay in bounds for ever), generation counters, and the device's
        copy of the tokens as the next call's input. What was there
        before is kept on `call` for `_rewind`."""
        call["before"] = (self._pos.copy(), self._slot_gen.copy(),
                          self._fresh.copy(), self._prev_out)
        call["fed"] = set()
        self._pos = np.minimum(self._pos + 1, self.config.max_len - 1,
                               dtype=np.int32)
        self._slot_gen += 1
        self._fresh[:] = 0
        self._prev_out = call["out"]

    def _rewind(self, lost, newer=None):
        """Put the host's half back where it was before call `lost` (and
        the `newer` one enqueued behind it), whose tokens never came: a
        step whose fetch failed is run again at the same positions and
        writes the same K/V. A slot fed since `lost` was enqueued keeps
        what it was fed."""
        if newer is not None:
            self._pos, self._slot_gen, self._fresh = newer["before"][:3]
        pos, gen, fresh, self._prev_out = lost["before"]
        keep = np.ones((self.config.slots,), bool)
        keep[list(lost["fed"])] = False
        self._pos[keep], self._slot_gen[keep] = pos[keep], gen[keep]
        self._fresh[keep] = fresh[keep]
        self._inflight = None

    def _fetch_call(self, call, newer=None):
        """Fetch `call`'s tokens under its own `serving::decode.wait` (the
        model's counters ride behind them); rewinds if they never come.
        The host's copy of a slot's last token (`_last_tokens`, what a
        speculative round drafts from) follows them, unless the host fed
        the slot since."""
        wait = dict(call["wait"], call=call["call"])
        try:
            with _span("serving::decode.wait", wait):
                before = self._fetches
                out = self._fetch(call["out"])
                wait["fetches"] = self._fetches - before
                if self._counter_names:
                    out, counts = np.split(out, [self.config.slots])
                    self.last_counters = dict(zip(
                        self._counter_names, map(int, counts)))
                    wait.update(self.last_counters)
        except BaseException:
            self._rewind(call, newer)
            raise
        fed = list(call["fed"])
        kept = self._last_tokens[fed]
        self._last_tokens = out.astype(np.int32)
        self._last_tokens[fed] = kept
        return out

    def collect(self):
        """Fetch the decode call `ahead()` left in flight: np.int32
        [slots], or None when there is none."""
        call, self._inflight = self._inflight, None
        return None if call is None else self._fetch_call(call)

    def _wait_attrs(self, pool_in):
        """What the wait span of a call says of it, from the host's
        positions at its dispatch."""
        wait = {"pool_donated": self._pool_donated(pool_in)}
        c = self.config
        if self.attention_impl != "gather":
            # what the kernel arm fetched of the dense view the gather
            # arm would have built
            wait["attn_blocks_read"] = int(
                ((self._pos[self._slot_active] + c.block_size)
                 // c.block_size).sum())
            wait["attn_blocks_table"] = c.slots * c.max_blocks_per_slot
        # what the layout's layers read: the gather arm builds the dense
        # view of every slot's whole table, a layer
        wait.update(self._layout.wait_notes(
            self._pos[self._slot_active],
            c.slots * c.max_blocks_per_slot * c.block_size
            if self.attention_impl == "gather" else None))
        return wait

    def decode(self):
        """Advance every slot one token; returns np.int32 [slots]. Active
        slots are guaranteed a writable block first (BlockAllocError
        under pressure — callers driving the engine directly see it; the
        scheduler pre-grows per slot so it can preempt instead). Under
        `ahead()` the call stays in flight and the tokens returned are
        the previous call's."""
        ahead = self._ahead
        if self._inflight is not None and not ahead:
            raise RuntimeError("a decode call is in flight: collect() it "
                               "first")
        # two siblings around `serving::decode_step`, which keeps its
        # extent (as `prefill.admit` and `.publish` are of
        # `serving::prefill`): `prepare` before it, `commit` after it
        with _span("serving::decode.prepare"):
            _faults.fire("serving.decode_step")
            self._fire_kv_quant_chaos()
            self._fire_numerics_chaos()
            self.ensure_decode_capacity()
            attrs = {"slots": self.config.slots,
                     "active": int(self._slot_active.sum()),
                     "paged": True,
                     "kv_dtype": self.config.kv_dtype,
                     "attend": self.attention_impl,
                     "ahead": int(self._inflight is not None),
                     "call": self._calls}
            attrs.update(self._layout.step_notes(
                self._pos[self._slot_active]))
        with RecordEvent("serving::decode_step",
                         TracerEventType.UserDefined, attrs), \
                blocks.attention_impl(self.attention_impl):
            # the three host phases of a decode step, each a child span:
            # tables, positions and fed tokens go up in one transfer; the
            # executable is enqueued; the host blocks until the tokens of
            # a call are back: this one's, or under `ahead()` the one's
            # before it
            with _span("serving::decode.upload"):
                before = self._transfers
                args = self._decode_args()
                _TRACER.note("transfers", self._transfers - before)
            with _span("serving::decode.dispatch", {"call": self._calls}):
                res = self._decode(*args)
            # the pool that went in is gone: rebind before the fetch, so
            # one that raises leaves the engine on live buffers
            self._pool = res[1]
            if self._numerics_armed:
                self._last_decode_args = args    # the localizer's replay
            call = {"call": self._calls, "out": res[0],
                    "wait": self._wait_attrs(args[1])}
            self._calls += 1
            self._advance(call)
            newer = None
            if ahead:
                # this call stays in flight; the one before it is fetched
                newer, call, self._inflight = call, self._inflight, call
            if call is None:
                with _span("serving::decode.wait", {"fetches": 0}):
                    out = np.zeros((0,), np.int32)
            else:
                out = self._fetch_call(call, newer)
        with _span("serving::decode.commit"):
            if self._numerics_armed:
                self._ingest_numerics(res[-1])
                res = res[:-1]
            if self.config.capture_logits:
                self.last_logits = np.asarray(res[2], np.float32)
            # the call's arguments and results are let go here, not as
            # the frame unwinds in the caller's time
            del args, res
        return out

    def _probe_context(self):
        return blocks.attention_impl(self.attention_impl)

    def _fire_kv_quant_chaos(self):
        """The `serving.kv_quant` chaos site (truncate mode, like the
        file-tear sites: the CALLER performs the damage): when armed and
        fired on a quantized engine, corrupt ONE in-use block's scale
        row (K and V, layer 0) — the int8 codes dequantize against a
        wrong scale from here on, which is exactly the silent-corruption
        class the serving_quant_* quality gate exists to catch."""
        if not self.kv_quantized:
            return
        spec = _faults.fire("serving.kv_quant")
        if spec is None or spec.mode != "truncate":
            # fire() also returns the spec for a served delay/raise —
            # only truncate mode contracts the caller to do damage
            return
        victim = next((int(b) for b in range(1, self.block_pool.num_blocks)
                       if self.block_pool.refcount(b) > 0), None)
        if victim is None:
            return
        layer = self._pool[0]
        self._pool = (type(layer)(
            layer.k, layer.v,
            layer.k_scale.at[victim].mul(64.0),
            layer.v_scale.at[victim].mul(64.0)),
        ) + self._pool[1:]

    # -- multi-host KV handoff (ISSUE 10) ------------------------------------
    def extract_kv(self, slot):
        """The handoff SOURCE half: read the `pos[slot]` resident tokens
        of `slot` out of the pool, per layer, as host numpy
        [plen, heads, head_dim] arrays (block padding stripped — only
        real tokens ship). Lossless: the bytes a decode worker adopts
        are bit-identical to what a local prefill would have written,
        which is what makes cross-host greedy streams exact. Returns
        (ks, vs, plen)."""
        self._require_kv_layout("extract_kv")
        row, plen, nb = self._extract_row(slot)
        ks, vs = [], []
        for layer in self._pool:
            k, v = blocks.read_kv(layer, row)            # [nb, bs, h, d]
            ks.append(self._strip_padding(k, nb, plen))
            vs.append(self._strip_padding(v, nb, plen))
        return ks, vs, plen

    def _extract_row(self, slot):
        """Shared head of the extract paths: validate the slot, return
        (block-id row device array, resident tokens, block count)."""
        slot = int(slot)
        if not self._slot_active[slot]:
            raise ValueError(f"slot {slot} holds no request to extract")
        plen = int(self._pos[slot])
        if plen < 1:
            raise ValueError(f"slot {slot} has no resident tokens")
        nb = self._blocks_for(plen)
        return jnp.asarray(self._tables[slot][:nb], jnp.int32), plen, nb

    def _strip_padding(self, arr, nb, plen):
        """[nb, bs, h, d] block stack -> contiguous [plen, h, d] host
        tokens (block padding stripped — only real tokens ship)."""
        a = np.asarray(jax.device_get(arr))
        return np.ascontiguousarray(
            a.reshape(nb * self.config.block_size, *a.shape[2:])[:plen])

    def extract_kv_wire(self, slot):
        """The wire-format half of `extract_kv`: everything
        `kv_handoff.pack_kv_bundle` needs, quantization-aware. Float
        engines return {"ks", "vs", "plen"}; quantized engines add the
        int8 codes' per-block per-head scales ("k_scales"/"v_scales",
        [nblocks, heads] float32 per layer) and "scale_block" (this
        pool's block size — the span each scale row covers), so the
        bundle ships the int8 bytes instead of a 4x dequantized copy."""
        self._require_kv_layout("extract_kv_wire")
        if not self.kv_quantized:
            ks, vs, plen = self.extract_kv(slot)
            return {"ks": ks, "vs": vs, "plen": plen}
        row, plen, nb = self._extract_row(slot)
        ks, vs, kss, vss = [], [], [], []
        for layer in self._pool:
            ks.append(self._strip_padding(layer.k[row], nb, plen))
            vs.append(self._strip_padding(layer.v[row], nb, plen))
            kss.append(np.asarray(jax.device_get(layer.k_scale[row]),
                                  np.float32))
            vss.append(np.asarray(jax.device_get(layer.v_scale[row]),
                                  np.float32))
        return {"ks": ks, "vs": vs, "plen": plen, "k_scales": kss,
                "v_scales": vss, "scale_block": self.config.block_size}

    def adopt_kv(self, slot, ks, vs, plen, first_token, rng=None):
        """The handoff SINK half: place a request whose prefill ran on
        ANOTHER host. Allocates the blocks `plen` tokens need, scatters
        the per-layer K/V slices into them through one fixed-shape
        `adopt[bucket]` executable (padded to the prefill-bucket ladder,
        so adoption compiles at most `len(buckets)` times, ever), and
        arms the slot exactly as a local prefill would: pos=plen, next
        decode input = `first_token` (the token the prefill host already
        emitted). `rng=(seed, gen)` is the v3 bundle's sampler state —
        the adopting slot's next token is generation index `gen`, so a
        sampled stream continues bit-identically across the handoff;
        None (v1/v2 bundles) arms a fresh local seed: greedy-only
        failover, as before ISSUE 13. Raises BlockAllocError under
        pressure — the scheduler's cue to preempt, like prefill."""
        self._require_kv_layout("adopt_kv")
        slot = int(slot)
        plen = int(plen)
        cfg = self._model.cfg
        head_shape = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        if len(ks) != cfg.num_layers or len(vs) != cfg.num_layers:
            raise ValueError(
                f"adopt bundle has {len(ks)}/{len(vs)} layers, model has "
                f"{cfg.num_layers}")
        for arr in list(ks) + list(vs):
            if tuple(arr.shape) != (plen,) + head_shape:
                raise ValueError(
                    f"adopt layer shape {tuple(arr.shape)} != "
                    f"{(plen,) + head_shape}")
        if plen < 1:
            raise ValueError("empty adopt bundle")
        if plen > self.max_prompt_len or self.config.max_len - plen < 1:
            raise ValueError(
                f"adopted prefix ({plen} tokens) exceeds the engine "
                f"limits (max prompt {self.max_prompt_len}, max_len "
                f"{self.config.max_len})")
        if self._slot_active[slot]:
            self.reset_slot(slot)
        bs = self.config.block_size
        n = self._blocks_for(plen)
        priv = self._alloc_blocks(n)        # all-or-nothing; may raise
        row = np.zeros((self.config.max_blocks_per_slot,), np.int32)
        row[:n] = priv
        self._tables[slot] = row
        self._slot_active[slot] = True
        bucket = self.bucket_for(plen)
        dtype = np.float32 if self.kv_quantized else self._pool[0].k.dtype
        pad_ks, pad_vs = [], []
        for k, v in zip(ks, vs):
            pk = np.zeros((bucket,) + head_shape, dtype)
            pv = np.zeros((bucket,) + head_shape, dtype)
            pk[:plen] = np.asarray(k, dtype)
            pv[:plen] = np.asarray(v, dtype)
            pad_ks.append(jnp.asarray(pk))
            pad_vs.append(jnp.asarray(pv))
        try:
            with RecordEvent("serving::adopt_kv",
                             TracerEventType.UserDefined,
                             {"slot": slot, "tokens": plen,
                              "bucket": bucket, "blocks": n}), \
                    blocks.attention_impl(self.attention_impl):
                self._adopt_scatter(slot, bucket, pad_ks, pad_vs)
        except Exception:
            self.reset_slot(slot)           # never strand the blocks
            raise
        self._pos[slot] = plen
        self.set_slot_token(slot, first_token)
        if rng is not None:
            self.set_slot_rng(slot, rng[0], rng[1])
        else:
            self.set_slot_rng(slot, self._default_slot_seed(), 0)
        self.last_prefill_stats = {"prefix_hit_tokens": 0,
                                   "blocks_allocated": n,
                                   "suffix_bucket": bucket,
                                   "adopted": True}
        return int(first_token)

    def _adopt_scatter(self, slot, bucket, pad_ks, pad_vs):
        """Run the adopt executable(s) and commit the new pool(s) — the
        one device step of `adopt_kv`, hook-shaped so the
        pipeline-parallel engine can scatter each stage's layer slices
        into that stage's own resident pool."""
        if bucket not in self._adopt:
            self._adopt[bucket] = self._make_adopt(bucket)
        pool_in = self._pool
        self._pool = self._adopt[bucket](
            pool_in, jnp.asarray(self._tables),
            jnp.asarray(slot, jnp.int32), pad_ks, pad_vs)
        _TRACER.note("pool_donated", self._pool_donated(pool_in))

    def _make_adopt(self, bucket):
        """One fixed-shape KV-adopt executable per bucket: scatter the
        padded [bucket, h, d] layer slices into the slot's blocks from
        position 0 (padding past plen lands in the slot's own blocks
        beyond pos — invisible, overwritten by decode, exactly like a
        right-padded local prefill tail). A quantized pool adopts
        through the quantizing write, so the adopted prefix requantizes
        against THIS pool's block layout."""
        nb = self.config.max_blocks_per_slot

        def adopt_fn(pool, tables, slot, new_ks, new_vs):
            self.trace_counts["adopt"][bucket] = \
                self.trace_counts["adopt"].get(bucket, 0) + 1
            slot = slot.astype(jnp.int32)
            row = jax.lax.dynamic_slice(tables, (slot, 0), (1, nb))
            zero = jnp.zeros((1,), jnp.int32)
            return self._constrain_pools(tuple(
                blocks.write_kv(layer, k[None], v[None], row, zero)
                for layer, k, v in zip(pool, new_ks, new_vs)))
        return self._cached(adopt_fn, f"adopt[{bucket}]",
                            donate_argnums=(0,))

    # -- fleet-global prefix cache halves (ISSUE 18) -------------------------
    def prefix_probe(self, prompt_ids, namespace=None):
        """Longest servable cached-prefix length for `prompt_ids`, in
        tokens, counting HBM entries AND tiered continuations.
        Side-effect-free (no refs, no LRU touches, no promotion) — the
        `OP_PREFIX_LOOKUP` readonly fabric verb answers from this, and
        the DistFrontend's affinity sweep calls it on every shard."""
        if self.prefix_cache is None:
            return 0
        toks = np.asarray(prompt_ids, np.int64).reshape(-1)
        return int(self.prefix_cache.probe(toks, namespace))

    def extract_prefix_kv(self, prompt_ids, namespace=None):
        """The fleet restore SOURCE half: read this engine's cached
        chain for `prompt_ids` — HBM entries and tiered continuations
        both — as per-layer [plen, heads, head_dim] float32 host arrays
        (the `extract_kv` wire shape), plus the covered token count.
        Entries stay resident here; the peer registers a COPY. Tiered
        records are verified (sha256 on disk) before export — a corrupt
        record ends the walk, shipping only the good prefix."""
        self._require_kv_layout("extract_prefix_kv")
        if self.prefix_cache is None:
            return [], [], 0
        toks = np.asarray(prompt_ids, np.int64).reshape(-1)
        bs = self.config.block_size
        cache = self.prefix_cache
        chain = cache.chain(toks, namespace)
        nl = len(self._pool)
        parts_k = [[] for _ in range(nl)]
        parts_v = [[] for _ in range(nl)]
        n = 0
        for k in range((len(toks) - 1) // bs):
            key = chain.key(k)
            blk = cache._entries.get(key)
            if blk is not None:
                for li, layer in enumerate(self._pool):
                    kb, vb = blocks.read_kv(layer, blk)
                    parts_k[li].append(
                        np.asarray(jax.device_get(kb), np.float32))
                    parts_v[li].append(
                        np.asarray(jax.device_get(vb), np.float32))
                n += 1
                continue
            rec = self.kv_tiers.peek(key) if self.kv_tiers is not None \
                and key in self.kv_tiers else None
            if rec is None:
                break
            for li in range(nl):
                kb = np.asarray(rec["arrays"][f"k{li}"])
                vb = np.asarray(rec["arrays"][f"v{li}"])
                if rec.get("quant"):
                    ksc = rec["arrays"][f"ks{li}"]
                    vsc = rec["arrays"][f"vs{li}"]
                    kb = np.asarray(blocks.dequant_codes(
                        kb, ksc[None, :, None]), np.float32)
                    vb = np.asarray(blocks.dequant_codes(
                        vb, vsc[None, :, None]), np.float32)
                parts_k[li].append(np.asarray(kb, np.float32))
                parts_v[li].append(np.asarray(vb, np.float32))
            n += 1
        if n == 0:
            return [], [], 0
        ks = [np.ascontiguousarray(np.concatenate(p)) for p in parts_k]
        vs = [np.ascontiguousarray(np.concatenate(p)) for p in parts_v]
        return ks, vs, n * bs

    def restore_prefix(self, prompt_ids, ks, vs, plen, namespace=None):
        """The fleet restore SINK half: register another host's exported
        prefix chain into THIS engine's prefix cache, so the very next
        local prefill of `prompt_ids` matches it like a warm local
        chain. Eager per-block device writes only (`_tier_write_block`)
        — no new traced programs, the compile-once contract holds.

        Fires `serving.kv_restore` once for the whole bundle: raise or
        truncate degrades to restoring NOTHING (the prefill recomputes
        — never a partial/corrupt registration). Allocation pressure
        (BlockAllocError after eviction) ends the walk early: the good
        prefix registered so far still matches. Returns tokens now
        servable from the restored chain (multiple of block_size)."""
        self._require_kv_layout("restore_prefix")
        if self.prefix_cache is None or int(plen) < 1:
            return 0
        from .kv_tiers.store import corrupt_counter
        try:
            spec = _faults.fire("serving.kv_restore")
        except Exception:
            # failed wire-restore read: nothing registers, the prefill
            # recomputes — latched failure-class like tiered restores
            corrupt_counter().inc()
            return 0
        if spec is not None and spec.mode == "truncate":
            corrupt_counter().inc()
            return 0
        cfg = self._model.cfg
        head_shape = (cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        if len(ks) != cfg.num_layers or len(vs) != cfg.num_layers:
            raise ValueError(
                f"restore bundle has {len(ks)}/{len(vs)} layers, model "
                f"has {cfg.num_layers}")
        for arr in list(ks) + list(vs):
            if tuple(np.asarray(arr).shape) != (int(plen),) + head_shape:
                raise ValueError(
                    f"restore layer shape {tuple(np.asarray(arr).shape)} "
                    f"!= {(int(plen),) + head_shape}")
        toks = np.asarray(prompt_ids, np.int64).reshape(-1)
        bs = self.config.block_size
        n = min(int(plen) // bs, (len(toks) - 1) // bs)
        cache = self.prefix_cache
        chain = cache.chain(toks, namespace)
        prev_key = None
        restored = 0
        for k in range(n):
            key = chain.key(k)
            if key in cache._entries:
                cache._touch(key)
                prev_key = key
                restored += 1
                continue
            if self.kv_tiers is not None and key in self.kv_tiers:
                # the continuation is tiered locally: stop registering —
                # a later entry whose parent lives in a cold tier would
                # orphan the chain (match promotes the tiered entry
                # itself when the prefill arrives)
                break
            try:
                blk = int(self._alloc_blocks(1, requester=namespace)[0])
            except blocks.BlockAllocError:
                break
            arrays = {}
            for li, layer in enumerate(self._pool):
                kb = np.ascontiguousarray(np.asarray(
                    ks[li][k * bs:(k + 1) * bs], np.float32))
                vb = np.ascontiguousarray(np.asarray(
                    vs[li][k * bs:(k + 1) * bs], np.float32))
                if hasattr(layer, "k_scale"):
                    ksc = np.maximum(
                        np.abs(kb).max(axis=(0, 2)), 1e-30
                    ).astype(np.float32)
                    vsc = np.maximum(
                        np.abs(vb).max(axis=(0, 2)), 1e-30
                    ).astype(np.float32)
                    arrays[f"k{li}"] = np.asarray(blocks.quantize_codes(
                        kb, ksc[None, :, None]), np.int8)
                    arrays[f"v{li}"] = np.asarray(blocks.quantize_codes(
                        vb, vsc[None, :, None]), np.int8)
                    arrays[f"ks{li}"] = ksc
                    arrays[f"vs{li}"] = vsc
                else:
                    arrays[f"k{li}"] = kb
                    arrays[f"v{li}"] = vb
            self._tier_write_block(blk, arrays)
            cache.register_block(key, blk, namespace, prev_key)
            prev_key = key
            restored += 1
        return restored * bs

    def reset_slot(self, slot):
        """Free the slot: every table entry drops the request's
        reference (blocks return to the pool unless the prefix cache
        still holds them), pos=0 hides whatever remains."""
        slot = int(slot)
        for b in self._tables[slot]:
            if b != blocks.GARBAGE_BLOCK:
                self.block_pool.unref(int(b))
        self._tables[slot] = blocks.GARBAGE_BLOCK
        self._slot_active[slot] = False
        self._pos[slot] = 0
        self.set_slot_token(slot, 0)
        self.set_slot_rng(slot, 0, 0)
        self._slot_adapter[slot] = 0
        self._slot_namespace.pop(slot, None)
        if self.state_store is not None:
            # the state rows are dead from here: the next prefill into
            # this slot starts from zeros and overwrites them
            self.state_store.release(slot)

    def slot_positions(self):
        return self._pos.copy()

    def layout_gauges(self):
        """What `serving::step` says of the cache as a step ends, as the
        layout answers it from the engine's numbers: the per-slot state and
        the paged rows in bytes and the prefix lookups that were refused
        (nothing of a layout of K/V layers)."""
        store, cache = self.state_store, self.prefix_cache
        return self._layout.gauges(
            # of the last token written
            np.maximum(self._pos[self._slot_active] - 1, 0),
            {"state_slots_in_use": 0 if store is None else store.in_use,
             "state_bytes": 0 if store is None else store.bytes_in_use,
             "latent_bytes_in_use":
                 self.block_pool.in_use * self.kv_block_bytes,
             "prefix_cache_bypassed": 0 if cache is None
             else cache.bypassed})


def _engine_kind(config):
    """"dense" | "paged" | "spec" | "tp" | "pp" | "spec_pp" for an
    EngineConfig-family instance (most-derived class first). The TP/PP
    checks consult sys.modules instead of importing: those config
    classes can only exist if their module was already imported, so
    classifying a plain dense/paged/spec config never pulls the
    multi-host tier in (the lazy-import contract of
    serving/distributed/)."""
    import sys
    from .spec_decode import SpecDecodeConfig
    pp_mod = sys.modules.get("paddle_tpu.serving.distributed.pp")
    if pp_mod is not None and \
            isinstance(config, pp_mod.PipelineParallelSpecConfig):
        return "spec_pp"
    if isinstance(config, SpecDecodeConfig):
        return "spec"
    if pp_mod is not None and \
            isinstance(config, pp_mod.PipelineParallelEngineConfig):
        return "pp"
    tp_mod = sys.modules.get("paddle_tpu.serving.distributed.tp")
    if tp_mod is not None and \
            isinstance(config, tp_mod.TensorParallelEngineConfig):
        return "tp"
    if isinstance(config, PagedEngineConfig):
        return "paged"
    if isinstance(config, EngineConfig):
        return "dense"
    raise TypeError(f"engine_config must be an EngineConfig, got "
                    f"{type(config).__name__}")


def make_engine(model, kind, config_dict, compile_cache_dir=None):
    """Rebuild an engine from a `.gencfg` serving record: the recorded
    ctor kwargs plus a machine-local compile-cache dir. Only an
    explicit kind="tp"/"pp" pays the multi-host tier import."""
    from .spec_decode import SpecDecodeConfig, SpeculativeEngine
    classes = {"dense": (GenerationEngine, EngineConfig),
               "paged": (PagedGenerationEngine, PagedEngineConfig),
               "spec": (SpeculativeEngine, SpecDecodeConfig)}
    if kind == "tp":
        from .distributed.tp import (TensorParallelEngineConfig,
                                     TensorParallelPagedEngine)
        classes["tp"] = (TensorParallelPagedEngine,
                         TensorParallelEngineConfig)
    if kind == "pp":
        from .distributed.pp import (PipelineParallelEngineConfig,
                                     PipelineParallelPagedEngine)
        classes["pp"] = (PipelineParallelPagedEngine,
                         PipelineParallelEngineConfig)
    if kind == "spec_pp":
        from .distributed.pp import (PipelineParallelSpecConfig,
                                     PipelineParallelSpeculativeEngine)
        classes["spec_pp"] = (PipelineParallelSpeculativeEngine,
                              PipelineParallelSpecConfig)
    if kind not in classes:
        raise ValueError(
            f"unknown serving engine kind {kind!r}; want one of "
            f"{sorted(classes) + ['tp', 'pp', 'spec_pp']}")
    engine_cls, cfg_cls = classes[kind]
    cfg = cfg_cls(compile_cache_dir=compile_cache_dir, **config_dict)
    return engine_cls(model, cfg)


def save_for_generation(model, path, input_spec=None, engine_config=None,
                        precompile=False, compile_cache_dir=None):
    """jit.save the model's plain forward AND persist its GPTConfig next to
    the artifact (`path.gencfg`), so a cold `inference.Predictor` can
    rebuild the cached-forward Layer and serve `generate` — the
    generation analogue of save_inference_model.

    With `engine_config` (an EngineConfig/PagedEngineConfig/
    SpecDecodeConfig), the sidecar additionally records the serving
    engine kind, its config, and the full executable set (decode + every
    prefill bucket + the speculative draft/verify set), so a Predictor
    rebuilds the EXACT engine the artifact was built for. With
    `precompile=True` the whole set is AOT-compiled right now into the
    persistent compile cache (`compile_cache_dir`, default
    `compile_cache.default_dir()`) — a cold Predictor then deserializes
    executables instead of compiling and is serving in seconds. Returns
    the precompile report ({executable: hit|miss|off}) or None."""
    from ..jit import save as jit_save
    from ..static import InputSpec
    from ..text.models.gpt import GPT, GPTForGeneration
    if isinstance(model, GPTForGeneration):
        model = model.gpt
    if not isinstance(model, GPT):
        raise TypeError("save_for_generation expects a GPT/GPTForGeneration")
    if input_spec is None:
        # batch stays symbolic; the sequence dim must be concrete (the
        # causal-attention trace compares sequence sizes, which symbolic
        # dims cannot answer). The one-shot run() path serves full-length
        # inputs; generate() rebuilds the Layer and is length-free.
        input_spec = [InputSpec([None, model.cfg.max_position_embeddings],
                                "int64", name="input_ids")]
    jit_save(model, path, input_spec=input_spec)
    cfg = {k: getattr(model.cfg, k) for k in (
        "vocab_size", "max_position_embeddings", "hidden_size", "num_layers",
        "num_heads", "intermediate_size", "hidden_dropout",
        "attention_dropout", "initializer_range", "tie_embeddings")}
    meta = {"model_family": "gpt", "config": cfg}
    engine = None
    if precompile and engine_config is None:
        raise ValueError("precompile=True needs an engine_config: the "
                         "executable set to AOT-build is derived from it")
    if engine_config is not None:
        kind = _engine_kind(engine_config)
        cache_dir = compile_cache_dir or _cc.default_dir()
        if precompile:
            engine = make_engine(model, kind, engine_config.as_dict(),
                                 compile_cache_dir=cache_dir)
            names = engine.executable_names()
        else:
            names = _executable_set(kind, engine_config)
        meta["serving"] = {"engine": kind,
                           "config": engine_config.as_dict(),
                           "executables": names}
    with open(path + GENCFG_SUFFIX, "w") as f:
        json.dump(meta, f)
    if engine is not None:
        return engine.precompile()
    return None


def _executable_set(kind, config):
    """Executable names for a serving record without building the engine
    (the precompile=False recording path) — the per-stage set for the
    pipeline kinds, mirroring each engine's executable_names()."""
    if kind in ("pp", "spec_pp"):
        # a pp-kind config only exists if its module is imported (the
        # lazy contract _engine_kind documents), so this import is free
        from .distributed.pp import pp_executable_names
        return pp_executable_names(config, spec=(kind == "spec_pp"))
    names = ["decode"] + [f"prefill[{b}]" for b in config.prefill_buckets]
    if kind == "spec":
        names += ["draft_decode", "spec_verify"]
        names += [f"draft_prefill[{b}]" for b in config.prefill_buckets]
    return names


def load_generation_model(prog_file, params):
    """Rebuild the eager GPT from a `.gencfg` sidecar + a loaded params
    dict (raw arrays keyed by state_dict names). Returns None when the
    artifact was not saved via save_for_generation."""
    base = prog_file[:-len(".pdmodel")] if prog_file.endswith(".pdmodel") \
        else prog_file
    gencfg = base + GENCFG_SUFFIX
    if not os.path.exists(gencfg):
        return None
    with open(gencfg) as f:
        meta = json.load(f)
    from ..text.models.gpt import GPT, GPTConfig
    model = GPT(GPTConfig(**meta["config"]))
    model.eval()
    state = {n: Tensor(v) for n, v in params.items()}
    model.set_state_dict(state)
    return model
