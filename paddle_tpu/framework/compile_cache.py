"""Persistent compiled-executable cache + AOT warmup (ROADMAP item 5).

Every process used to recompile every executable from scratch: the
serving engine's compile-once guarantee (PR 3/6/7) died with the
process, so a cold `inference.Predictor` paid the full prefill-bucket +
decode + verify compilation bill before its first token, and every
replacement worker the PR 5 relaunch machinery brought up paid it
again. The reference stack gets warm starts for free from ahead-of-time
ProgramDesc compilation (AnalysisPredictor pays analysis ONCE,
inference/api/analysis_predictor.h:95); this module is the TPU-native
equivalent: XLA executables are serialized to disk once and later
processes deserialize them instead of compiling.

Two tiers, chosen per entry at commit time, degrading transparently:

  executable  `jax.experimental.serialize_executable` round-trips the
              compiled artifact itself — a warm load performs ZERO
              tracing and ZERO compilation,
  exported    when the executable does not serialize (backend/version
              quirks), the lowering is persisted via `jax.export` and
              compiled at load — the python trace is skipped, the XLA
              compile is paid,
  (miss)      when neither round-trips, the entry is simply not
              persisted and the call behaves exactly like plain
              `jax.jit` — caching can degrade, never break.

Key derivation (docs/compile_cache.md has the full walkthrough). A key
digests, in order:

  - the CACHE FORMAT version,
  - jax / jaxlib versions and the backend platform + device kind
    (serialized executables are not portable across either),
  - the framework source fingerprint — a digest over every `.py` file
    of the `paddle_tpu` package, so ANY code change invalidates
    signature-keyed entries (conservative by construction: a stale
    executable can never be served after a deploy),
  - per `key_mode`:
      "lowering"   the StableHLO text of the lowered program — fully
                   content-addressed (shapes, dtypes, sharding/mesh and
                   donation all appear in the module text). Used for
                   the device-layer eager op runners, which trace
                   cheaply anyway; the persistent tier only skips the
                   XLA compile.
      "signature"  a static signature (caller-provided config dict,
                   e.g. model + engine config) plus the flattened
                   input avals (treedef, shapes, dtypes, weak types)
                   and the donation spec — computed WITHOUT tracing,
                   so a warm hit never runs the python function at all.
                   This is what lets a restarted serving process report
                   zero traces in its compile-once counters.

Commit protocol: each entry is a directory committed through
`framework/ckpt_commit.atomic_commit` — data files first, sha256
MANIFEST last, fsync, atomic rename. SIGKILL mid-commit leaves a hidden
tempdir readers never see; a torn or bit-rotted entry fails manifest
verification at load and is deleted and recompiled. The
`checkpoint.write` fault-injection site fires inside every commit, so
the crash suite (tests/test_compile_cache.py) replays torn writes and
kill-windows deterministically. Corruption therefore ALWAYS degrades to
a miss-and-recompile, never a crash or a wrong executable.

Invalidation / coherence with the in-memory op cache:
`device.clear_op_cache()` calls `invalidate_active()`, which stamps the
active cache with "bypass anything committed before now": entries older
than the stamp read as misses for the REST OF THIS PROCESS and are
recommitted on the next compile, so a cleared in-memory cache can never
resurrect a pre-clear persistent entry. Fresh processes see every entry
again — content-addressed keys (and the source fingerprint) make that
safe across restarts, which is the entire point of the cache.

Retention (ROADMAP item 5 debt): `FLAGS_compile_cache_max_entries` (or
`CompileCache(max_entries=)`) caps committed entries per cache dir —
a `gc_old`-style sweep runs at commit time, evicting least-recently-USED
first (dir mtime; lookup hits refresh it), never the entry just
committed. 0 = unlimited (the default).

Placement (docs/compile_cache.md): ONE rule, `place()`, called by every
entry point that compiles for the chip. `$JAX_COMPILATION_CACHE_DIR`, when
set, is the root and code sets no jax cache directory (jax reads the
variable itself); unset, the root is `<checkout>/.jax_cache`. jax's own
persistent cache writes into the root, this module's entries default to
its `executables/` sub-directory (`default_dir()`), and an explicit
`compile_cache_dir=` from a caller still wins for that caller. The path
is never derived from a temporary name, a pid or a clock — a cache that
moves never hits.

Observability: `compile_cache_hits_total` / `compile_cache_misses_total`
counters (the hits/misses rate-rule in tools/metrics_report.py gates a
hit-rate drop as a failure-class regression), per-executable compile and
load seconds histograms, and per-instance `stats` dicts
(tests/test_compile_cache.py reads them across a restart).
"""
import hashlib
import json
import os
import pickle
import shutil
import time
import warnings

from . import ckpt_commit
from ..observability import metrics as _metrics

__all__ = ["FORMAT_VERSION", "ENTRY_SCHEMA", "CompileCache",
           "CachedFunction", "cached_jit", "attach", "detach", "active",
           "invalidate_active", "framework_fingerprint", "aval_signature",
           "CACHE_ENV", "cache_root", "default_dir", "place"]

FORMAT_VERSION = 1
ENTRY_SCHEMA = "paddle_tpu.compile_cache.v1"
ENTRY_META = "entry.json"
EXEC_FILE = "executable.pkl"
EXPORT_FILE = "exported.bin"

_M_HITS = _metrics.counter(
    "compile_cache_hits_total",
    "Persistent compile-cache lookups served from disk")
_M_MISSES = _metrics.counter(
    "compile_cache_misses_total",
    "Persistent compile-cache lookups that had to compile")
_M_COMPILE_S = _metrics.histogram(
    "compile_cache_compile_seconds",
    "Per-executable XLA compile wall time on a cache miss",
    labelnames=("executable",))
_M_LOAD_S = _metrics.histogram(
    "compile_cache_load_seconds",
    "Per-executable deserialize/compile-at-load wall time on a hit",
    labelnames=("executable",))


# -------------------------------------------------------------- placement

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root():
    """Where compiled artefacts live: `$JAX_COMPILATION_CACHE_DIR` when
    set, else `<checkout>/.jax_cache` (git-ignored)."""
    return os.path.abspath(os.environ.get(CACHE_ENV)
                           or os.path.join(_CHECKOUT, ".jax_cache"))


def default_dir():
    """Default directory of this module's executable entries: a
    sub-directory of the root, so one variable places both caches."""
    return os.path.join(cache_root(), "executables")


def place(explicit=None):
    """Turn on jax's persistent compilation cache under the placement
    rule and return its directory. With `$JAX_COMPILATION_CACHE_DIR` set
    this sets NO directory (jax already reads the variable); otherwise
    the directory is `explicit` (a caller's own `compile_cache_dir=`) or
    the checkout default. Every executable is made cacheable — the
    serving set is many sub-second compiles."""
    import jax
    if os.environ.get(CACHE_ENV):
        path = cache_root()
    else:
        path = os.path.abspath(str(explicit)) if explicit else cache_root()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------ fingerprint

_FINGERPRINT = None


def framework_fingerprint():
    """Digest over every `.py` source file of the paddle_tpu package plus
    the jax/jaxlib versions and backend platform + device kind. Two
    processes share signature-keyed entries ONLY when this matches, so a
    code change or runtime upgrade can never serve a stale executable.
    Computed once per process (the backend must already be initialized —
    every caller compiles executables, so it is)."""
    global _FINGERPRINT
    if _FINGERPRINT is not None:
        return _FINGERPRINT
    import jax
    h = hashlib.sha256()
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = []
    for dirpath, _, names in os.walk(pkg_root):
        for name in names:
            if name.endswith(".py"):
                full = os.path.join(dirpath, name)
                files.append((os.path.relpath(full, pkg_root), full))
    for rel, full in sorted(files):
        h.update(rel.encode())
        try:
            with open(full, "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
        except OSError:
            h.update(b"<unreadable>")
    h.update(jax.__version__.encode())
    try:
        import jaxlib
        h.update(getattr(jaxlib, "__version__", "?").encode())
    except ImportError:
        pass
    dev = jax.devices()[0]
    h.update(jax.default_backend().encode())
    h.update(getattr(dev, "device_kind", "?").encode())
    _FINGERPRINT = h.hexdigest()
    return _FINGERPRINT


def aval_signature(args):
    """Deterministic, trace-free signature of a call's inputs: the pytree
    structure plus (shape, dtype, weak_type) per array leaf and
    (type, repr) per non-array leaf. Stable across processes — dict
    insertion order rides the treedef repr, which callers keep fixed."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append((tuple(int(s) for s in leaf.shape),
                          str(leaf.dtype),
                          bool(getattr(leaf, "weak_type", False))))
        else:
            parts.append((type(leaf).__name__, repr(leaf)))
    return (str(treedef), tuple(parts))


def _digest(parts):
    h = hashlib.sha256()
    for p in parts:
        h.update(repr(p).encode())
        h.update(b"\x1f")
    return h.hexdigest()


def _safe_name(name):
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in name)[:80]


# ------------------------------------------------------------- the cache

class CompileCache:
    """One on-disk executable cache directory. Entries are committed via
    the ckpt_commit atomic protocol; `lookup` verifies the manifest and
    treats ANY verification or deserialization failure as a miss (the
    offending entry is deleted so the next compile recommits it)."""

    def __init__(self, path, max_entries=None):
        self.path = os.path.abspath(str(path))
        os.makedirs(self.path, exist_ok=True)
        # entries committed before this stamp are bypassed (see
        # invalidate()); 0.0 = serve everything
        self._min_ts = 0.0
        if max_entries is None:
            # the raw dict, not get_flags(): flags.py attaches the
            # process-global cache at import time, before its accessors
            # are defined
            from .flags import _FLAGS
            max_entries = _FLAGS.get("FLAGS_compile_cache_max_entries", 0)
        self.max_entries = int(max_entries or 0)
        self.stats = {"hits": 0, "misses": 0, "bypass": 0, "corrupt": 0,
                      "uncacheable": 0, "evicted": 0}

    # -- key --------------------------------------------------------------
    def entry_key(self, name, parts):
        """(dirname, digest) for an executable `name` + key `parts`
        (which must already include the mode-specific content — lowering
        hash or static signature + avals)."""
        digest = _digest((FORMAT_VERSION, framework_fingerprint()) + parts)
        return f"{_safe_name(name)}.{digest[:24]}", digest

    def _entry_dir(self, dirname):
        return os.path.join(self.path, dirname)

    def invalidate(self):
        """Bypass every entry committed before NOW for the rest of this
        process (they read as misses and are overwritten by the next
        compile). The coherence hook behind `device.clear_op_cache()` —
        a cleared in-memory cache must not resurrect a pre-clear
        persistent entry. Fresh processes see all entries again."""
        self._min_ts = time.time()

    def clear(self):
        """Delete every committed entry (the persistent analogue of
        clear_op_cache's in-memory wipe)."""
        for name in os.listdir(self.path):
            full = self._entry_dir(name)
            if os.path.isdir(full):
                shutil.rmtree(full, ignore_errors=True)

    def entries(self):
        """Names of committed (manifested) entries."""
        out = []
        for name in sorted(os.listdir(self.path)):
            full = self._entry_dir(name)
            if not name.startswith(".") and os.path.isdir(full) \
                    and ckpt_commit.read_manifest(full) is not None:
                out.append(name)
        return out

    # -- load -------------------------------------------------------------
    def lookup(self, name, dirname, digest, donate_argnums=()):
        """A callable runner for the entry, or None (miss). Never raises:
        torn/corrupt/version-skewed/undeserializable entries are deleted
        and reported as misses. `donate_argnums` is the function's own
        (already in `digest`): a serialized executable carries its input
        aliasing, an exported lowering does not and is re-jitted with
        it, so no tier hands back a runner that copies what the compile
        run updated in place."""
        full = self._entry_dir(dirname)
        if not os.path.isdir(full):
            self._miss()
            return None
        try:
            manifest = ckpt_commit.verify_dir(full)
        except ckpt_commit.CheckpointCorruptError as e:
            warnings.warn(f"compile cache entry {dirname} failed "
                          f"verification ({e}); recompiling")
            shutil.rmtree(full, ignore_errors=True)
            self.stats["corrupt"] += 1
            self._miss()
            return None
        if float(manifest.get("ts", 0.0)) < self._min_ts:
            self.stats["bypass"] += 1
            self._miss()
            return None
        try:
            meta = self._read_meta(full, digest)
            t0 = time.perf_counter()
            runner = self._load_runner(full, meta, donate_argnums)
            _M_LOAD_S.labels(executable=name).observe(
                time.perf_counter() - t0)
        except Exception as e:                               # noqa: BLE001
            # wrong jax build, pickle rot, backend mismatch, ...: the
            # entry is useless here — drop it and recompile
            warnings.warn(f"compile cache entry {dirname} failed to load "
                          f"({type(e).__name__}: {str(e)[:200]}); "
                          f"recompiling")
            shutil.rmtree(full, ignore_errors=True)
            self.stats["corrupt"] += 1
            self._miss()
            return None
        self.stats["hits"] += 1
        _M_HITS.inc()
        try:
            os.utime(full)        # LRU recency: a served entry is "used"
        except OSError:
            pass
        return runner

    def _read_meta(self, full, digest):
        with open(os.path.join(full, ENTRY_META)) as f:
            meta = json.load(f)
        # defense in depth: the digest already covers all of these, but a
        # hand-copied or hash-colliding entry must still be rejected
        import jax
        if meta.get("schema") != ENTRY_SCHEMA:
            raise ValueError(f"entry schema {meta.get('schema')!r}")
        if meta.get("digest") != digest:
            raise ValueError("entry digest mismatch")
        if meta.get("jax_version") != jax.__version__:
            raise ValueError(
                f"jax version skew: entry {meta.get('jax_version')} vs "
                f"runtime {jax.__version__}")
        if meta.get("backend") != jax.default_backend():
            raise ValueError(f"backend skew: entry {meta.get('backend')}")
        if meta.get("fingerprint") != framework_fingerprint():
            raise ValueError("framework source fingerprint skew")
        return meta

    def _load_runner(self, full, meta, donate_argnums=()):
        if meta["format"] == "executable":
            from jax.experimental import serialize_executable as _se
            import jax
            with open(os.path.join(full, EXEC_FILE), "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            # load onto the devices the executable was compiled for: the
            # default (every device of the backend) breaks a one-device
            # executable on any multi-device host
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in meta["device_ids"]]
            return _se.deserialize_and_load(payload, in_tree, out_tree,
                                            execution_devices=devices)
        if meta["format"] == "exported":
            import jax
            from jax import export as _jexport
            with open(os.path.join(full, EXPORT_FILE), "rb") as f:
                exported = _jexport.deserialize(f.read())
            # compile-at-load tier: the python trace is skipped, the XLA
            # compile happens on the first call of this jit. jax.export
            # keeps no donation, so the entry's own is put back here
            return jax.jit(exported.call, donate_argnums=donate_argnums)
        raise ValueError(f"unknown entry format {meta['format']!r}")

    def _miss(self):
        self.stats["misses"] += 1
        _M_MISSES.inc()

    # -- store ------------------------------------------------------------
    def store(self, name, dirname, digest, compiled, export_fn,
              compile_seconds, extra_meta=None):
        """Commit a freshly compiled executable. Tries the serialized-
        executable tier first, falls back to the exported lowering
        (`export_fn()` -> bytes|None, invoked only when needed), and
        returns False (uncacheable, transparent miss) when neither
        round-trips or the commit itself fails — a failed store must
        never take the serving path down with it."""
        import jax
        payload = None
        fmt = None
        device_ids = None
        try:
            from jax.experimental import serialize_executable as _se
            payload = pickle.dumps(_se.serialize(compiled))
            fmt = "executable"
            # the same private handle serialize() reads: the ordered
            # device assignment the executable must be reloaded onto
            device_ids = [int(d.id) for d in
                          compiled._executable._unloaded_executable
                          .device_list]
        except Exception as e:                               # noqa: BLE001
            exported_bytes = export_fn() if export_fn is not None else None
            if exported_bytes is not None:
                payload, fmt = exported_bytes, "exported"
            else:
                warnings.warn(
                    f"compile cache: {name} is uncacheable "
                    f"({type(e).__name__}: {str(e)[:200]})")
                self.stats["uncacheable"] += 1
                return False
        meta = {"schema": ENTRY_SCHEMA, "name": name, "digest": digest,
                "format": fmt, "jax_version": jax.__version__,
                "backend": jax.default_backend(),
                "fingerprint": framework_fingerprint(),
                "compile_seconds": compile_seconds,
                "device_ids": device_ids,
                **(extra_meta or {})}
        final = self._entry_dir(dirname)
        try:
            with ckpt_commit.atomic_commit(final) as tmp:
                with open(os.path.join(tmp, ENTRY_META), "w") as f:
                    json.dump(meta, f, indent=1)
                fname = EXEC_FILE if fmt == "executable" else EXPORT_FILE
                with open(os.path.join(tmp, fname), "wb") as f:
                    f.write(payload)
        except Exception as e:                               # noqa: BLE001
            # injected truncate / full disk / ...: the atomic protocol
            # guarantees nothing half-written is visible; serving carries
            # on with the in-memory executable
            warnings.warn(f"compile cache commit of {name} failed "
                          f"({type(e).__name__}: {str(e)[:200]}); entry "
                          f"not persisted")
            self.stats["uncacheable"] += 1
            return False
        self._sweep_retention(protect=dirname)
        return True

    def _sweep_retention(self, protect=None):
        """Retention cap (ROADMAP item 5 debt): keep at most
        `max_entries` committed entries, evicting least-recently-used
        first (dir mtime — refreshed by both commits and lookup hits),
        at commit time like `ckpt_commit.gc_old`. The entry just
        committed is always protected, so the cap can never evict the
        executable the caller is about to run. 0 = unlimited."""
        if self.max_entries <= 0:
            return
        aged = []
        for name in self.entries():
            if name == protect:
                continue
            try:
                aged.append((os.path.getmtime(self._entry_dir(name)), name))
            except OSError:
                continue
        excess = len(aged) + (1 if protect else 0) - self.max_entries
        if excess <= 0:
            return
        aged.sort()
        for _, name in aged[:excess]:
            shutil.rmtree(self._entry_dir(name), ignore_errors=True)
            self.stats["evicted"] += 1


# ---------------------------------------------------- process-global tier

_ACTIVE = None


def attach(path):
    """Attach (or re-point) the process-global persistent cache — the
    tier the device-layer op runners use. Serving engines may instead
    carry a private cache via EngineConfig(compile_cache_dir=...)."""
    global _ACTIVE
    _ACTIVE = CompileCache(path)
    return _ACTIVE


def detach():
    global _ACTIVE
    _ACTIVE = None


def active():
    return _ACTIVE


def invalidate_active():
    """`device.clear_op_cache()`'s persistent-tier hook (no-op when no
    cache is attached)."""
    if _ACTIVE is not None:
        _ACTIVE.invalidate()


# ------------------------------------------------------- cached functions

_JAX_CACHE_HITS = [0]
_LISTENING = False
# entry dirnames whose executable jax's own cache served in this process
# (a later compile of the same program reuses it in memory, with no event)
_JAX_SERVED = set()


def _jax_cache_hits():
    """How many compiles jax's OWN persistent cache has served in this
    process (its monitoring event; the listener is installed on first
    use)."""
    global _LISTENING
    if not _LISTENING:
        import jax.monitoring

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                _JAX_CACHE_HITS[0] += 1
        jax.monitoring.register_event_listener(on_event)
        _LISTENING = True
    return _JAX_CACHE_HITS[0]



class CachedFunction:
    """`jax.jit` plus the persistent executable tier.

    With no cache resolvable the call IS `jax.jit(fn)(*args)` — same
    tracing, same executables, same trace-counter semantics. With a
    cache, each new input-aval signature goes through load-or-compile
    once and the resulting executable is called directly from then on.

    key_mode "signature" never traces on a warm hit (the serving
    contract); "lowering" traces to hash the StableHLO text (the eager
    op-runner contract — content-addressed, compile-skipping).
    `warm(*args)` runs load-or-compile WITHOUT executing — the AOT
    warmup entry point.
    """

    def __init__(self, fn, name, static_sig=None, key_mode="signature",
                 cache=None, donate_argnums=()):
        if key_mode not in ("signature", "lowering"):
            raise ValueError(f"key_mode {key_mode!r}")
        self._fn = fn
        self.name = name
        self._static_sig = static_sig
        self._key_mode = key_mode
        self._cache = cache          # CompileCache | callable | None
        self._donate = tuple(donate_argnums)
        import jax
        self._jit = jax.jit(fn, donate_argnums=donate_argnums) \
            if donate_argnums else jax.jit(fn)
        self._runners = {}           # aval sig -> executable
        self._sole_runner = None     # fast path while only one sig seen

    def _resolve_cache(self):
        c = self._cache
        if callable(c):
            c = c()
        return c if c is not None else _ACTIVE

    def __call__(self, *args):
        cache = self._resolve_cache()
        if cache is None:
            return self._jit(*args)
        # hot-path shortcut: serving executables see exactly one aval
        # signature for their lifetime, so skip the per-call signature
        # walk and let the executable's own aval check catch a mismatch
        # (a compiled runner raises TypeError on differing arg types —
        # probed for both fresh and deserialized executables)
        if self._sole_runner is not None:
            try:
                return self._sole_runner(*args)
            except TypeError:
                pass                 # new signature: take the full path
        sig = aval_signature(args)
        runner = self._runners.get(sig)
        if runner is None:
            runner = self._load_or_compile(cache, sig, args)
        return runner(*args)

    def warm(self, *args):
        """AOT-precompile for these example args (lower/trace only — the
        function is never executed). Returns "hit", "miss", or "off"."""
        cache = self._resolve_cache()
        if cache is None:
            return "off"
        sig = aval_signature(args)
        if sig in self._runners:
            return "hit"
        before = cache.stats["hits"]
        self._load_or_compile(cache, sig, args)
        return "hit" if cache.stats["hits"] > before else "miss"

    def _load_or_compile(self, cache, sig, args):
        lowered = None
        if self._key_mode == "lowering":
            lowered = self._jit.lower(*args)
            # the module header carries the python function's NAME
            # (`module @jit_f` vs `module @jit__lambda_`); content
            # addressing must not care what the op was called
            text = lowered.as_text()
            head, _, rest = text.partition("\n")
            if head.startswith("module @"):
                text = "module @m " + head.split(" ", 2)[-1] + "\n" + rest
            parts = ("lowering",
                     hashlib.sha256(text.encode()).hexdigest())
        else:
            parts = ("signature", self.name, repr(self._static_sig),
                     sig, self._donate)
        dirname, digest = cache.entry_key(self.name, parts)
        runner = cache.lookup(self.name, dirname, digest, self._donate)
        if runner is None:
            if lowered is None:
                lowered = self._jit.lower(*args)
            hits0 = _jax_cache_hits()
            t0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            _M_COMPILE_S.labels(executable=self.name).observe(compile_s)
            if _jax_cache_hits() > hits0:
                _JAX_SERVED.add(dirname)
            if dirname in _JAX_SERVED:
                # jax's own persistent cache served this compile (same
                # program, new key: e.g. the source fingerprint moved).
                # An executable jax LOADED does not re-serialize into a
                # loadable payload (XLA:CPU: "Function ... not found" at
                # the next process's first call), and jax already holds
                # it — so it is not stored a second time, here.
                cache.stats["uncacheable"] += 1
            else:
                cache.store(self.name, dirname, digest, compiled,
                            lambda: self._export_bytes(args), compile_s,
                            extra_meta={"key_mode": self._key_mode})
            runner = compiled
        self._runners[sig] = runner
        self._sole_runner = runner if len(self._runners) == 1 else None
        return runner

    def _export_bytes(self, args):
        """The exported-lowering fallback payload, or None when this
        function does not export (e.g. extended-dtype PRNG key inputs on
        some jax versions) — then only the serialized-executable tier
        can persist it."""
        try:
            from jax import export as _jexport
            return _jexport.export(self._jit)(*args).serialize()
        except Exception:                                    # noqa: BLE001
            return None


def cached_jit(fn, name, static_sig=None, key_mode="signature", cache=None,
               donate_argnums=()):
    """The drop-in `jax.jit` replacement for persistent-cache call sites
    (serving executables, device op runners). See CachedFunction."""
    return CachedFunction(fn, name, static_sig=static_sig,
                          key_mode=key_mode, cache=cache,
                          donate_argnums=donate_argnums)
