"""ISSUE 19: the numerics health plane.

Three layers under test:

  1. the in-trace sentinel vocabulary (stats vectors, sink scopes,
     per-layer taps) and its host-side twins;
  2. the online detector (nonfinite/saturation/drift latching, rolling
     healthy-only baselines) + the bisection localizer;
  3. the arming contract across every engine kind: taps DISABLED is
     bit-identical (token streams AND trace counts) to the pre-ISSUE
     engine, taps ENABLED still compiles once and emits the same
     tokens — plus the chaos drill: a NaN planted in one decode
     tensor is latched, bisection-localized to the guilty layer, and
     bundled within ONE engine step.

Satellites ride along: host-tier requant saturation, the kvledger
`sat` field + serve_report residency join, metrics_report gating,
optimizer-side taps.
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.observability import faults, numerics
from paddle_tpu.serving import (GenerationEngine, PagedGenerationEngine,
                                SpeculativeEngine)
from paddle_tpu.text.models.gpt import gpt_tiny

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
import metrics_report  # noqa: E402
import serve_report  # noqa: E402

PROMPT = np.arange(1, 9, dtype=np.int32)


@pytest.fixture(scope="module")
def tiny():
    m = gpt_tiny()
    m.eval()
    return m


# ------------------------------------------------------------- stats math

def test_stats_vector_masks_nonfinite():
    import jax
    import jax.numpy as jnp
    x = jnp.asarray([1.0, -3.0, jnp.nan, 2.0])
    vec = np.asarray(jax.jit(numerics.stats_vector)(x))
    ff, absmax, rms, sat = (float(v) for v in vec)
    assert ff == pytest.approx(0.75)
    # the NaN is masked OUT of the magnitude channels
    assert absmax == pytest.approx(3.0)
    assert rms == pytest.approx(math.sqrt((1 + 9 + 0 + 4) / 4))
    assert sat == 0.0
    # host-side twin agrees with the traced vector
    np.testing.assert_allclose(
        numerics.np_stats(np.asarray([1.0, -3.0, np.nan, 2.0],
                                     np.float32)),
        vec, rtol=1e-6)


def test_stats_vector_saturation_threshold():
    codes = np.asarray([127, -127, 3, 0], np.int8)
    vec = numerics.np_stats(codes, sat_threshold=127)
    assert vec[0] == 1.0
    assert vec[3] == pytest.approx(0.5)
    assert numerics.stats_unhealthy(vec, sat_frac_max=0.25)
    assert not numerics.stats_unhealthy(
        numerics.np_stats(np.asarray([1.0, 2.0], np.float32)))


def test_tree_stats_fuse_leaves():
    a = np.ones((2, 3), np.float32)
    b = np.full((6,), 2.0, np.float32)
    ff, absmax, rms, _ = numerics.np_tree_stats([a, b])
    assert ff == 1.0
    assert absmax == 2.0
    assert rms == pytest.approx(math.sqrt((6 * 1 + 6 * 4) / 12))


def test_tap_is_noop_without_sink():
    # the bit-identical-when-disabled contract at its root: no ambient
    # sink means tap() never touches jax at all
    numerics.tap("anywhere", object())
    with numerics.sink_scope() as sink:
        numerics.tap("site", np.ones(3, np.float32))
    assert "site" in sink
    # layer taps stay dormant without a layer filter, even armed
    with numerics.sink_scope() as sink:
        numerics.tap_layer(0, "act", np.ones(3, np.float32))
    assert not sink
    with numerics.sink_scope(layers=(1,)) as sink:
        numerics.tap_layer(0, "act", np.ones(3, np.float32))
        numerics.tap_layer(1, "act", np.ones(3, np.float32))
    assert list(sink) == ["layer1.act"]


# --------------------------------------------------------------- detector

def test_monitor_latches_three_kinds(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_POSTMORTEM_DIR", str(tmp_path))
    mon = numerics.NumericsMonitor(min_history=3, auto_bundle=True)
    for _ in range(4):
        assert mon.observe("s", [1.0, 2.0, 1.0, 0.0]) == []
    assert mon.observe("s", [0.5, 2.0, 1.0, 0.0]) == ["nonfinite"]
    assert mon.observe("s", [1.0, 2.0, 1.0, 0.9]) == ["saturation"]
    assert mon.observe("s", [1.0, 2.0, 100.0, 0.0]) == ["drift"]
    assert mon.total() == 3
    assert set(mon.counts()) == {"s:nonfinite", "s:saturation", "s:drift"}
    # auto_bundle dumped ONE postmortem, on the FIRST anomaly
    assert mon.bundle_path and os.path.exists(mon.bundle_path)


def test_monitor_baseline_extends_only_on_healthy():
    mon = numerics.NumericsMonitor(min_history=3, auto_bundle=False)
    for _ in range(3):
        mon.observe("s", [1.0, 2.0, 1.0, 0.0])
    # the drifted value latches and must NOT teach the baseline
    assert mon.observe("s", [1.0, 2.0, 50.0, 0.0]) == ["drift"]
    assert mon.observe("s", [1.0, 2.0, 50.0, 0.0]) == ["drift"]
    # the healthy value is still healthy against the unmoved baseline
    assert mon.observe("s", [1.0, 2.0, 1.0, 0.0]) == []


def test_bisect_first_unhealthy():
    assert numerics.bisect_first_unhealthy(8, lambda k: k >= 3) == 3
    assert numerics.bisect_first_unhealthy(8, lambda k: True) == 0
    assert numerics.bisect_first_unhealthy(8, lambda k: False) is None
    assert numerics.bisect_first_unhealthy(0, lambda k: True) is None
    # O(log n): count probe evaluations
    calls = []
    numerics.bisect_first_unhealthy(
        1024, lambda k: (calls.append(k), k >= 700)[1])
    assert len(calls) <= 12


# ----------------------------------------------- arming across engine kinds

def _build(kind, model, taps):
    if kind == "dense":
        return GenerationEngine(model, slots=2, max_len=64,
                                numerics_taps=taps)
    if kind == "paged":
        return PagedGenerationEngine(model, slots=2, max_len=64,
                                     block_size=8, numerics_taps=taps)
    if kind == "spec":
        return SpeculativeEngine(model, slots=2, max_len=64, block_size=8,
                                 gamma=2, numerics_taps=taps)
    if kind == "tp":
        from paddle_tpu.serving.distributed.tp import (
            TensorParallelPagedEngine)
        return TensorParallelPagedEngine(model, tp=2, slots=2, max_len=64,
                                         block_size=8, numerics_taps=taps)
    if kind == "pp":
        from paddle_tpu.serving.distributed.pp import (
            PipelineParallelPagedEngine)
        return PipelineParallelPagedEngine(model, pp=2, slots=2, max_len=64,
                                           block_size=8, numerics_taps=taps)
    from paddle_tpu.serving.distributed.pp import (
        PipelineParallelSpeculativeEngine)
    return PipelineParallelSpeculativeEngine(
        model, pp=2, slots=2, max_len=64, block_size=8, gamma=2,
        numerics_taps=taps)


def _drive(eng, kind):
    if kind in ("spec", "spec_pp"):
        eng.prefill(0, PROMPT)
        out = []
        for _ in range(3):
            toks, n = eng.decode_many()
            out.extend(int(x) for x in toks[0, :int(n[0])])
        return out
    out = [eng.prefill(0, PROMPT)]
    for _ in range(3):
        out.append(int(eng.decode()[0]))
    return out


@pytest.mark.parametrize("kind", ["dense", "paged", "spec",
                                  "tp", "pp", "spec_pp"])
def test_taps_disabled_bit_identical_enabled_compiles_once(kind, tiny):
    """THE arming contract, per engine kind: disabled taps are the
    pre-ISSUE program (same tokens, same trace counts); enabled taps
    emit the SAME tokens from a program still compiled once, with the
    sink ingested into the engine monitor (zero anomalies healthy)."""
    off = _build(kind, tiny, False)
    toks_off = _drive(off, kind)
    assert off.numerics_monitor is None
    on = _build(kind, tiny, True)
    toks_on = _drive(on, kind)
    assert toks_on == toks_off
    assert on.trace_counts == off.trace_counts
    assert on.numerics_monitor.total() == 0
    assert on.last_numerics, "armed engine ingested no sink"
    for site, st in on.last_numerics.items():
        assert st["finite_frac"] == 1.0, (site, st)


def test_paged_int8_taps_cover_quant_surfaces(tiny):
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                kv_dtype="int8", weight_dtype="int8",
                                numerics_taps=True)
    eng.prefill(0, PROMPT)
    eng.decode()
    sites = set(eng.last_numerics)
    assert {"decode.logits", "kv.codes", "kv.scale",
            "weights.q", "weights.scale"} <= sites
    assert eng.numerics_monitor.total() == 0
    assert eng.trace_counts["decode"] == 1


# ----------------------------------------------------------------- chaos

def test_chaos_nan_detected_localized_bundled_one_step(tiny, tmp_path,
                                                       monkeypatch):
    """The acceptance drill: numerics.corrupt plants a NaN in layer 1's
    ln weight; ONE decode step later the anomaly is latched, the
    bisection localizer names layer 1, and the postmortem bundle is on
    disk — with the probe traces counted under numerics_probe, never
    decode."""
    monkeypatch.setenv("PADDLE_TPU_POSTMORTEM_DIR", str(tmp_path))
    assert "numerics.corrupt" in faults.SITES
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                numerics_taps=True)
    eng.prefill(0, PROMPT)
    faults.arm("numerics.corrupt", mode="nan", nth=1, max_fires=1,
               target="blocks.1.ln1.weight")
    try:
        eng.decode()
    finally:
        faults.disarm_all()
    mon = eng.numerics_monitor
    assert mon.counts().get("decode.logits:nonfinite", 0) >= 1, mon.counts()
    loc = eng.last_localization
    assert loc is not None
    assert loc["first_unhealthy_layer"] == 1
    assert loc["site"] == "layer1.act"
    assert loc["stats"]["finite_frac"] < 1.0
    assert loc["layers"] == tiny.cfg.num_layers
    assert mon.bundle_path and os.path.exists(mon.bundle_path)
    with open(mon.bundle_path) as f:
        bundle = json.load(f)
    assert "numerics" in json.dumps(bundle)
    # compile discipline: the step executable never retraced; probes
    # have their own counter
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["numerics_probe"] >= 1
    # the prefill/master params were never poisoned (dict-copy contract)
    mon2 = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                 numerics_taps=True)
    mon2.prefill(0, PROMPT)
    mon2.decode()
    assert mon2.numerics_monitor.total() == 0

    # ... and metrics_report --compare names the latched counter (rc=1)
    def snap(anoms):
        return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
                "metrics": [{
                    "name": "numerics_anomaly_total", "type": "counter",
                    "help": "", "labelnames": ["site", "kind"],
                    "samples": [{"labels": {"site": "decode.logits",
                                            "kind": "nonfinite"},
                                 "value": anoms}]}]}
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, rec in ((pa, snap(0)), (pb, snap(mon.total()))):
        with open(path, "w") as f:
            f.write(json.dumps(rec) + "\n")
    cli = [sys.executable, os.path.join(_ROOT, "tools",
                                        "metrics_report.py")]
    bad = subprocess.run(cli + ["--compare", pa, pb],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "numerics_anomaly_total" in bad.stdout


def test_chaos_scale_zero_drifts_weight_scales(tiny):
    """scale_zero zeroes an int8 weight entry's scale: nothing goes
    non-finite, but the weights.scale rms collapses and the drift rule
    latches against the rolling baseline built on healthy steps."""
    eng = PagedGenerationEngine(tiny, slots=2, max_len=64, block_size=8,
                                kv_dtype="int8", weight_dtype="int8",
                                numerics_taps=True)
    eng.prefill(0, PROMPT)
    n_healthy = eng.numerics_monitor.min_history + 1
    for _ in range(n_healthy):
        eng.decode()
    assert eng.numerics_monitor.total() == 0
    faults.arm("numerics.corrupt", mode="scale_zero", nth=1, max_fires=1,
               target="blocks.0.mlp.fc1.weight")
    try:
        eng.decode()
    finally:
        faults.disarm_all()
    kinds = eng.numerics_monitor.counts()
    assert kinds.get("weights.scale:drift", 0) >= 1, kinds
    assert eng.trace_counts["decode"] == 1


def test_corrupt_spec_parses_target_from_env():
    specs = faults.load_env(
        "numerics.corrupt=nan:nth=2:max=1:target=blocks.0.attn.weight")
    try:
        assert len(specs) == 1
        assert specs[0].mode == "nan"
        assert specs[0].target == "blocks.0.attn.weight"
        assert specs[0].nth == 2
        # nan is caller-interpreted: fire() returns the spec, raises
        # nothing
        assert faults.fire("numerics.corrupt") is None   # nth=2: not yet
        assert faults.fire("numerics.corrupt") is specs[0]
    finally:
        faults.disarm_all()


# ------------------------------------------------------ host-tier requant

def test_host_tier_records_requant_saturation():
    from paddle_tpu.serving.kv_tiers.host import HostTier
    tier = HostTier(8, dtype="int8")
    blk = {"ns": None, "parent": None, "quant": False,
           "arrays": {"k0": np.ones((8, 2, 4), np.float32)}}
    tier.put("a", blk)
    # constant input: every code lands exactly on the ±127 rail
    assert tier.last_put_saturation == pytest.approx(1.0)
    ramp = np.linspace(0.01, 1.0, 8 * 2 * 4, dtype=np.float32)
    tier.put("b", {"ns": None, "parent": None, "quant": False,
                   "arrays": {"k0": ramp.reshape(8, 2, 4)}})
    assert tier.last_put_saturation < 0.5
    st = tier.saturation_stats()
    assert st["samples"] == 2
    assert st["max"] == pytest.approx(1.0)
    assert 0.0 < st["mean"] <= 1.0
    # float32 tier never requantizes: no saturation sample
    f32 = HostTier(8, dtype="float32")
    f32.put("a", blk)
    assert f32.last_put_saturation is None
    assert f32.saturation_stats()["samples"] == 0


def test_host_tier_feeds_process_monitor():
    from paddle_tpu.serving.kv_tiers.host import HostTier
    mon = numerics.NumericsMonitor(sat_frac_max=0.25, auto_bundle=False)
    prev = numerics.set_monitor(mon)
    try:
        tier = HostTier(8, dtype="int8")
        tier.put("a", {"ns": None, "parent": None, "quant": False,
                       "arrays": {"k0": np.ones((8, 2, 4), np.float32)}})
    finally:
        numerics.set_monitor(prev)
    assert mon.counts().get("kv_tier.requant_codes:saturation", 0) >= 1


def test_ledger_demote_carries_sat_and_serve_report_joins(tmp_path):
    from paddle_tpu.observability.kvledger import KVLedger
    led = KVLedger(num_blocks=4)
    led.tier_demote((1,), "key1", "host", "default", sat=0.5)
    led.tier_demote((2,), "key2", "host", "default", sat=0.3)
    led.tier_demote((), "key3", "disk", "default")     # no sat: f32 path
    evs = [e for e in led.events if e["event"] == "tier_demote"]
    assert evs[0]["sat"] == pytest.approx(0.5)
    assert "sat" not in evs[2]
    # the serving-JSONL records validate with the new optional field...
    recs = [dict(e, kind="kvledger",
                 schema=serve_report.KVLEDGER_SCHEMA,
                 request_id=None, tenant="default", origin=None)
            for e in evs]
    assert serve_report.validate_records(recs) == []
    # ...and the residency join summarizes per-tier requant saturation
    res = serve_report.kv_residency(recs)
    host = res["tiers"]["host"]
    assert host["requant_sat"]["samples"] == 2
    assert host["requant_sat"]["mean"] == pytest.approx(0.4)
    assert host["requant_sat"]["max"] == pytest.approx(0.5)
    assert res["tiers"]["disk"]["requant_sat"] is None


def test_store_stats_surface_requant_saturation():
    from paddle_tpu.serving.kv_tiers.host import HostTier
    from paddle_tpu.serving.kv_tiers.store import TieredBlockStore
    store = TieredBlockStore.__new__(TieredBlockStore)
    store.host = HostTier(8, dtype="int8")
    store.disk = None
    store.host.put("a", {"ns": None, "parent": None, "quant": False,
                         "arrays": {"k0": np.ones((8, 2, 4), np.float32)}})
    st = store.stats()
    assert st["host_requant_saturation"]["samples"] == 1
    assert st["host_requant_saturation"]["max"] == pytest.approx(1.0)


# -------------------------------------------------------- metrics gating

def test_metrics_compare_gates_finite_frac_drop(tmp_path):
    def snap(ff):
        return {"schema": metrics_report.SCHEMA, "ts": 1.0, "pid": 1,
                "metrics": [{
                    "name": "numerics_site_finite_frac", "type": "gauge",
                    "help": "", "labelnames": ["site"],
                    "samples": [{"labels": {"site": "decode.logits"},
                                 "value": ff}]}]}
    regs = metrics_report.compare_counters(snap(1.0), snap(0.5))
    why = {k: w for k, *_, w in regs}
    assert any("finite fraction dropped" in w for w in why.values()), regs
    # identical runs stay clean
    assert metrics_report.compare_counters(snap(1.0), snap(1.0)) == []


# ---------------------------------------------------------- optimizer taps

def test_functional_update_taps_in_trace():
    import jax
    import jax.numpy as jnp
    o = opt.SGD(learning_rate=0.1)
    params = {"w": jnp.ones((4,)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.full((4,), 0.5), "b": jnp.ones((2,))}
    state = o.functional_state(params)

    def step(p, g, s):
        with numerics.sink_scope() as sink:
            new_p, new_s = o.apply_gradients_functional(p, g, s)
        return new_p, new_s, sink

    new_p, _, sink = jax.jit(step)(params, grads, state)
    assert set(sink) == {"train.grad_norm", "train.param_norm"}
    gstats = numerics.stats_dict(np.asarray(sink["train.grad_norm"]))
    assert gstats["finite_frac"] == 1.0
    assert gstats["absmax"] == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.full(4, 0.95),
                               rtol=1e-6)
    # disarmed: same update, no sink, no extra outputs
    p2, _ = o.apply_gradients_functional(params, grads, state)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(new_p["w"]))


def test_eager_step_observes_into_process_monitor():
    mon = numerics.NumericsMonitor(auto_bundle=False)
    prev = numerics.set_monitor(mon)
    try:
        p = paddle.to_tensor(np.array([1.0, 2.0], np.float32),
                             stop_gradient=False)
        o = opt.SGD(learning_rate=0.1, parameters=[p])
        (p * p).sum().backward()
        o.step()
        assert mon.total() == 0
        assert {"train.grad_norm", "train.param_norm"} <= \
            set(mon.site_stats())
        # a NaN grad is latched by the same observation point
        p.clear_grad()
        (p * float("nan")).sum().backward()
        o.step()
        assert mon.counts().get("train.grad_norm:nonfinite", 0) >= 1
    finally:
        numerics.set_monitor(prev)
