"""Render / validate / compare unified-registry metrics snapshots.

The metrics registry (paddle_tpu.observability.metrics) writes
schema-versioned JSONL snapshots (`paddle_tpu.metrics.v1`) and Prometheus
text dumps (`registry().write_snapshot(path)`, `dump_prometheus()`).
This tool is the offline half: it renders a snapshot as a table, schema-
validates files (the CI guard in tests/test_perf_pipeline.py), and diffs
two runs with a REGRESSION mode for CI:

  python tools/metrics_report.py RUN/metrics.jsonl
  python tools/metrics_report.py --compare A.jsonl B.jsonl \
         [--max-regress-pct 25]

`--compare` exits nonzero when a counter regressed by more than the
threshold. Direction matters and is decided per counter name:

  - FAILURE counters (name matches error|reject|timeout|miss|drop|
    failure|retr(y|ies)|fault|breaker|failover): regression = the count
    GREW past the threshold — `ps_retries_total` and friends are
    failure-CLASS evidence (each one is a transport fault the fabric
    absorbed), so a run that suddenly retries more is a regression even
    when it still converges. `serving_failover_total` (requests re-routed
    off a dead serving host) and `serving_swap_dropped_requests_total`
    (requests a weight hot-swap failed — must stay 0) join the class
    for the multi-host serving tier (ISSUE 10),
  - all other counters (work done: tokens, requests, bytes, hits):
    regression = the count SHRANK past the threshold,
  - rate pairs (X_hits/X_misses incl. the persistent compile cache,
    spec accepted/proposed): the RATIO dropping past the threshold is
    failure-class even when the numerator grew with traffic,
  - gap gauges (`*cost_model_measured_vs_predicted`): a measured/
    analytically-predicted step-time ratio GROWING past the threshold
    is failure-class — the hardware regressed or the model lost contact,
  - quantized-serving quality gauges (ISSUE 11):
    `serving_quant_greedy_match` (token agreement vs the f32 oracle)
    DROPPING and `serving_quant_logit_kl` GROWING are failure-class —
    int8 serving that quietly stops matching its float oracle is a
    correctness regression, not a perf trade,
  - histogram tails (ISSUE 10): `serving_kv_handoff_seconds` approximate
    p99 (from the cumulative buckets) GROWING past the threshold is
    failure-class — a handoff-latency tail stalls decode admission even
    when every transfer still succeeds,
  - SLO watchdog gauges (ISSUE 12): `serving_slo_burn{slo,window}`
    GROWING past the threshold is failure-class, and both burn and
    `serving_slo_degraded` are additionally FLIP-gated — a burn rate
    crossing 1.0 (error budget consumed faster than allowed) or a
    degraded flip 0 -> 1 fires even from a zero baseline, where
    percentage rules are meaningless,
  - KV-ledger watchdog counters (ISSUE 16):
    `serving_kv_ledger_divergence_total{invariant=...}` joins the
    failure class (pattern `diverg`/`leak`) — the reconciler primes
    every invariant child at 0, so a single latched divergence in run B
    gates through the zero-baseline failure-counter rule even though
    run A never saw the series move,
  - multi-tenant serving counters (ISSUE 17):
    `serving_rate_limited_total{tenant}` (token-bucket denials) and
    `serving_prefix_ns_evicted_total{namespace}` (prefix blocks evicted
    out of a tenant namespace) join the failure class (patterns
    `rate_limited`/`evict`); both gate per labelset under the tenant
    membership-intersection rule — a newly onboarded tenant's counters
    never read as regressions, a shared tenant's growth fires on
    exactly the tenant that regressed,
  - KV memory hierarchy (ISSUE 18): `serving_kv_tier_corrupt_total`
    (restores that failed verification — every one degraded a chain to
    recompute) and `serving_kv_tier_drop_total{tier}` (tiered entries
    discarded) join the failure class (patterns `corrupt`/`drop`);
    `serving_kv_tier_{hits,misses}_total{tier}` gate as a per-tier
    HIT-RATE pair under the generic hits/misses rule (a rate drop fires
    even when hit counts grew with traffic); and the
    `serving_kv_restore_seconds` approximate p99 growing past the
    threshold is failure-class (cold-chain promotion losing its race
    against recompute),
  - numerics health plane (ISSUE 19): `numerics_anomaly_total{site,kind}`
    — latched by the sentinel monitor when a tapped tensor goes
    non-finite, drifts past its rolling-MAD baseline, or saturates its
    int8 code range — joins the failure class (pattern `anomal`), and a
    `numerics_site_finite_frac{site}` gauge dropping below run A is
    failure-class on its own (non-finite values entered a tapped tensor
    even if no counter latched in run A's window),
  - gray-failure plane (ISSUE 20): `serving_deadline_missed_total{where}`
    (requests shed past their deadline budget, router- or worker-side;
    the `miss` pattern grew a `missed` arm for it),
    `serving_migrations_total{reason=suspect}` (streams yanked off a
    gray worker — drain-reason migrations are deliberate and do NOT
    gate), and `serving_retry_budget_exhausted_total{worker}` (the
    token bucket refusing a retry — a retry STORM absorbed, matched by
    the existing `retr(y|ies)` arm) join the failure class; the hedging
    pair `serving_hedge_primary_total` / `serving_hedge_fired_total`
    gates as a RATE (primary/(primary+fired)) — the primary answering
    within the p99-derived delay less often means the fleet's readonly
    tail got slower even when every hedge still wins the race.

Fleet-merged snapshots (ISSUE 12, observability/fleet.py) are compared
LABEL-AWARE: every series already carries `worker_id`/`role` labels in
its comparison key, so per-worker series match per worker — and the
comparison first intersects the two snapshots' worker memberships,
skipping series of workers absent from either side (a decode host that
died mid-run B would otherwise read as every one of its work counters
"shrinking" to zero; its death is already gated through the failure-
class counters — failover, errors — that live on the surviving
members and the `_fleet` aggregate).

The TENANT dimension (ISSUE 15) rides the same membership machinery:
series are intersected on their `tenant` label values too, so a tenant
onboarded or offboarded between runs never reads as counters appearing
from / shrinking to zero, while per-tenant series of tenants live in
BOTH runs gate per labelset — `serving_shed_total{tenant=a}` growing,
`serving_slo_burn{slo=ttft,tenant=a,...}` growing or crossing 1.0 from
a clean baseline, and per-tenant acceptance/hit-rate drops all fire on
exactly the tenant that regressed (tenant `_all` — the unscoped SLO
rows — always participates).

Small-count noise is ignored via --min-delta (absolute floor, default 1).

Stdlib-only, no live backend needed — like tools/perf_report.py, the
artifacts must outlive the TPU grant that produced them.
"""
import argparse
import json
import os
import re
import sys

SCHEMA = "paddle_tpu.metrics.v1"
_TYPES = ("counter", "gauge", "histogram")
_FAIL_PAT = re.compile(
    r"error|reject|timeout|miss(?:es|ed)?(?:_|$)|drop|failure|retr(?:y|ies)"
    r"|fault|breaker|(?:^|_)shed(?:_|$)|preempt|failover|diverg|leak"
    r"|rate_limited|evict|corrupt|anomal"
    # ISSUE 20: suspect-reason migrations are streams yanked off a gray
    # worker (absorbed damage); drain-reason migrations are deliberate
    # rolling-restart traffic and stay out of the class
    r"|migrations_total\{[^}]*reason=suspect",
    re.I)

# counter pairs whose RATIO is the SLO signal: a rate drop past the
# threshold is a failure-class regression even when the numerator grew
# (e.g. more traffic, worse prefix sharing / draft acceptance). Each
# entry: (numerator regex, denominator suffix, denominator-includes-
# numerator?, rate name suffix).
#   hits/(hits+misses)    — prefix-cache style hit rate; the SAME rule
#                           covers compile_cache_{hits,misses}_total
#                           (the ISSUE 8 gate: a persistent-cache
#                           hit-rate drop means restarts started
#                           compiling again)
#   accepted/proposed     — spec-decode acceptance rate (the ISSUE 7
#                           gate: a rate drop means the draft rots or
#                           the verify rule broke, even under growth)
#   primary/(primary+fired) — hedged-call primary-win rate (the ISSUE 20
#                           gate: the primary answering inside the p99-
#                           derived hedge delay less often means the
#                           readonly tail got slower fleet-wide, even
#                           when every fired hedge still wins its race)
_RATE_RULES = (
    (re.compile(r"^(?P<base>.*_)hits_total(?P<labels>\{.*\})?$"),
     "misses_total", True, "hit_rate"),
    (re.compile(r"^(?P<base>.*_)accepted_total(?P<labels>\{.*\})?$"),
     "proposed_total", False, "acceptance_rate"),
    (re.compile(r"^(?P<base>.*_)hedge_primary_total(?P<labels>\{.*\})?$"),
     "hedge_fired_total", True, "hedge_primary_rate"),
)

# GAUGE rules: gauges whose GROWTH past the threshold is failure-class.
# *cost_model_measured_vs_predicted is the analytical-delta gate: a
# measured/predicted step-time ratio growing means the step got slower
# relative to what the roofline says the hardware can do.
_GAUGE_GROW_RULES = (
    (re.compile(r"cost_model_measured_vs_predicted(\{.*\})?$"),
     "measured/predicted gap widened"),
    # ISSUE 11: the quantized tier's logit divergence vs the f32 oracle
    # growing means the int8 path is drifting (scale corruption, requant
    # rot) even while tokens still mostly match
    (re.compile(r"serving_quant_logit_kl(\{.*\})?$"),
     "quantized logit KL vs f32 oracle grew"),
    # ISSUE 12: the online SLO watchdog's burn rate growing means the
    # fleet is eating its error budget faster than run A did
    (re.compile(r"serving_slo_burn(\{.*\})?$"),
     "SLO burn rate grew"),
    # ISSUE 13: the pipeline-serving tick schedule's idle fraction
    # growing means stages are stalling (schedule rot, microbatch
    # imbalance) — throughput decays even while every stream stays
    # token-exact
    (re.compile(r"serving_pp_bubble_fraction(\{.*\})?$"),
     "pipeline-serving bubble fraction grew"),
)

# FLIP rules (ISSUE 12): gauges judged against an ABSOLUTE line, not a
# percentage — the percentage rules skip zero baselines, but a burn
# gauge crossing 1.0 or a degraded gauge flipping 0 -> 1 is an incident
# precisely when run A sat at 0. Each entry: (pattern, threshold B must
# reach while A sat at/below zero, reason).
_GAUGE_FLIP_RULES = (
    (re.compile(r"serving_slo_degraded(\{.*\})?$"), 1e-9,
     "fleet flipped into sustained SLO breach"),
    (re.compile(r"serving_slo_burn(\{.*\})?$"), 1.0,
     "SLO burn rate crossed 1.0 from a clean baseline"),
)

# GAUGE rules: gauges whose DROP past the threshold is failure-class.
_GAUGE_DROP_RULES = (
    # ISSUE 11 quality gate: greedy-match rate vs the f32 oracle is THE
    # quantized-serving correctness headline — a drop past the threshold
    # is failure-class no matter how fast the int8 path got
    (re.compile(r"serving_quant_greedy_match(\{.*\})?$"),
     "quantized greedy-match rate vs f32 oracle dropped"),
    # ISSUE 19 numerics plane: a site's finite fraction dropping below
    # run A means non-finite values entered a tensor the sentinel taps —
    # failure-class even before any anomaly counter latches
    (re.compile(r"numerics_site_finite_frac(\{.*\})?$"),
     "tapped-site finite fraction dropped"),
)

# HISTOGRAM rules (ISSUE 10): histograms whose approximate p99 GROWING
# past the threshold is failure-class. serving_kv_handoff_seconds is the
# multi-host KV-handoff latency: its tail blowing up means prefill
# workers stall decode admission (TTFT regression) even when every
# handoff still succeeds, so the count/sum rules alone would miss it.
_HIST_P99_RULES = (
    (re.compile(r"serving_kv_handoff_seconds(\{.*\})?$"),
     "KV handoff p99 grew"),
    # ISSUE 18: the per-block tier-restore latency tail growing means
    # cold-chain promotion is losing its race against recompute — the
    # TTFT win the hierarchy exists for erodes even while every restore
    # still verifies
    (re.compile(r"serving_kv_restore_seconds(\{.*\})?$"),
     "KV tier restore p99 grew"),
)


_WORKER_LABEL = re.compile(r"worker_id=([^,}]+)")
_FLEET_LABEL = "_fleet"      # the fleet-aggregate member id (fleet.py)
_TENANT_LABEL = re.compile(r"[{,]tenant=([^,}]+)")
_ALL_TENANTS = "_all"        # tenant value of unscoped SLO gauges
# prefix-cache namespaces (ISSUE 17) are tenant trust boundaries — the
# same onboard/offboard churn argument applies to their label dimension
_NAMESPACE_LABEL = re.compile(r"[{,]namespace=([^,}]+)")


def _label_values(rec, labelname, drop=()):
    """Distinct values of one label across a snapshot's samples (empty
    when the dimension is absent — filtering then no-ops)."""
    out = set()
    for m in rec.get("metrics", []):
        for s in m.get("samples", []):
            v = (s.get("labels") or {}).get(labelname)
            if v:
                out.add(v)
    return out - set(drop)


def _dimension_filter(a_rec, b_rec, labelname, pat, always=()):
    """key -> bool over ONE label dimension: keep series whose label
    value appears in BOTH snapshots (plus the `always` sentinels —
    fleet aggregates, the _all-tenants SLO rows — and every series not
    carrying the label). The PR 12 per-worker membership-intersection
    rule, generalized so the tenant dimension (ISSUE 15) rides the same
    machinery: a tenant absent from one run (onboarded/offboarded
    between A and B) must not read as every one of its counters
    appearing or vanishing."""
    ma = _label_values(a_rec, labelname, drop=always)
    mb = _label_values(b_rec, labelname, drop=always)
    if not ma or not mb:
        return lambda key: True
    common = (ma & mb) | set(always)

    def keep(key):
        m = pat.search(key)
        return m is None or m.group(1) in common
    return keep


def _member_filter(a_rec, b_rec):
    """key -> bool: worker-membership AND tenant-membership
    intersection (see the module docstring's label-aware comparison
    rules)."""
    fw = _dimension_filter(a_rec, b_rec, "worker_id", _WORKER_LABEL,
                           always=(_FLEET_LABEL,))
    ft = _dimension_filter(a_rec, b_rec, "tenant", _TENANT_LABEL,
                           always=(_ALL_TENANTS,))
    fn = _dimension_filter(a_rec, b_rec, "namespace", _NAMESPACE_LABEL)
    return lambda key: fw(key) and ft(key) and fn(key)


def _approx_p99(buckets, count):
    """Upper edge of the bucket holding the 99th percentile — the
    standard Prometheus histogram_quantile approximation (cumulative
    counts, '+Inf' edge reads as infinity)."""
    want = 0.99 * count
    for edge in sorted((e for e in buckets if e != "+Inf"), key=float):
        if buckets[edge] >= want:
            return float(edge)
    return float("inf")


def _hist_p99s(rec):
    """{ 'name{labels}': approx p99 } for every histogram sample matching
    a _HIST_P99_RULES pattern, with its rule's reason."""
    out = {}
    for m in rec.get("metrics", []):
        if m["type"] != "histogram":
            continue
        for pat, why in _HIST_P99_RULES:
            if not pat.match(m["name"]):
                continue
            for s in m["samples"]:
                if not s.get("count"):
                    continue
                labels = s.get("labels") or {}
                key = m["name"] + ("{" + ",".join(
                    f"{k}={labels[k]}" for k in sorted(labels)) + "}"
                    if labels else "")
                out[key] = (_approx_p99(s["buckets"], s["count"]), why)
    return out


# ------------------------------------------------------------- validation

def validate_snapshot(rec):
    """Return a list of schema violations ([] == valid)."""
    errs = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not dict"]
    if rec.get("schema") != SCHEMA:
        errs.append(f"schema={rec.get('schema')!r}, want {SCHEMA!r}")
    for field, types in (("ts", (int, float)), ("pid", int),
                         ("metrics", list)):
        if not isinstance(rec.get(field), types):
            errs.append(f"{field}={rec.get(field)!r} invalid")
    for m in rec.get("metrics") or []:
        if not isinstance(m, dict):
            errs.append(f"metric row {m!r} not a dict")
            continue
        name = m.get("name")
        if not isinstance(name, str) or not name:
            errs.append(f"metric name {name!r} invalid")
        if m.get("type") not in _TYPES:
            errs.append(f"{name}: type={m.get('type')!r} invalid")
        if not isinstance(m.get("samples"), list):
            errs.append(f"{name}: samples missing")
            continue
        for s in m["samples"]:
            labels = s.get("labels")
            if not isinstance(labels, dict):
                errs.append(f"{name}: sample labels {labels!r} invalid")
            if m.get("type") == "histogram":
                missing = [k for k in ("buckets", "sum", "count")
                           if k not in s]
                if missing:
                    errs.append(f"{name}: histogram sample missing {missing}")
                    continue
                counts = list(s["buckets"].values())
                if counts != sorted(counts):
                    errs.append(f"{name}: buckets not cumulative")
                if "+Inf" not in s["buckets"]:
                    errs.append(f"{name}: no +Inf bucket")
                elif s["buckets"]["+Inf"] != s["count"]:
                    errs.append(f"{name}: +Inf bucket != count")
            else:
                if not isinstance(s.get("value"), (int, float)):
                    errs.append(f"{name}: value {s.get('value')!r} invalid")
                elif m.get("type") == "counter" and s["value"] < 0:
                    errs.append(f"{name}: negative counter {s['value']}")
    return errs


def load_snapshots(path):
    """Parse + validate a JSONL snapshot stream; ValueError on any invalid
    record."""
    records = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i + 1}: not JSON: {e}") from None
            errs = validate_snapshot(rec)
            if errs:
                raise ValueError(f"{path}:{i + 1}: " + "; ".join(errs))
            records.append(rec)
    if not records:
        raise ValueError(f"{path}: empty snapshot stream")
    return records


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+(?: [0-9.]+)?$")


def validate_prometheus(text):
    """Basic text-exposition lint: every line is a comment, blank, or a
    parseable sample; every sample's family has a # TYPE."""
    errs = []
    typed = set()
    for i, line in enumerate(text.splitlines()):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in _TYPES:
                errs.append(f"line {i + 1}: bad TYPE line {line!r}")
            else:
                typed.add(parts[2])
            continue
        if line.startswith("#"):
            continue
        if not _PROM_SAMPLE.match(line):
            errs.append(f"line {i + 1}: unparseable sample {line!r}")
            continue
        fam = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(bucket|sum|count)$", "", fam)
        if fam not in typed and base not in typed:
            errs.append(f"line {i + 1}: sample {fam!r} has no # TYPE")
    return errs


# -------------------------------------------------------------- rendering

def flatten(rec, kinds=("counter", "gauge")):
    """{ 'name{k=v}': value } for scalar metrics of one snapshot."""
    out = {}
    for m in rec.get("metrics", []):
        if m["type"] not in kinds:
            continue
        for s in m["samples"]:
            labels = s.get("labels") or {}
            key = m["name"]
            if labels:
                key += "{" + ",".join(f"{k}={labels[k]}"
                                      for k in sorted(labels)) + "}"
            out[key] = s["value"]
    return out


def _counter_keys(rec):
    return set(flatten(rec, kinds=("counter",)))


def _hist_rows(rec):
    rows = []
    for m in rec.get("metrics", []):
        if m["type"] != "histogram":
            continue
        for s in m["samples"]:
            if not s["count"]:
                continue
            labels = s.get("labels") or {}
            key = m["name"] + ("{" + ",".join(
                f"{k}={labels[k]}" for k in sorted(labels)) + "}"
                if labels else "")
            rows.append((key, s["count"], s["sum"] / s["count"]))
    return rows


def render(records, title="metrics report"):
    """Markdown table of the LAST snapshot (+ how many snapshots seen)."""
    last = records[-1]
    lines = [f"# {title}", "",
             f"snapshots: {len(records)}  ·  pid {last['pid']}  ·  "
             f"ts {last['ts']:.3f}"]
    flat = flatten(last)
    if flat:
        lines += ["", "## counters & gauges", "", "| metric | value |",
                  "|---|---|"]
        for k in sorted(flat):
            v = flat[k]
            lines.append(f"| {k} | {v:g} |")
    hist = _hist_rows(last)
    if hist:
        lines += ["", "## histograms", "",
                  "| metric | count | mean |", "|---|---|---|"]
        for key, count, mean in sorted(hist):
            lines.append(f"| {key} | {count} | {mean:.6g} |")
    return "\n".join(lines)


# ------------------------------------------------------------- comparison

def _hit_rates(flat):
    """{name: rate} for every rate-rule counter pair with at least one
    event (X_hits/X_misses hit rate, X_accepted/X_proposed acceptance
    rate). Labeled pairs pair PER LABELSET and keep the labels on the
    derived rate key (ISSUE 14: serving_spec_*_total{engine=spec_pp}
    gates separately from the single-device engine's series — one
    engine's draft rotting must not hide behind another's healthy
    rate)."""
    rates = {}
    agg = {}
    for key, num in flat.items():
        for pat, denom_suffix, denom_adds, rate_suffix in _RATE_RULES:
            m = pat.match(key)
            if not m:
                continue
            labels = m.group("labels") or ""
            denom_key = m.group("base") + denom_suffix + labels
            denom = flat.get(denom_key)
            if denom is None:
                continue
            total = num + denom if denom_adds else denom
            if total <= 0:
                continue
            rates[m.group("base") + rate_suffix + labels] = num / total
            # labeled pairs ALSO roll up into a family aggregate under
            # the BARE rate name, so a baseline recorded before a family
            # grew labels (unlabeled totals) still pairs and gates
            # against a labeled run across the upgrade boundary
            n0, t0 = agg.get(m.group("base") + rate_suffix, (0.0, 0.0))
            agg[m.group("base") + rate_suffix] = (n0 + num, t0 + total)
    for key, (n, t) in agg.items():
        rates.setdefault(key, n / t)
    return rates


def _schema_bridge(key, other_flat):
    """True when `key` and the OTHER snapshot express the same family
    under opposite label schemas — bare here vs labeled there, or
    labeled here vs bare there: the upgrade boundary of a family that
    grew labels between runs, where the per-key counter rules must not
    read the key mismatch as a counter appearing/vanishing. A LABELED
    key missing from a side that is itself labeled is NOT a schema
    change — it is a vanished member (e.g. an engine dropping out of
    the fleet) and must keep gating."""
    fam = key.split("{", 1)[0]
    if "{" in key:
        return fam in other_flat             # labeled here, bare there
    return any(k.startswith(fam + "{") for k in other_flat)


def compare_counters(a_rec, b_rec, max_regress_pct=25.0, min_delta=1.0):
    """[(key, a, b, pct, why)] counter regressions of B against A.

    Three regression classes: failure counters that grew (shed/preempt/
    retry/... — each one is absorbed damage), work counters that shrank,
    and hits/misses RATIOS that dropped (prefix-cache hit rate et al —
    the miss counter growing would fire the failure rule, but a rate
    comparison stays meaningful when B simply served more traffic)."""
    a, b = flatten(a_rec, ("counter",)), flatten(b_rec, ("counter",))
    keep = _member_filter(a_rec, b_rec)
    regressions = []
    for key in sorted(set(a) | set(b)):
        if not keep(key):
            continue                  # member absent from one side
        va, vb = a.get(key), b.get(key)
        # label-schema bridge (ISSUE 14): when one run writes a family
        # bare and the other labeled (the upgrade boundary — e.g. the
        # spec counters grew an engine label), the bare and labeled
        # keys are the SAME data. Labeled keys defer to the bare row,
        # and the bare row compares against the labeled side's family
        # SUM — so the volume rules (work shrank / failure grew) keep
        # gating across the boundary, next to the aggregate rate.
        if va is None and _schema_bridge(key, a):
            if "{" in key:
                continue              # covered by the bare-family row
            va = sum(v for k2, v in a.items()
                     if k2.startswith(key + "{"))
        if vb is None and _schema_bridge(key, b):
            if "{" in key:
                continue
            vb = sum(v for k2, v in b.items()
                     if k2.startswith(key + "{"))
        va, vb = va or 0.0, vb or 0.0
        delta = vb - va
        if abs(delta) < min_delta:
            continue
        pct = (delta / va * 100.0) if va else float("inf")
        if _FAIL_PAT.search(key):
            if delta > 0 and (va == 0 or pct > max_regress_pct):
                regressions.append((key, va, vb, pct,
                                    "failure counter grew"))
        else:
            if delta < 0 and -pct > max_regress_pct:
                regressions.append((key, va, vb, pct,
                                    "work counter shrank"))
    ra, rb = _hit_rates(a), _hit_rates(b)
    for key in sorted(set(ra) & set(rb)):
        if not keep(key):
            continue
        if "{" not in key \
                and any(k.startswith(key + "{") for k in ra) \
                and any(k.startswith(key + "{") for k in rb):
            # both runs carry per-labelset rates for this family: those
            # series gate. The bare family aggregate exists only to
            # bridge the pre-label schema boundary — gating it between
            # two labeled runs would flag a pure traffic-MIX shift as a
            # rate drop (Simpson's paradox) with no per-engine change
            continue
        va, vb = ra[key], rb[key]
        if va <= 0:
            continue
        pct = (vb - va) / va * 100.0
        if vb < va and -pct > max_regress_pct:
            regressions.append((key, va, vb, pct, "hit rate dropped"))
    ga, gb = flatten(a_rec, ("gauge",)), flatten(b_rec, ("gauge",))
    for key in sorted(set(ga) | set(gb)):
        if not keep(key):
            continue
        va, vb = ga.get(key, 0.0), gb.get(key, 0.0)
        # absolute flip rules first: meaningful exactly when va == 0,
        # where every percentage rule below must skip
        for pat, floor, why in _GAUGE_FLIP_RULES:
            if pat.search(key) and va <= 0 and vb >= floor:
                regressions.append((key, va, vb, float("inf"), why))
        if key not in ga or key not in gb or va <= 0:
            continue
        pct = (vb - va) / va * 100.0
        for pat, why in _GAUGE_GROW_RULES:
            if pat.search(key) and vb > va and pct > max_regress_pct:
                regressions.append((key, va, vb, pct, why))
        for pat, why in _GAUGE_DROP_RULES:
            if pat.search(key) and vb < va and -pct > max_regress_pct:
                regressions.append((key, va, vb, pct, why))
    ha, hb = _hist_p99s(a_rec), _hist_p99s(b_rec)
    for key in sorted(set(ha) & set(hb)):
        if not keep(key):
            continue
        (va, why), (vb, _) = ha[key], hb[key]
        if va <= 0 or vb <= va:
            continue
        pct = float("inf") if vb == float("inf") \
            else (vb - va) / va * 100.0
        if pct > max_regress_pct:
            regressions.append((key + ":p99", va, vb, pct, why))
    return regressions


def render_compare(a_recs, b_recs, a_name, b_name, max_regress_pct=25.0,
                   min_delta=1.0):
    """(markdown, regressions) between the last snapshots of two runs."""
    a, b = a_recs[-1], b_recs[-1]
    fa, fb = flatten(a), flatten(b)
    lines = [f"# metrics comparison: {a_name} vs {b_name}", "",
             "| metric | A | B | delta |", "|---|---|---|---|"]
    for key in sorted(set(fa) | set(fb)):
        va, vb = fa.get(key, 0.0), fb.get(key, 0.0)
        d = f"{100.0 * (vb - va) / va:+.1f}%" if va else \
            ("-" if vb == va else "new")
        lines.append(f"| {key} | {va:g} | {vb:g} | {d} |")
    regs = compare_counters(a, b, max_regress_pct=max_regress_pct,
                            min_delta=min_delta)
    if regs:
        lines += ["", f"## REGRESSIONS (> {max_regress_pct:g}%)", ""]
        for key, va, vb, pct, why in regs:
            pct_s = "inf" if pct == float("inf") else f"{pct:+.1f}%"
            lines.append(f"- **{key}**: {va:g} -> {vb:g} ({pct_s}) — {why}")
    else:
        lines += ["", "no counter regressions"]
    return "\n".join(lines), regs


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("run", nargs="?", help="metrics .jsonl to render")
    p.add_argument("--compare", nargs=2, metavar=("A", "B"),
                   help="diff two snapshot streams; exit 1 on counter "
                        "regressions past --max-regress-pct")
    p.add_argument("--max-regress-pct", type=float, default=25.0)
    p.add_argument("--min-delta", type=float, default=1.0,
                   help="ignore counter moves smaller than this (absolute)")
    args = p.parse_args(argv)
    if args.compare:
        a_path, b_path = args.compare
        md, regs = render_compare(
            load_snapshots(a_path), load_snapshots(b_path),
            os.path.basename(a_path), os.path.basename(b_path),
            max_regress_pct=args.max_regress_pct,
            min_delta=args.min_delta)
        print(md)
        return 1 if regs else 0
    if not args.run:
        p.error("give a metrics .jsonl, or --compare A B")
    records = load_snapshots(args.run)
    print(render(records, title=f"metrics report: {args.run}"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
