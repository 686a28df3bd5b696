"""paddle_tpu.observability — the unified metrics/trace/postmortem substrate.

ISSUE 4's tentpole: PR 1 (profiler spans), PR 2 (PS RPC fabric) and PR 3
(serving counters) each grew private ad-hoc counters and JSONL formats,
and a wedged run could still die without evidence. This package is the
one substrate they all report through:

  metrics.py         — Counter/Gauge/Histogram registry with label sets,
                       consistent snapshots, JSONL (metrics.v1) +
                       Prometheus text exposition; zero-cost when
                       disabled. Rendered/compared by
                       tools/metrics_report.py.
  tracecontext.py    — trace/span ids, thread+process propagation scope,
                       the 24-byte wire context the PS RPC frames carry,
                       and merge_chrome_traces() for one causally-linked
                       multi-process timeline.
  flight_recorder.py — bounded ring of recent spans + watchdog + SIGTERM
                       hook; dumps thread stacks, the span ring, and a
                       metrics snapshot to a postmortem artifact
                       (postmortem.v1) on hang/crash.
  faults.py          — deterministic fault injection: named sites on the
                       failure-prone paths (PS RPC, checkpoint commit,
                       serving decode, DataLoader) armed via env/API to
                       raise/delay/drop/truncate with seeded triggers;
                       every fired fault is a metric + a span
                       (docs/robustness.md).
  fleet.py           — the LIVE fleet plane (ISSUE 12): metrics
                       federation (merge N per-process metrics.v1
                       snapshots into one worker_id/role-labeled fleet
                       snapshot, histogram buckets merged bucket-wise),
                       the multi-window SLO burn-rate watchdog, and the
                       router-side FleetPlane pump that polls OP_METRICS,
                       streams fleet_metrics.jsonl, and pulls a fleet
                       postmortem bundle over OP_DUMP on sustained
                       breach.
  reqtimeline.py     — per-request end-to-end timelines (ISSUE 12): the
                       canonical phase vocabulary (queue/prefill/
                       kv_handoff/adopt/place/decode/failover), the
                       contiguous PhaseTrail whose segment durations sum
                       exactly to the request's end-to-end span, and the
                       reqtimeline.v1 record both the serving scheduler
                       and the fleet router emit.
  kvledger.py        — the KV-memory attribution plane (ISSUE 16): the
                       kvledger.v1 block lifecycle event log (alloc/ref/
                       unref/free/share/cache_insert/cache_evict) the
                       block pool + prefix cache emit, per-tenant
                       resident-HBM gauges (serving_kv_blocks/bytes
                       {tenant,kind}), and the LedgerReconciler shadow-
                       pool watchdog that latches any ledger-vs-pool
                       divergence at scheduler-step boundaries.
  numerics.py        — the numerics health plane (ISSUE 19): in-trace
                       tensor sentinels (tap/tap_layer/tap_tree emit one
                       fused [finite_frac, absmax, rms, sat_frac] vector
                       per site as extra executable outputs, armed at
                       build time like capture_logits), the rolling
                       median/MAD online detector latching
                       numerics_anomaly_total{site,kind}, and the NaN
                       bisection localizer engines use to name the first
                       unhealthy layer in a postmortem bundle.

Producers already wired in: serving scheduler (queue depth, slot
occupancy, admission/timeout/reject counts, tokens, TTFT), PS RPC client
and server (per-verb latency/bytes, pool size, in-band errors),
io.DataLoader (wait-time histogram), device op-cache (hits/misses via a
collector), and live/peak device bytes (collector below).

Every submodule is stdlib-only at import time: importable before (or
without) jax. A chip belongs to ONE process, so whatever must run beside
the process that holds it — a supervisor writing a postmortem, the
offline tools replaying a ledger — has to work without initialising jax.
"""
import sys

from . import faults, fleet, flight_recorder, metrics  # noqa: F401
from . import kvledger, numerics, reqtimeline  # noqa: F401
from . import tracecontext  # noqa: F401
from .flight_recorder import dump_postmortem  # noqa: F401
from .metrics import registry  # noqa: F401
from .tracecontext import merge_chrome_traces, trace_scope  # noqa: F401

__all__ = ["metrics", "tracecontext", "flight_recorder", "faults",
           "fleet", "reqtimeline", "kvledger", "numerics", "registry",
           "dump_postmortem", "trace_scope", "merge_chrome_traces"]


def _collect_live_bytes(reg):
    """Snapshot-time collector: live device bytes now + the peak observed
    across snapshots (the HBM high-water proxy `jax.live_arrays` can
    answer). Touches jax only if the process already imported it — a
    metrics snapshot must never trigger backend init."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        live = int(sum(a.size * a.dtype.itemsize for a in jax.live_arrays()))
    except Exception:                                        # noqa: BLE001
        return
    reg.gauge("live_device_bytes",
              "Bytes of device arrays the process currently holds").set(live)
    reg.gauge("live_device_bytes_peak",
              "High-water mark of live_device_bytes across snapshots"
              ).set_to_max(live)


registry().register_collector(_collect_live_bytes)
