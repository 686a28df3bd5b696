"""The result line: one JSON object, the last line of standard output."""
import json
import sys


def device_block(memory_peak_bytes, busy_s=None, window_s=None):
    import jax
    devs = jax.devices()
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": memory_peak_bytes}
    if busy_s is not None:
        out["busy_s"] = busy_s
        out["window_s"] = window_s
    return out


def memory_peak_bytes(n_devices=1):
    """Peak on the fullest chip, as the runtime reports it (None on a
    backend that reports nothing, such as the CPU)."""
    import jax
    peaks = []
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def emit(correct, attempted, failed, metrics, device, checks, breakdown=None):
    """`checks` is a list of {"name", "value", "limit", "ok"}: each number
    compared beside its limit. Printed as the last lines of standard error,
    and under a key of its own that comes last in the result line."""
    for c in checks:
        print(f"check {c['name']}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    print(json.dumps(line), flush=True)
