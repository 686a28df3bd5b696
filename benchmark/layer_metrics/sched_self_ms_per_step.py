"""Scheduler's own host time per step(): mean step() time minus the engine
calls (prefill, decode) inside it, from the harness's timing wrappers."""


def read(record, trace):
    s = record.get("spans", {})
    if not s.get("steps"):
        return None
    return 1e3 * (s["step_s"] - s["prefill_s"] - s["decode_s"]) / s["steps"]
