"""Device time of the Pallas kernels by the name the program gives them.

Since PR 26 every `pl.pallas_call` of the program passes
`metadata={"kernel": <name>}`, which XLA prints into the custom call's
`frontend_attributes={kernel_metadata={"kernel":"<name>"}}`, and an `XLA Ops`
event is named by its whole HLO instruction: so the name is in
`trace["op_s"]`'s keys. A program that names nothing (the parent of PR 26:
`kernel_metadata={}`) has no such operation and the reader gets None.
"""
import re


def seconds_of(trace, *kernels):
    """Self seconds of the operations whose kernel name is one of
    `kernels`; None where none ran."""
    found = re.compile(r'"kernel"\s*:\s*"(%s)"'
                       % "|".join(map(re.escape, kernels)))
    hit = [sec for name, sec in trace["op_s"].items() if found.search(name)]
    return sum(hit) if hit else None


def roofline_pct(record, trace, phase, *kernels):
    """100 x the least time the chip could take for flash attention's
    `phase` ("fwd" or "bwd") at the step's shapes, all layers and traced
    steps, over the device time of `kernels`."""
    from . import model_flops, peaks
    if trace is None:
        return None
    spent = seconds_of(trace, *kernels)
    if not spent:
        return None
    s = record["shapes"]
    peak = peaks.peaks_for(record["device_kind"])
    w = model_flops.flash_flops_bytes(s["batch"], s["heads"], s["seq"],
                                      s["head_dim"], s["itemsize"])
    least = max(w[f"{phase}_flops"] / peak["flops_bf16"],
                w[f"{phase}_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * s["layers"] * record["trace_steps"] / spent
