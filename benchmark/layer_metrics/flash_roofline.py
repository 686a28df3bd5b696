"""Flash attention's share of its roofline in the train step.

The least time the chip could take for the causal forward and backward at
the step's (B, H, S, D) — the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, per layer, computed from shapes by model_flops — over the
device time of the step's flash kernels in the trace (forward, the forward
again where remat recomputes it, dq, dk/dv). Compute-bound at these shapes.
"""
from benchmark.harness import model_flops, peaks, trace_reduce

# every Pallas kernel of the train step is a flash kernel; the trace names
# an operation by its HLO text, which holds the custom call's target and not
# the kernel's own name (the tracing issue can give the kernels names)
KERNELS = (trace_reduce.PALLAS,)


def read(record, trace):
    if trace is None:
        return None
    spent = trace_reduce.time_of(trace, *KERNELS)
    if not spent:
        return None
    s = record["shapes"]
    peak = peaks.peaks_for(record["device_kind"])
    w = model_flops.flash_flops_bytes(s["batch"], s["heads"], s["seq"],
                                      s["head_dim"], s["itemsize"])
    least = sum(max(w[f"{p}_flops"] / peak["flops_bf16"],
                    w[f"{p}_bytes"] / peak["hbm_bytes_per_s"])
                for p in ("fwd", "bwd"))
    least *= s["layers"] * record["trace_steps"]
    return 100.0 * least / spent
