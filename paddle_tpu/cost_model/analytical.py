"""Analytical per-op cost model: jaxpr walk + roofline.

Reference: python/paddle/cost_model/cost_model.py (profile-based per-op
cost table + static_op_benchmark.json lookups feeding auto-parallel
planning). TPU-native equivalent: instead of replaying profiled kernels,
trace the function once (`jax.make_jaxpr`) and attribute FLOPs and HBM
bytes to every equation, then lower to a time estimate with a roofline
model (time = max(flops/peak, bytes/bandwidth)) for a device spec.

The walk recurses through pjit/remat/custom-vjp bodies, multiplies scan
bodies by trip count, takes the max over cond branches, and counts one
iteration of while_loop (trip count is data-dependent; flagged in the
report) — mirroring how the passes in static/ir_pass.py traverse the
same structures.
"""
from dataclasses import dataclass, field

import numpy as np

__all__ = ["DeviceSpec", "OpCost", "CostReport", "estimate", "DEVICES",
           "device_spec"]


@dataclass(frozen=True)
class DeviceSpec:
    name: str
    peak_flops: float          # FLOP/s at the matmul dtype
    hbm_bw: float              # bytes/s
    kinds: tuple = ()          # jax `device_kind` spellings of this chip

    def roofline_s(self, flops, bytes_):
        return max(flops / self.peak_flops, bytes_ / self.hbm_bw)


# THE peaks table: bf16 MXU peak / HBM bandwidth per chip (Google Cloud TPU
# documentation, the "TPU v5e" / "TPU v4" / "TPU v5p" system pages), found
# from a live device through its `device_kind` by `device_spec`. The "cpu"
# row is a nominal host for pricing jaxprs in tests — never a peak to
# divide a measured rate by.
DEVICES = {
    "tpu-v5e": DeviceSpec("tpu-v5e", 197e12, 819e9,
                          ("TPU v5 lite", "TPU v5e")),
    "tpu-v4": DeviceSpec("tpu-v4", 275e12, 1228e9, ("TPU v4",)),
    "tpu-v5p": DeviceSpec("tpu-v5p", 459e12, 2765e9, ("TPU v5p", "TPU v5")),
    "cpu": DeviceSpec("cpu", 1e11, 5e10, ("cpu",)),
}


def device_spec(device_kind=None):
    """The DeviceSpec of the attached device (`jax.devices()[0]`), or of
    the given `device_kind`. A kind the table does not list is an error,
    not a default: pricing an unknown chip as a v5e is how a number gets
    written under the wrong device's name."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    for spec in DEVICES.values():
        if device_kind in spec.kinds:
            return spec
    raise KeyError(
        f"device_kind {device_kind!r} is not in cost_model.analytical."
        f"DEVICES; add its peaks (with their source) before measuring on it")


@dataclass
class OpCost:
    flops: float = 0.0
    bytes: float = 0.0
    count: int = 0


@dataclass
class CostReport:
    device: DeviceSpec
    by_op: dict = field(default_factory=dict)   # prim name -> OpCost
    has_while: bool = False

    @property
    def total_flops(self):
        return sum(c.flops for c in self.by_op.values())

    @property
    def total_bytes(self):
        return sum(c.bytes for c in self.by_op.values())

    @property
    def time_ms(self):
        """Roofline estimate applied per-op (each op is either compute- or
        bandwidth-bound), with a greedy producer-consumer fusion model for
        bytes: fusable intermediates cost nothing, materialized tensors
        cost one write + one read. Still an upper bound (~2.5x measured on
        the flagship GPT step) — chiefly because a trace taken on a CPU
        host prices the XLA S^2-materializing attention fallback, not the
        Pallas flash path the chip runs. FLOP totals are exact; prefer
        them for balancing and use time for relative comparisons."""
        return 1e3 * sum(
            self.device.roofline_s(c.flops, c.bytes)
            for c in self.by_op.values())

    def table(self, top=12):
        rows = sorted(self.by_op.items(),
                      key=lambda kv: -self.device.roofline_s(
                          kv[1].flops, kv[1].bytes))[:top]
        out = ["| op | calls | GFLOP | MB | est ms |", "|---|---|---|---|---|"]
        for name, c in rows:
            out.append(
                f"| {name} | {c.count} | {c.flops / 1e9:.2f} | "
                f"{c.bytes / 1e6:.1f} | "
                f"{1e3 * self.device.roofline_s(c.flops, c.bytes):.3f} |")
        if self.has_while:
            out.append("| (while_loop counted for ONE iteration) | | | | |")
        return "\n".join(out)


def _nbytes(aval):
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:                                        # noqa: BLE001
        return 0


def _dot_flops(eqn):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    a = eqn.invars[0].aval
    b = eqn.invars[1].aval
    batch = int(np.prod([a.shape[i] for i in lb], initial=1))
    k = int(np.prod([a.shape[i] for i in lc], initial=1))
    m = int(np.prod([s for i, s in enumerate(a.shape)
                     if i not in lc and i not in lb], initial=1))
    n = int(np.prod([s for i, s in enumerate(b.shape)
                     if i not in rc and i not in rb], initial=1))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn):
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    k_spatial = int(np.prod([rhs.shape[i] for i in dn.rhs_spec[2:]],
                            initial=1))
    cin_per_group = rhs.shape[dn.rhs_spec[1]]   # already divided by groups
    return 2.0 * int(np.prod(out.shape)) * k_spatial * cin_per_group


_ELEMENTWISE_FLOPS = {
    "add": 1, "add_any": 1, "sub": 1, "mul": 1, "div": 1, "max": 1,
    "min": 1, "neg": 1,
    "exp": 8, "log": 8, "tanh": 8, "logistic": 8, "erf": 8, "rsqrt": 4,
    "sqrt": 4, "pow": 8, "integer_pow": 2, "select_n": 1, "abs": 1,
    "sign": 1, "floor": 1, "ceil": 1, "round": 1, "cos": 8, "sin": 8,
}

_REDUCE_PRIMS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax"}

# ops XLA reliably fuses into neighbouring loops: their intermediates live
# in registers/VMEM and never round-trip HBM. Reductions fuse as epilogues
# (their INPUT read fuses with an elementwise producer); dots/convs/
# gather/scatter/concat materialize.
_FUSABLE = set(_ELEMENTWISE_FLOPS) | {
    "broadcast_in_dim", "convert_element_type", "transpose", "reshape",
    "squeeze", "expand_dims", "iota", "stop_gradient", "copy",
    "reduce_precision", "and", "or", "not", "xor", "eq", "ne", "lt", "le",
    "gt", "ge", "is_finite", "clamp",
}


_CALL_PRIMS = {"pjit", "jit", "xla_call", "closed_call", "core_call",
               "core_closed_call", "shard_map", "remat2",
               "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "checkpoint", "scan", "while",
               "cond"}


def _fusion_maps(jaxpr):
    """(var -> producing eqn, var -> consumers, var -> read-charging eqn,
    external outputs) within one jaxpr, for the greedy producer-consumer
    fusion model: a fusable op's output that only fusable ops consume is
    never materialized; a materialized tensor costs one write plus one
    read, charged to the first consumer whose read does NOT fuse (call/
    control-flow consumers are skipped — their sub-jaxpr walk counts the
    boundary read itself)."""
    producer, consumers = {}, {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            producer[v] = i
        for v in eqn.invars:
            if hasattr(v, "aval") and not hasattr(v, "val"):  # skip Literals
                consumers.setdefault(v, []).append(i)
    external = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    charge = {}
    for v, cs in consumers.items():
        p = producer.get(v)
        p_fusable = p is not None and \
            jaxpr.eqns[p].primitive.name in _FUSABLE
        for c in cs:
            cname = jaxpr.eqns[c].primitive.name
            if cname in _CALL_PRIMS:
                continue
            if p_fusable and cname in (_FUSABLE | _REDUCE_PRIMS):
                continue                     # this consumer's read fuses
            charge[v] = c
            break
    return producer, consumers, charge, external


def _walk(jaxpr, report, mult=1.0):
    producer, consumers, charge, external = _fusion_maps(jaxpr)
    eqns = jaxpr.eqns

    def read_bytes(eqn, idx):
        total = 0
        for v in eqn.invars:
            if not hasattr(v, "aval") or hasattr(v, "val"):
                continue                              # Literal: in-line
            if charge.get(v) == idx:
                total += _nbytes(v.aval)
        return total

    def write_bytes(eqn):
        total = 0
        for v in eqn.outvars:
            cs = consumers.get(v, [])
            fused_write = (eqn.primitive.name in _FUSABLE and
                           v not in external and cs and
                           all(eqns[c].primitive.name in
                               (_FUSABLE | _REDUCE_PRIMS) for c in cs))
            if not fused_write:
                total += _nbytes(v.aval)
        return total

    for idx, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        # control flow / call primitives: recurse with multipliers
        if name in ("pjit", "jit", "xla_call", "closed_call", "core_call",
                    "core_closed_call", "shard_map", "remat2",
                    "custom_jvp_call", "custom_vjp_call",
                    "custom_vjp_call_jaxpr", "checkpoint"):
            inner = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                     or eqn.params.get("fun_jaxpr"))
            if inner is not None:
                _walk(getattr(inner, "jaxpr", inner), report, mult)
                continue
        if name == "scan":
            length = eqn.params.get("length", 1)
            _walk(eqn.params["jaxpr"].jaxpr, report, mult * length)
            continue
        if name == "while":
            report.has_while = True
            _walk(eqn.params["cond_jaxpr"].jaxpr, report, mult)
            _walk(eqn.params["body_jaxpr"].jaxpr, report, mult)
            continue
        if name == "cond":
            # max over branches (worst case; branches are traced anyway)
            subs = [CostReport(report.device) for _ in
                    eqn.params["branches"]]
            for br, sub in zip(eqn.params["branches"], subs):
                _walk(br.jaxpr, sub, mult)
            worst = max(subs, key=lambda r: r.time_ms, default=None)
            if worst is not None:
                for k, c in worst.by_op.items():
                    agg = report.by_op.setdefault(k, OpCost())
                    agg.flops += c.flops
                    agg.bytes += c.bytes
                    agg.count += c.count
                report.has_while |= worst.has_while
            continue

        in_bytes = read_bytes(eqn, idx)
        out_bytes = write_bytes(eqn)
        out_elems = sum(int(np.prod(v.aval.shape, initial=1))
                        for v in eqn.outvars)
        if name == "dot_general":
            flops = _dot_flops(eqn)
        elif name == "conv_general_dilated":
            flops = _conv_flops(eqn)
        elif name in _ELEMENTWISE_FLOPS:
            flops = _ELEMENTWISE_FLOPS[name] * out_elems
        elif name in _REDUCE_PRIMS:
            flops = sum(_nbytes(v.aval) / max(v.aval.dtype.itemsize, 1)
                        for v in eqn.invars if hasattr(v, "aval"))
        else:
            flops = 0.0          # layout/gather/slice/collective: bytes-bound
        if mult == 0:
            continue                     # zero-trip scan body: never runs
        agg = report.by_op.setdefault(name, OpCost())
        agg.flops += mult * flops
        agg.bytes += mult * (in_bytes + out_bytes)
        agg.count += max(int(mult), 1)


def estimate(fn, *args, device="tpu-v5e", **kwargs):
    """Trace `fn(*args, **kwargs)` and return a CostReport (no execution:
    abstract eval only, so it works for TPU-sized shapes on a CPU host)."""
    import jax
    spec = DEVICES[device] if isinstance(device, str) else device
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    report = CostReport(spec)
    _walk(jaxpr.jaxpr, report)
    return report
