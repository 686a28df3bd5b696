"""Device / Place abstraction (reference: paddle/phi/common/place.h,
paddle/fluid/platform/device_context.h).

On TPU there is a single accelerator backend managed by PjRt through JAX; the
reference's Place zoo (CUDAPlace/XPUPlace/NPUPlace/...) collapses to
{cpu, tpu}. `set_device` picks the JAX default device; multi-chip placement is
expressed with `jax.sharding.Mesh` (see paddle_tpu.distributed), not with
per-device contexts.
"""
import jax


class Place:
    """Mirror of paddle's Place: identifies a device."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and (self.kind, self.index) == (other.kind, other.index))

    def __hash__(self):
        return hash((self.kind, self.index))

    def jax_device(self):
        platform = _JAX_PLATFORM.get(self.kind, self.kind)
        devs = [d for d in jax.devices() if d.platform == platform]
        if self.index >= len(devs):
            # never another platform's device in its place: a program that
            # asked for the chip must not run on the host without a word
            raise RuntimeError(
                f"{self!r}: jax reports {len(devs)} {platform!r} device(s) "
                f"(backend {jax.default_backend()!r})")
        return devs[self.index]


_JAX_PLATFORM = {"tpu": "tpu", "cpu": "cpu", "gpu": "gpu"}


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


_current_place = None


def _auto_place() -> Place:
    platforms = {d.platform for d in jax.devices()}
    if "tpu" in platforms:
        return Place("tpu", 0)
    return Place("cpu", 0)


def set_device(device):
    """paddle.set_device('tpu') / ('tpu:0') / ('cpu')."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    name, _, idx = str(device).partition(":")
    name = name.lower()
    if name in ("tpu", "xla"):
        name = "tpu"
    elif name in ("cpu",):
        name = "cpu"
    elif name in ("gpu", "cuda"):
        name = "gpu"
    else:
        raise ValueError(f"Unsupported device {device!r}; expected 'tpu' or 'cpu'")
    place = Place(name, int(idx) if idx else 0)
    place.jax_device()              # raises when there is no such device
    _current_place = place
    return _current_place


def get_device() -> str:
    p = default_device()
    return f"{p.kind}:{p.index}"


def default_device() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _auto_place()
    return _current_place


def device_count(kind: str = None) -> int:
    kind = kind or default_device().kind
    return len([d for d in jax.devices() if d.platform == kind]) or len(jax.devices())


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def CUDAPlace(index: int = 0) -> Place:
    """Compat shim for reference code written against CUDA (reference:
    paddle.CUDAPlace): maps to the accelerator place of THIS backend so
    `paddle.CUDAPlace(0)` call sites keep selecting "the accelerator".
    Warns once — there is no CUDA device here."""
    import warnings
    warnings.warn("CUDAPlace is not a real device on the TPU backend; "
                  "mapping to the accelerator (TPU) place", stacklevel=2)
    auto = _auto_place()
    return Place("tpu", index) if auto.kind == "tpu" else auto


def _compat_place(name: str, index: int = 0) -> Place:
    """Shared shim for vendor Places (reference paddle.{NPU,XPU,IPU,MLU}
    Place): warn once and map to the accelerator place."""
    import warnings
    warnings.warn(f"{name} is not a real device on the TPU backend; "
                  f"mapping to the accelerator (TPU) place", stacklevel=3)
    return Place("tpu", index)


def NPUPlace(index: int = 0) -> Place:
    """Compat shim (reference: paddle.NPUPlace) — see CUDAPlace."""
    return _compat_place("NPUPlace", index)


def CUDAPinnedPlace() -> Place:
    """Compat shim (reference: paddle.CUDAPinnedPlace): pinned host memory
    maps to the host place — PjRt host buffers are already DMA-able."""
    return Place("cpu", 0)


def disable_signal_handler():
    """Reference paddle.disable_signal_handler tears down the C++ fault
    handlers (platform/init.cc). This runtime installs none (failures
    surface as Python exceptions from PjRt), so this is a true no-op."""
    return None
