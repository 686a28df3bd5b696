"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built on JAX/XLA/Pallas.

Top-level namespace mirrors `paddle` (reference: python/paddle/__init__.py):
tensor creation/math, paddle.nn, paddle.optimizer, paddle.io, paddle.amp,
paddle.distributed, paddle.vision, paddle.Model, ...
"""

__version__ = "0.1.0"

from .core import (  # noqa: F401
    Tensor, no_grad, enable_grad, set_grad_enabled, is_grad_enabled,
    seed, get_rng_state, set_rng_state,
    set_device, get_device, device_count,
    is_compiled_with_tpu, is_compiled_with_cuda, is_compiled_with_xpu,
    is_compiled_with_npu,
    CPUPlace, TPUPlace,
    set_default_dtype, get_default_dtype,
    float16, bfloat16, float32, float64, int8, int16, int32, int64,
    uint8, bool_, complex64, complex128,
)
from .core.tensor import to_tensor, Parameter  # noqa: F401

from .tensor import *  # noqa: F401,F403
from .tensor import einsum  # noqa: F401

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import amp  # noqa: F401
from . import autograd  # noqa: F401
from . import jit  # noqa: F401
from . import distributed  # noqa: F401
from . import vision  # noqa: F401
from . import static  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import sparse  # noqa: F401
from . import linalg  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from .batch import batch  # noqa: F401
from . import reader  # noqa: F401
from . import audio  # noqa: F401
from . import distribution  # noqa: F401
from . import text  # noqa: F401
from . import device  # noqa: F401
from . import version  # noqa: F401
from . import inference  # noqa: F401
from . import onnx  # noqa: F401
from . import utils  # noqa: F401
from . import hub  # noqa: F401
from . import callbacks  # noqa: F401
from . import sysconfig  # noqa: F401
from . import regularizer  # noqa: F401
from . import quantization  # noqa: F401
from . import geometric  # noqa: F401
from . import cost_model  # noqa: F401
from . import serving  # noqa: F401
from . import observability  # noqa: F401

from .framework.io import save, load  # noqa: F401
from .hapi import Model, summary, flops  # noqa: F401
from .jit import to_static  # noqa: F401

# --- top-level parity aliases (reference python/paddle/__init__.py __all__)
import numpy as _np

dtype = _np.dtype                       # paddle.dtype: dtype constructor/type
bool = bool_                            # noqa: A001  (paddle.bool dtype)
from .core.device import (  # noqa: F401,E402
    CUDAPlace, NPUPlace, CUDAPinnedPlace, disable_signal_handler)
from .nn import ParamAttr  # noqa: F401,E402
from .distributed.parallel_layers import DataParallel  # noqa: F401,E402

# TPU has one device RNG stream; the cuda-named accessors map onto it
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state

floor_mod = mod                         # noqa: F405 (alias, reference math.py)
reverse = flip                          # noqa: F405 (alias, reference manipulation)


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """Standalone parameter factory (reference: paddle.create_parameter /
    fluid/layers/tensor.py create_parameter). Honors ParamAttr's
    initializer / trainable / regularizer / name the same way
    Layer.create_parameter does."""
    from .nn.initializer import Constant, XavierNormal
    from .nn.param_attr import ParamAttr
    attr = ParamAttr._to_attr(attr)
    init = default_initializer
    if init is None and attr is not None and attr is not False \
            and getattr(attr, "initializer", None) is not None:
        init = attr.initializer
    if init is None:
        init = Constant(0.0) if is_bias else XavierNormal()
    import jax.numpy as jnp
    data = init(tuple(shape), jnp.dtype(dtype))
    p = Parameter(data, name=name)
    if attr is not None and attr is not False:
        if attr.name:
            p.name = attr.name
        # NB: builtin bool is shadowed by the paddle.bool dtype above
        p.trainable = not not attr.trainable
        p.stop_gradient = not attr.trainable
        if attr.regularizer is not None:
            p.regularizer = attr.regularizer
    return p


class LazyGuard:
    """Reference paddle.LazyGuard defers parameter materialization so huge
    models can be constructed before placement. Under PjRt, initializer ops
    are dispatched asynchronously and buffers materialize on first use, so
    eager construction already has lazy cost; the guard is a scope marker
    kept for API parity."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def check_shape(shape):
    """Validate a shape argument (reference exports this helper)."""
    if isinstance(shape, (list, tuple)):
        for s in shape:
            if not isinstance(s, (int, _np.integer)) and s is not None:
                raise TypeError(f"invalid dim {s!r} in shape {shape!r}")
    return shape


# paddle.disable_static/enable_static compatibility: we are always "dygraph"
_static_mode = False


def disable_static(place=None):
    global _static_mode
    _static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def in_dynamic_mode():
    return not _static_mode


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    from .autograd import grad as _grad
    return _grad(outputs, inputs, grad_outputs, retain_graph, create_graph,
                 only_inputs, allow_unused, no_grad_vars)


def get_flags(flags=None):
    from .framework import flags as _f
    return _f.get_flags(flags)


def set_flags(flags):
    from .framework import flags as _f
    return _f.set_flags(flags)


def set_printoptions(**kw):
    import numpy as np
    np.set_printoptions(**{k: v for k, v in kw.items()
                           if k in ("precision", "threshold", "edgeitems", "linewidth")})
