"""Tensor façade over ``jax.Array``.

TPU-native replacement for the reference's DenseTensor + public Tensor
(paddle/phi/core/dense_tensor.h:37, paddle/phi/api/include/tensor.h:82) and the
eager AutogradMeta (paddle/fluid/eager/autograd_meta.h:61): a lightweight
Python wrapper holding a jax value plus autograd metadata. The jax value may be
a concrete ``jax.Array`` (eager mode — dispatch-committed async, the analog of
Paddle's stream-async kernels) or a tracer (inside ``jit``/``grad``
transforms), so the same Tensor code works in both execution modes.

Autograd: ``stop_gradient`` has Paddle semantics (default True; Parameters
default False). ``backward()`` walks the tape built by
:mod:`paddle_tpu.core.autograd`.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtype_mod

__all__ = ["Tensor", "Parameter", "to_tensor", "is_tensor"]

_tensor_method_registry = {}


def monkey_patch_method(name):
    """Register a function as a Tensor method (the analog of the generated
    pybind Tensor methods, paddle/fluid/pybind/eager_method.cc)."""
    def deco(fn):
        setattr(Tensor, name, fn)
        _tensor_method_registry[name] = fn
        return fn
    return deco


# concretization listener (jit SOT tape recorder): when set, every
# device->host fetch that can steer python control flow reports
# (jax_value, python_result) — the reference SOT's "graph break on
# data-dependent control flow" observation points.
_concretize_hook = [None]


def _notify_concretize(value, result):
    hook = _concretize_hook[0]
    if hook is not None:
        hook(value, result)
    return result


class Tensor:
    __slots__ = ("_value", "stop_gradient", "_grad", "_grad_node", "_out_index",
                 "name", "persistable", "_backward_hooks", "trainable",
                 "_dist_mesh", "_placements", "sequence_parallel",
                 "__weakref__")

    def __init__(self, value, stop_gradient=True, name=None):
        if isinstance(value, Tensor):
            value = value._value
        self._value = value
        self.stop_gradient = stop_gradient
        self._grad = None           # Tensor | None
        self._grad_node = None      # autograd.GradNode | None
        self._out_index = 0
        self.name = name
        self.persistable = False
        self.trainable = True
        self._backward_hooks = None

    # -- value access -----------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self):
        return np.dtype(self._value.dtype)

    @property
    def place(self):
        try:
            devs = list(self._value.devices())
            return str(devs[0]) if devs else "tpu"
        except Exception:
            return "traced"

    def numel(self):
        return self.size

    def dim(self):
        return self._value.ndim

    @property
    def is_leaf(self):
        return self._grad_node is None

    def numpy(self):
        v = self._value
        if _concretize_hook[0] is not None:
            # host fetches can steer python control flow: report to the
            # SOT tape recorder (guarded on the full array)
            return _notify_concretize(v, self._numpy_raw())
        return self._numpy_raw()

    def _numpy_raw(self):
        return np.asarray(jax.device_get(self._value))

    def item(self, *args):
        # _numpy_raw: exactly one concretize notification per fetch
        if args:
            return _notify_concretize(self._value,
                                      self._numpy_raw().item(*args))
        return _notify_concretize(self._value, self._numpy_raw().item())

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, *a, **kw):
        return self._value.__dlpack__(*a, **kw)

    # -- autograd ----------------------------------------------------------
    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, g):
        self._grad = g

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            self._grad = Tensor(jnp.zeros_like(self._grad._value))
        else:
            self._grad = None

    def backward(self, grad_tensor=None, retain_graph=False):
        from . import autograd
        autograd.backward([self], [grad_tensor] if grad_tensor is not None else None,
                          retain_graph=retain_graph)

    def register_hook(self, hook):
        """Register a grad hook (reference: paddle/fluid/eager/hooks.h).
        Returns a removable handle."""
        if self._backward_hooks is None:
            self._backward_hooks = []
        self._backward_hooks.append(hook)
        hooks = self._backward_hooks
        class _Handle:
            def remove(self):
                if hook in hooks:
                    hooks.remove(hook)
        return _Handle()

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from .dispatch import op_call
        return op_call("clone", lambda x: x + jnp.zeros((), dtype=x.dtype) if jnp.issubdtype(x.dtype, jnp.inexact) else x, self)

    # -- in-place-ish helpers ---------------------------------------------
    def _set_value(self, value):
        """Replace the underlying buffer (used by optimizers / set_state_dict).
        Detaches from any recorded graph."""
        if isinstance(value, Tensor):
            value = value._value
        self._value = value
        return self

    def set_value(self, value):
        if isinstance(value, (np.ndarray, list, tuple, int, float)):
            value = jnp.asarray(value, dtype=self._value.dtype)
        return self._set_value(value)

    def copy_(self, other, blocking=True):
        return self._set_value(other)

    def fill_(self, v):
        return self._set_value(jnp.full_like(self._value, v))

    def zero_(self):
        return self._set_value(jnp.zeros_like(self._value))

    # -- misc --------------------------------------------------------------
    def astype(self, dtype):
        from .dispatch import op_call
        d = dtype_mod.convert_dtype(dtype)
        return op_call("cast", lambda x: x.astype(d), self)

    def cast(self, dtype):
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        # Accepts dtype and/or device strings; device moves are XLA-managed.
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in ("cpu", "tpu", "gpu") or ":" in str(a):
                continue
            try:
                out = out.astype(a)
            except ValueError:
                continue
        return out

    def cpu(self):
        return Tensor(jax.device_get(self._value), stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    def __len__(self):
        if self._value.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        sg = self.stop_gradient
        try:
            val = np.asarray(self._value)
            body = np.array2string(val, precision=6, separator=", ")
        except Exception:
            body = f"<traced {self._value}>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"stop_gradient={sg},\n       {body})")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        return _notify_concretize(self._value, bool(self._value))

    def __int__(self):
        return _notify_concretize(self._value, int(self._value))

    def __float__(self):
        return _notify_concretize(self._value, float(self._value))

    def __index__(self):
        return _notify_concretize(self._value, int(self._value))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __format__(self, spec):
        if self._value.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    # Arithmetic dunders are attached in paddle_tpu/tensor/__init__.py via
    # monkey_patch_method, mirroring how the reference patches math methods
    # onto Tensor (python/paddle/tensor/tensor.prototype.pyi pattern).


class Parameter(Tensor):
    """Trainable tensor (reference: python/paddle/base/framework.py
    EagerParamBase). stop_gradient defaults to False."""
    __slots__ = ("optimize_attr", "regularizer", "do_model_average", "need_clip",
                 "is_distributed")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False

    @property
    def trainable_(self):
        return self.trainable


def is_tensor(x):
    return isinstance(x, Tensor)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity (reference python/paddle/tensor/creation.py)."""
    d = dtype_mod.convert_dtype(dtype)
    if isinstance(data, Tensor):
        v = data._value
        if d is not None and v.dtype != d:
            v = v.astype(d)
        return Tensor(v, stop_gradient=stop_gradient)
    if isinstance(data, (list, tuple)) and any(isinstance(x, Tensor) for x in data):
        data = [x._value if isinstance(x, Tensor) else x for x in data]
        v = jnp.stack([jnp.asarray(x) for x in data])
    else:
        if isinstance(data, (float, int, bool, complex)) or (
                isinstance(data, np.ndarray) and d is None):
            # match paddle: python floats default to the default float dtype
            if isinstance(data, bool):
                v = jnp.asarray(data)
            elif isinstance(data, float):
                v = jnp.asarray(data, dtype=dtype_mod.default_float_dtype())
            elif isinstance(data, int):
                v = jnp.asarray(data, dtype=jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
            else:
                # numpy array: preserve its dtype (downcast 64-bit under x32)
                v = jnp.asarray(data)
        else:
            v = jnp.asarray(data, dtype=d)
    if d is not None and v.dtype != d:
        v = v.astype(d)
    return Tensor(v, stop_gradient=stop_gradient)


# -- pytree registration ---------------------------------------------------
def _tensor_flatten(t: Tensor):
    return (t._value,), (t.stop_gradient, t.name)


def _tensor_unflatten(aux, children):
    t = Tensor(children[0], stop_gradient=aux[0], name=aux[1])
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)
jax.tree_util.register_pytree_node(
    Parameter,
    lambda p: ((p._value,), (p.trainable, p.name)),
    lambda aux, ch: Parameter(ch[0], trainable=aux[0], name=aux[1]),
)
