"""paddle_tpu: a TPU-native deep learning framework.

A from-scratch framework with the capabilities of the reference
(PaddlePaddle ~3.0-rc, mounted at /root/reference) re-designed for TPU:
jax/XLA is the kernel library + compiler + async executor, Pallas provides
hand-tuned kernels for the hot ops, and jax.sharding/shard_map over device
meshes provides the distributed layer (DP/TP/PP/ZeRO/SP/EP) that the
reference implements over NCCL.

Public surface mirrors `paddle.*` so reference users can switch directly.
"""
from __future__ import annotations

__version__ = "0.1.0"

from . import flags as _flags_mod
from .flags import get_flags, set_flags

from .core import dtype as _dtype
from .core.dtype import (
    bool_, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128, float8_e4m3fn, float8_e5m2,
    set_default_dtype, get_default_dtype,
)
from .core.tensor import Tensor, Parameter, to_tensor, is_tensor
from .core.dispatch import no_grad, enable_grad, is_grad_enabled, set_grad_enabled
from .core import autograd as _autograd_core
from .core.autograd import grad
from .core.random import seed, get_rng_state, set_rng_state
from .core.device import (
    set_device, get_device, device_count, is_compiled_with_cuda,
    is_compiled_with_tpu, is_compiled_with_rocm, is_compiled_with_xpu,
    is_compiled_with_distribute,
)

from .tensor import *  # noqa: F401,F403 — functional op surface
from . import tensor  # noqa: F401

from . import autograd  # noqa: F401
from . import device  # noqa: F401
from .framework import save, load, CPUPlace, TPUPlace, CUDAPlace, in_dynamic_mode  # noqa: F401

# Subsystems (each lands with its build stage; see SURVEY.md §7)
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import distributed  # noqa: F401
from . import ops  # noqa: F401
from .ops.pallas import register_all as _register_pallas_kernels
# TPU-only; deferred to first kernel lookup because probing jax.devices()
# here would initialise the XLA backend before a multi-process launch can
# call jax.distributed.initialize (distributed/env.py)
from .core import dispatch as _dispatch_mod
_dispatch_mod.add_lazy_initializer(_register_pallas_kernels)
from . import vision  # noqa: F401
from . import incubate  # noqa: F401
from . import metric  # noqa: F401
from . import profiler  # noqa: F401
from . import hapi  # noqa: F401
from . import inference  # noqa: F401
from . import resilience  # noqa: F401
from .hapi import Model, summary, flops  # noqa: F401
from . import static  # noqa: F401
from . import sparse  # noqa: F401
from . import text  # noqa: F401
from . import audio  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import distribution  # noqa: F401
from . import geometric  # noqa: F401
from . import regularizer  # noqa: F401
from . import onnx  # noqa: F401
from . import quantization  # noqa: F401
from . import version  # noqa: F401

from .nn.layer import Layer  # convenience re-export used widely in reference code
from .distributed.parallel import DataParallel  # noqa: F401


def disable_static(place=None):
    """Dygraph is the default (and only) eager mode; accepted for compat."""


def enable_static():
    """Static-graph building is expressed via paddle_tpu.jit/static."""


def disable_signal_handler():
    pass


def in_dynamic_or_pir_mode():
    return True
