"""Device management.

TPU-native analog of paddle/phi/backends/ DeviceManager + python
paddle.device (python/paddle/device/__init__.py). There are no streams —
XLA owns async execution — so stream/event APIs are compatibility shims with
synchronization mapped to ``jax.block_until_ready``.
"""
from __future__ import annotations

import os

import jax

__all__ = ["setup_compile_cache", "set_device", "get_device",
           "device_count", "get_all_device_type",
           "is_compiled_with_cuda", "is_compiled_with_tpu", "synchronize",
           "Stream", "Event", "current_stream"]

_current = ["tpu:0"]


def _platform():
    return jax.default_backend()


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    The location is decided from OUTSIDE the program: when
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing
    is set in code; otherwise the cache lives at ``<checkout>/.jax_cache``
    (git-ignored).  The directory is part of every cache key, so it must
    not move between runs — never a temp dir.  Entry points call this once
    before their first compile (chip_smoke.py, benchmark/run.py,
    serving/worker.py);
    nothing else in the repo names a cache path.  Touches no backend."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        cache_dir = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def set_device(device: str):
    """paddle.set_device parity. Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'
    (gpu mapped to the default backend for reference-script compat)."""
    dev = device.lower()
    if dev.startswith("gpu") or dev.startswith("cuda") or dev.startswith("xpu"):
        dev = dev.replace("gpu", "tpu").replace("cuda", "tpu").replace("xpu", "tpu")
    _current[0] = dev if ":" in dev else f"{dev}:0"
    return _current[0]


def get_device() -> str:
    plat = _platform()
    if plat == "cpu":
        return "cpu"
    idx = _current[0].split(":")[1] if ":" in _current[0] else "0"
    return f"{plat}:{idx}"


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def is_compiled_with_distribute() -> bool:
    return True


def synchronize(device=None):
    """Block until all dispatched work completes (device.synchronize parity).
    XLA has no user-visible streams; sync via a trivial barrier value."""
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class Stream:
    """Compatibility shim: XLA schedules asynchronously; wait == barrier."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, other):
        synchronize()

    def record_event(self, event=None):
        event = event or Event()
        return event

    def wait_event(self, event):
        pass


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_default_stream = Stream()


def current_stream(device=None):
    return _default_stream
