"""Discrete Fourier transforms (reference: python/paddle/fft.py — the
fft_c2c/fft_r2c/fft_c2r kernel trio behind the 22-function public API).

TPU-native design: everything lowers to `jnp.fft`, whose XLA FFT op runs on
TPU natively; gradients come from jax's fft JVP/transpose rules rather than
the reference's hand-written fft_grad kernels.  The Hermitian family members
jnp lacks (hfft2/ihfft2/hfftn/ihfftn) are built from the conjugation
identities  hfftn(x) = irfftn(conj(x), norm=swap)  and
ihfftn(x) = conj(rfftn(x, norm=swap))  (same contract as the reference's
fftn_c2r/fftn_r2c with forward flipped).

Every transform executes as a cached jitted program (keyed on the static
n/s/axis/norm arguments), not an eager op stream: one dispatch per
transform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .core.tensor import Tensor
from .core.dispatch import op_call

__all__ = [
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2", "hfft2", "ihfft2",
    "fftn", "ifftn", "rfftn", "irfftn", "hfftn", "ihfftn",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]

_NORMS = ("backward", "ortho", "forward")


def _check_norm(norm):
    if norm not in _NORMS:
        raise ValueError(
            f"Unexpected norm: {norm!r}. Norm should be 'forward', "
            f"'backward' or 'ortho'")
    return norm


def _swap_norm(norm):
    """backward <-> forward (ortho is self-dual): the Hermitian-transform
    identities flip which direction carries the 1/n factor."""
    return {"backward": "forward", "forward": "backward",
            "ortho": "ortho"}[norm]


def _as_complex(v):
    if jnp.issubdtype(v.dtype, jnp.complexfloating):
        return v
    if v.dtype == jnp.float64:
        return v.astype(jnp.complex128)
    return v.astype(jnp.complex64)


def _as_real(v):
    if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
        return v.astype(jnp.float32)
    return v


def _shape_of(x):
    return tuple(x.shape)


def _dtype_of(x):
    v = x._value if isinstance(x, Tensor) else x
    return jnp.result_type(v)


def _check_1d(x, axis, real_input=False):
    nd = len(_shape_of(x))
    if not isinstance(axis, int):
        raise ValueError(f"Invalid fft axis: {axis!r}")
    if not (-nd <= axis < nd):
        raise ValueError(f"axis {axis} out of range for rank {nd}")
    if real_input and jnp.issubdtype(_dtype_of(x), jnp.complexfloating):
        raise TypeError("Input must be real, but got a complex tensor")


def _check_nd(x, s, axes, real_input=False):
    if s is not None and axes is not None and len(s) != len(axes):
        raise ValueError(
            f"Length of s ({len(s)}) and axes ({len(axes)}) must match")
    if real_input and jnp.issubdtype(_dtype_of(x), jnp.complexfloating):
        raise TypeError("Input must be real, but got a complex tensor")


def _tup(v):
    if v is None or isinstance(v, int):
        return v
    return tuple(v)


@functools.lru_cache(maxsize=1024)
def _exec(kind, n_or_s, ax, norm):
    """Cached jitted executor for one (transform, static-args) combo."""
    def body(v):
        if kind == "fft":
            return jnp.fft.fft(_as_complex(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "ifft":
            return jnp.fft.ifft(_as_complex(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "rfft":
            return jnp.fft.rfft(_as_real(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "irfft":
            return jnp.fft.irfft(_as_complex(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "hfft":
            return jnp.fft.hfft(_as_complex(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "ihfft":
            return jnp.fft.ihfft(_as_real(v), n=n_or_s, axis=ax, norm=norm)
        if kind == "fftn":
            return jnp.fft.fftn(_as_complex(v), s=n_or_s, axes=ax, norm=norm)
        if kind == "ifftn":
            return jnp.fft.ifftn(_as_complex(v), s=n_or_s, axes=ax, norm=norm)
        if kind == "rfftn":
            return jnp.fft.rfftn(_as_real(v), s=n_or_s, axes=ax, norm=norm)
        if kind == "irfftn":
            return jnp.fft.irfftn(_as_complex(v), s=n_or_s, axes=ax,
                                  norm=norm)
        if kind == "hfftn":
            return jnp.fft.irfftn(jnp.conj(_as_complex(v)), s=n_or_s, axes=ax,
                                  norm=_swap_norm(norm))
        if kind == "ihfftn":
            return jnp.conj(jnp.fft.rfftn(_as_real(v), s=n_or_s, axes=ax,
                                          norm=_swap_norm(norm)))
        if kind == "fftshift":
            return jnp.fft.fftshift(v, axes=ax)
        if kind == "ifftshift":
            return jnp.fft.ifftshift(v, axes=ax)
        raise ValueError(kind)
    return jax.jit(body)


# --- 1d -------------------------------------------------------------------
def fft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis)
    return op_call("fft_c2c", _exec("fft", n, axis, norm), x)


def ifft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis)
    return op_call("fft_c2c", _exec("ifft", n, axis, norm), x)


def rfft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis, real_input=True)
    return op_call("fft_r2c", _exec("rfft", n, axis, norm), x)


def irfft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis)
    return op_call("fft_c2r", _exec("irfft", n, axis, norm), x)


def hfft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis)
    return op_call("fft_c2r", _exec("hfft", n, axis, norm), x)


def ihfft(x, n=None, axis=-1, norm="backward", name=None):
    _check_norm(norm)
    _check_1d(x, axis, real_input=True)
    return op_call("fft_r2c", _exec("ihfft", n, axis, norm), x)


# --- nd -------------------------------------------------------------------
def fftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes)
    return op_call("fft_c2c", _exec("fftn", _tup(s), _tup(axes), norm), x)


def ifftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes)
    return op_call("fft_c2c", _exec("ifftn", _tup(s), _tup(axes), norm), x)


def rfftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes, real_input=True)
    return op_call("fft_r2c", _exec("rfftn", _tup(s), _tup(axes), norm), x)


def irfftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes)
    return op_call("fft_c2r", _exec("irfftn", _tup(s), _tup(axes), norm), x)


def hfftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes)
    return op_call("fft_c2r", _exec("hfftn", _tup(s), _tup(axes), norm), x)


def ihfftn(x, s=None, axes=None, norm="backward", name=None):
    _check_norm(norm)
    _check_nd(x, s, axes, real_input=True)
    return op_call("fft_r2c", _exec("ihfftn", _tup(s), _tup(axes), norm), x)


# --- 2d (thin fixed-axes wrappers, same as the reference) -----------------
def _axes2(axes):
    if axes is None:
        return (-2, -1)
    if len(axes) != 2:
        raise ValueError(f"Invalid 2D fft axes: {axes!r}")
    return tuple(axes)


def fft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return fftn(x, s=s, axes=_axes2(axes), norm=norm)


def ifft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return ifftn(x, s=s, axes=_axes2(axes), norm=norm)


def rfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return rfftn(x, s=s, axes=_axes2(axes), norm=norm)


def irfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return irfftn(x, s=s, axes=_axes2(axes), norm=norm)


def hfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return hfftn(x, s=s, axes=_axes2(axes), norm=norm)


def ihfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    return ihfftn(x, s=s, axes=_axes2(axes), norm=norm)


# --- helpers --------------------------------------------------------------
def fftfreq(n, d=1.0, dtype=None, name=None):
    out = jnp.fft.fftfreq(n, d=d)
    return Tensor(out.astype(jnp.dtype(dtype)) if dtype else out)


def rfftfreq(n, d=1.0, dtype=None, name=None):
    out = jnp.fft.rfftfreq(n, d=d)
    return Tensor(out.astype(jnp.dtype(dtype)) if dtype else out)


def fftshift(x, axes=None, name=None):
    return op_call("fftshift", _exec("fftshift", None, _tup(axes), None), x)


def ifftshift(x, axes=None, name=None):
    return op_call("fftshift", _exec("ifftshift", None, _tup(axes), None), x)