"""Signal processing: frame / overlap_add / stft / istft (reference:
python/paddle/signal.py — frame/overlap_add are backed by CPU/GPU kernels
there; here they are gather / scatter-add index maps that XLA fuses, and the
DFT itself rides the TPU FFT op).

The stft/istft bodies run as cached jitted programs rather than eager op
streams: one dispatch for a 10-op DSP pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .core.tensor import Tensor
from .core.dispatch import op_call

__all__ = ["frame", "overlap_add", "stft", "istft"]


def frame(x, frame_length, hop_length, axis=-1, name=None):
    """Slice into overlapping frames: [..., seq] -> [..., frame_length,
    num_frames] (axis=-1) or [seq, ...] -> [num_frames, frame_length, ...]
    (axis=0)."""
    if hop_length <= 0:
        raise ValueError(
            f"hop_length should be > 0, but got {hop_length}")
    if axis not in (0, -1):
        raise ValueError(f"axis should be 0 or -1, but got {axis}")

    def impl(v):
        seq = v.shape[axis]
        if not 0 < frame_length <= seq:
            raise ValueError(
                f"frame_length should be in (0, {seq}], got {frame_length}")
        n_frames = 1 + (seq - frame_length) // hop_length
        offsets = hop_length * jnp.arange(n_frames)
        taps = jnp.arange(frame_length)
        if axis == -1:
            idx = taps[:, None] + offsets[None, :]   # [frame_length, n_frames]
            return v[..., idx]
        idx = offsets[:, None] + taps[None, :]       # [n_frames, frame_length]
        return v[idx]
    return op_call("frame", impl, x)


def overlap_add(x, hop_length, axis=-1, name=None):
    """Inverse of `frame` under summation: frames scatter-add into
    [..., seq_length] (axis=-1) or [seq_length, ...] (axis=0), with
    seq_length = (n_frames - 1) * hop_length + frame_length."""
    if hop_length <= 0:
        raise ValueError(
            f"hop_length should be > 0, but got {hop_length}")
    if axis not in (0, -1):
        raise ValueError(f"axis should be 0 or -1, but got {axis}")

    def impl(v):
        if v.ndim < 2:
            raise ValueError("overlap_add expects rank >= 2 input")
        if axis == -1:
            frame_length, n_frames = v.shape[-2], v.shape[-1]
            seq = (n_frames - 1) * hop_length + frame_length
            pos = (jnp.arange(frame_length)[:, None]
                   + hop_length * jnp.arange(n_frames)[None, :])
            out = jnp.zeros(v.shape[:-2] + (seq,), v.dtype)
            return out.at[..., pos].add(v)
        n_frames, frame_length = v.shape[0], v.shape[1]
        seq = (n_frames - 1) * hop_length + frame_length
        pos = (hop_length * jnp.arange(n_frames)[:, None]
               + jnp.arange(frame_length)[None, :])
        out = jnp.zeros((seq,) + v.shape[2:], v.dtype)
        return out.at[pos].add(v)
    return op_call("overlap_add", impl, x)


@functools.lru_cache(maxsize=64)
def _stft_exec(n_fft, hop_length, center, pad_mode, normalized, onesided):
    @jax.jit
    def run(v, win):
        vv = v if v.ndim == 2 else v[None]
        if win.shape[0] < n_fft:
            pl = (n_fft - win.shape[0]) // 2
            win = jnp.pad(win, (pl, n_fft - win.shape[0] - pl))
        if center:
            p = n_fft // 2
            mode = "reflect" if pad_mode == "reflect" else "constant"
            vv = jnp.pad(vv, ((0, 0), (p, p)), mode=mode)
        n_frames = 1 + (vv.shape[-1] - n_fft) // hop_length
        idx = (jnp.arange(n_fft)[:, None]
               + hop_length * jnp.arange(n_frames)[None, :])
        fr = jnp.transpose(vv[..., idx], (0, 2, 1)) * win
        norm = "ortho" if normalized else "backward"
        if jnp.issubdtype(fr.dtype, jnp.complexfloating):
            out = jnp.fft.fft(fr, axis=-1, norm=norm)
        elif onesided:
            out = jnp.fft.rfft(fr, axis=-1, norm=norm)
        else:
            out = jnp.fft.fft(fr.astype(
                jnp.complex128 if fr.dtype == jnp.float64 else jnp.complex64),
                axis=-1, norm=norm)
        out = jnp.transpose(out, (0, 2, 1))     # [B, freq, n_frames]
        return out[0] if v.ndim == 1 else out
    return run


def stft(x, n_fft, hop_length=None, win_length=None, window=None,
         center=True, pad_mode="reflect", normalized=False, onesided=True,
         name=None):
    """Short-time Fourier transform. Returns [batch, n_fft//2+1 | n_fft,
    num_frames] (batch dim squeezed for 1-D input), complex dtype."""
    x_rank = len(x.shape)
    if x_rank not in (1, 2):
        raise ValueError(
            f"x should be a 1D or 2D real tensor, got rank {x_rank}")
    seq = x.shape[-1]
    if not 0 < n_fft <= seq:
        raise ValueError(f"n_fft should be in (0, {seq}], got {n_fft}")
    if hop_length is None:
        hop_length = n_fft // 4
    if hop_length <= 0:
        raise ValueError(f"hop_length should be > 0, got {hop_length}")
    if win_length is None:
        win_length = n_fft
    if not 0 < win_length <= n_fft:
        raise ValueError(
            f"win_length should be in (0, {n_fft}], got {win_length}")
    if center and pad_mode not in ("constant", "reflect"):
        raise ValueError(
            f'pad_mode should be "reflect" or "constant", got "{pad_mode}"')
    xdt = jnp.result_type(x._value if isinstance(x, Tensor) else x)
    if onesided and jnp.issubdtype(xdt, jnp.complexfloating):
        # reference signal.py: a complex spectrum is not Hermitian — the
        # one-sided half would be unrecoverable
        raise ValueError(
            "onesided should be False when input is a complex Tensor")
    w = window if window is not None else \
        Tensor(jnp.ones((win_length,), jnp.float32))
    wv = w._value if isinstance(w, Tensor) else jnp.asarray(w)
    if wv.ndim != 1 or wv.shape[0] != win_length:
        raise ValueError(
            f"expected a 1D window of size win_length({win_length}), "
            f"got shape {tuple(wv.shape)}")
    exec_fn = _stft_exec(n_fft, hop_length, center, pad_mode, normalized,
                         onesided)
    return op_call("stft", exec_fn, x,
                   w if isinstance(w, Tensor) else Tensor(wv))


@functools.lru_cache(maxsize=64)
def _istft_exec(n_fft, hop_length, center, normalized, onesided, length,
                return_complex):
    @jax.jit
    def run(v, win):
        vv = v if v.ndim == 3 else v[None]
        n_frames = vv.shape[-1]
        if win.shape[0] < n_fft:
            pl = (n_fft - win.shape[0]) // 2
            win = jnp.pad(win, (pl, n_fft - win.shape[0] - pl))
        fr = jnp.transpose(vv, (0, 2, 1))        # [B, n_frames, freq]
        norm = "ortho" if normalized else "backward"
        if return_complex:
            out = jnp.fft.ifft(fr, axis=-1, norm=norm)
        else:
            if not onesided:
                fr = fr[..., : n_fft // 2 + 1]
            out = jnp.fft.irfft(fr, n=n_fft, axis=-1, norm=norm)
        out = out * win
        pos = (hop_length * jnp.arange(n_frames)[:, None]
               + jnp.arange(n_fft)[None, :])
        seq = (n_frames - 1) * hop_length + n_fft
        sig = jnp.zeros(out.shape[:1] + (seq,), out.dtype)
        sig = sig.at[:, pos].add(out)
        env = jnp.zeros((seq,), win.dtype).at[pos].add(
            jnp.broadcast_to(win * win, (n_frames, n_fft)))
        if length is None:
            if center:
                sig = sig[:, n_fft // 2: -(n_fft // 2)]
                env = env[n_fft // 2: -(n_fft // 2)]
        else:
            start = n_fft // 2 if center else 0
            sig = sig[:, start: start + length]
            env = env[start: start + length]
        envmin = jnp.min(jnp.abs(env))
        # NOLA-degenerate bins divide by ~0 — clamp so traced callers (where
        # the eager-only hard NOLA error in istft() can't fire) get finite
        # output instead of silent inf/nan; a healthy envelope is untouched.
        env = jnp.where(jnp.abs(env) > 1e-11, env, jnp.ones_like(env))
        sig = sig / env
        return (sig[0] if v.ndim == 2 else sig), envmin
    return run


def istft(x, n_fft, hop_length=None, win_length=None, window=None,
          center=True, normalized=False, onesided=True, length=None,
          return_complex=False, name=None):
    """Inverse STFT with window-envelope normalization and the NOLA check
    (reference signal.py istft)."""
    x_rank = len(x.shape)
    if x_rank not in (2, 3):
        raise ValueError(
            f"x should be a 2D or 3D complex tensor, got rank {x_rank}")
    if hop_length is None:
        hop_length = n_fft // 4
    if win_length is None:
        win_length = n_fft
    if not 0 < hop_length:
        raise ValueError(f"hop_length should be > 0, got {hop_length}")
    if not 0 < win_length <= n_fft:
        raise ValueError(
            f"win_length should be in (0, {n_fft}], got {win_length}")
    if return_complex and onesided:
        raise ValueError("onesided should be False when return_complex=True")
    fft_size = x.shape[-2]
    expected = n_fft // 2 + 1 if onesided else n_fft
    if fft_size != expected:
        raise ValueError(
            f"fft_size (dim -2) should be {expected} for n_fft={n_fft}, "
            f"onesided={onesided}; got {fft_size}")
    w = window if window is not None else \
        Tensor(jnp.ones((win_length,), jnp.float32))
    wv = w._value if isinstance(w, Tensor) else jnp.asarray(w)
    if wv.ndim != 1 or wv.shape[0] != win_length:
        raise ValueError(
            f"expected a 1D window of size win_length({win_length}), "
            f"got shape {tuple(wv.shape)}")
    exec_fn = _istft_exec(n_fft, hop_length, center, normalized, onesided,
                          length, return_complex)
    sig, envmin = op_call("istft", exec_fn, x,
                          w if isinstance(w, Tensor) else Tensor(wv))
    ev = envmin._value if isinstance(envmin, Tensor) else envmin
    # The hard NOLA error is EAGER-ONLY: under jit/compiled pipelines envmin
    # is a tracer, and the jitted body instead clamps degenerate envelope
    # bins to 1 so traced callers degrade gracefully (finite output).
    if not isinstance(ev, jax.core.Tracer):
        if float(ev) < 1e-11:
            raise ValueError(
                "Abort istft: Nonzero Overlap Add (NOLA) condition "
                "failed (see scipy.signal.check_NOLA)")
    return sig
