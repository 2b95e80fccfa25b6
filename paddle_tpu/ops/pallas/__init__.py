"""Pallas TPU kernel overrides.

The PD_REGISTER_KERNEL(..., GPU, ...) analog: `register_all()` registers
Pallas implementations for hot ops under the same op names the functional API
dispatches through (kernel_registry.h:196 → core/dispatch.py registry).  It
runs lazily, on the first kernel lookup (probing the platform initialises a
backend, which must not happen at import), registers only when a TPU is
among `jax.devices()`, and lets a failing device probe raise: on the CPU the
jnp defaults run.  Off the chip the kernels are covered twice — numerics via
`interpret=True` parity tests, and the Mosaic lowering itself by compiling
for a described v5e (tests/test_chip_compile.py); interpret mode alone
cannot see a block shape or a VMEM budget the chip's compiler refuses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import register_kernel
from . import flash_attention as fa_mod
from . import paged_attention as pa_mod

__all__ = ["register_all", "flash_attention",
           "ragged_paged_attention_decode"]

flash_attention = fa_mod.flash_attention
ragged_paged_attention_decode = pa_mod.ragged_paged_attention_decode


# the kernel module owns its jnp reference (graftlint PAR001: every Pallas
# kernel pairs with a `*_ref` in its own module)
_naive_sdpa = lambda q, k, v, causal, window=None: \
    fa_mod.flash_attention_ref(q, k, v, causal=causal, window=window)


def _softmax_pallas(x, *, axis=-1, cast_dtype=None):
    from . import fused
    from ... import flags as _flags
    if cast_dtype is not None:
        x = x.astype(cast_dtype)
    # flag read at CALL time so toggling works after first registration
    if _flags.get_flag("use_pallas_norm_kernels") and axis in (-1, x.ndim - 1):
        out = fused.softmax(x)
        if out is not None:
            return out
    return jax.nn.softmax(x, axis=axis)


def _layer_norm_pallas(x, *rest, n_axes=1, epsilon=1e-5):
    from . import fused
    from ... import flags as _flags
    if _flags.get_flag("use_pallas_norm_kernels") and n_axes == 1 \
            and len(rest) == 2:
        out = fused.layer_norm(x, rest[0], rest[1], eps=epsilon)
        if out is not None:
            return out
    # flag off / unaffine / multi-axis / untileable: the shared jnp fallback
    from ...nn.functional.norm import layer_norm_ref
    return layer_norm_ref(x, rest[0] if rest else None,
                          rest[1] if len(rest) > 1 else None, n_axes, epsilon)


def _rms_norm_pallas(x, *rest, epsilon=1e-6):
    from . import fused
    if rest:
        out = fused.rms_norm(x, rest[0], eps=epsilon)
        if out is not None:
            return out
    # unweighted or untileable: the shared jnp fallback (XLA fuses it anyway)
    from ...nn.functional.norm import rms_norm_ref
    return rms_norm_ref(x, rest[0] if rest else None, epsilon)


def _fa_varlen(q, k, v, seg, causal=False, rate=0.0, seed=None):
    """Segment-masked (varlen) flash attention, optionally with in-kernel
    dropout; None on unsupported shapes so the caller's block-diagonal XLA
    fallback runs."""
    return fa_mod.flash_attention(q, k, v, causal=causal, segment_ids=seg,
                                  dropout_rate=rate, dropout_seed=seed)


def _fa_plain(q, k, v):
    out = fa_mod.flash_attention(q, k, v, causal=False)
    return out if out is not None else _naive_sdpa(q, k, v, False)


def _fa_dropout(q, k, v, seed, rate=0.1, causal=False):
    """Attention-probability dropout INSIDE the flash kernel (the mask is
    regenerated per block from `seed`, never materialized) — keeps
    dropout-training attention off the [B,H,S,S]-materializing XLA path.
    Falls back to the fused-softmax XLA path on unsupported shapes."""
    out = fa_mod.flash_attention(q, k, v, causal=causal, dropout_rate=rate,
                                 dropout_seed=seed)
    if out is not None:
        return out
    from ...nn.functional.attention import _sdpa_ref
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.int32))
    return _sdpa_ref(q, k, v, dropout=rate, causal=causal, dropout_key=key)


def _fa_causal(q, k, v, window=None):
    out = fa_mod.flash_attention(q, k, v, causal=True, window=window)
    return out if out is not None else _naive_sdpa(q, k, v, True, window)


_registered = [False]


def register_all(force=False):
    """Register Pallas overrides (TPU backend only unless force)."""
    if _registered[0]:
        return
    if not (force or any(d.platform == "tpu" for d in jax.devices())):
        return
    register_kernel("flash_attention", impl="pallas")(_fa_plain)
    register_kernel("flash_attention_causal", impl="pallas")(_fa_causal)
    register_kernel("flash_attention_dropout", impl="pallas")(_fa_dropout)
    register_kernel("rms_norm", impl="pallas")(_rms_norm_pallas)
    register_kernel("flash_attention_varlen", impl="pallas")(_fa_varlen)
    # softmax/layer_norm kernels are opt-in (FLAGS_use_pallas_norm_kernels,
    # checked at CALL time inside the impls): XLA's own fusion measured
    # faster inside full models on v5e (bench r3: ViT-L 239→211 img/s)
    register_kernel("softmax", impl="pallas")(_softmax_pallas)
    register_kernel("layer_norm", impl="pallas")(_layer_norm_pallas)
    from .fused import adamw_update

    def _adamw_gated(*args, **kw):
        # opt-in (FLAGS_use_pallas_adamw, read at CALL time): XLA's own
        # fused elementwise chain measured ~2% faster end-to-end on v5e
        # (round-4 ablation H); None routes the optimizer to its jnp path
        from ... import flags as _flags
        if not _flags.get_flag("use_pallas_adamw"):
            return None
        return adamw_update(*args, **kw)

    register_kernel("adamw_fused", impl="pallas")(_adamw_gated)
    _registered[0] = True
