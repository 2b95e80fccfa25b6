"""The selective state-space recurrence of a Mamba-2 layer (SSD, "state space
duality"), in the two forms a serving path needs:

  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,      y_t = h_t C_t

with A a negative scalar a head, x_t [heads, P], B_t / C_t [groups, N] shared
by the heads of a group, h [heads, P, N] (float32 whatever the inputs are).
The caller adds the skip term ``D x_t``, the gate and the norm.

`ssd_chunked_scan` is the prefill form: the sequence is cut into chunks of
``chunk`` tokens; INSIDE a chunk the outputs are masked ``[chunk, chunk]``
products a head (what the matrix unit is for), BETWEEN chunks only the state
passes on, in a loop of T / chunk steps.  `ssm_decode_update` is the decode
form: one token a slot, the state read and written once.  Both are plain
``jax.numpy``; a token whose ``dt`` is 0 leaves the state as it was (decay
1, no input), which is how padding and dead slots are masked.  Each runs
under its own region (`profiler.device_span`: ``ssm.chunked_scan``, the
loop between chunks included, and ``ssm.decode_update``), which is how a
device trace finds them: XLA's fusions of plain ``jax.numpy`` have no other
name.

Mamba-1's recurrence (`selective_scan`, `selective_update`) is another rule:

  s_t[d, n] = exp(dt_t[d] A[d, n]) s_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n],
  y_t[d] = sum_n s_t[d, n] C_t[n]

a decay a CHANNEL and state index (no heads, no groups), which the chunked
form above cannot express (its decay is a scalar a head, so a chunk's
[Q, Q] mask is shared by a head's channels; here it would be one a channel
and state index).  The state is held ``[N, D]`` — the N state indices major,
the D channels on the lanes: N = 16 as a minor dimension would be padded to
a lane tile of 128, eight times the bytes in memory and in every pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..profiler import device_span

__all__ = ["ssd_chunked_scan", "ssm_decode_update", "selective_scan",
           "selective_update"]


@device_span("ssm.chunked_scan")
def ssd_chunked_scan(x, dt, a, b, c, h0, chunk: int = 128):
    """x [T, heads, P], dt [T, heads] (after softplus; 0 = the token is
    padding), a [heads] (negative), b / c [T, groups, N], h0 [heads, P, N]
    float32 -> (y [T, heads, P] in x's dtype, h after the last token,
    float32).  T is padded to whole chunks here (with dt 0)."""
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    rep = heads // groups
    pad = -t % chunk
    x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                   for v in (x, dt, b, c))
    nc = (t + pad) // chunk
    f32 = jnp.float32
    xc = x.reshape(nc, chunk, heads, p)
    dtc = dt.astype(f32).reshape(nc, chunk, heads)
    bc = b.reshape(nc, chunk, groups, n)
    cc = c.reshape(nc, chunk, groups, n)
    # log-decay from the chunk's start up to and including each token
    cum = jnp.cumsum(dtc * a.astype(f32), axis=1)            # [nc, Q, heads]
    total = cum[:, -1]                                       # [nc, heads]

    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    scores = jnp.einsum("cign,cjgn->cgij", cc, bc,
                        preferred_element_type=f32)          # [nc, G, Q, Q]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal[None, None], cum.transpose(0, 2, 1)[:, :, :, None]
        - cum.transpose(0, 2, 1)[:, :, None, :], -jnp.inf))  # [nc, H, Q, Q]
    mix = decay.reshape(nc, groups, rep, chunk, chunk) \
        * scores[:, :, None] \
        * dtc.transpose(0, 2, 1).reshape(nc, groups, rep, 1, chunk)
    y = jnp.einsum("chij,cjhp->cihp",
                   mix.reshape(nc, heads, chunk, chunk).astype(x.dtype), xc,
                   preferred_element_type=f32)

    # what each chunk adds to the state by its end
    to_end = jnp.exp(total[:, None] - cum) * dtc             # [nc, Q, heads]
    xw = (xc.astype(f32) * to_end[..., None]).astype(x.dtype)
    added = jnp.einsum(
        "cjgrp,cjgn->cgrpn", xw.reshape(nc, chunk, groups, rep, p), bc,
        preferred_element_type=f32).reshape(nc, heads, p, n)

    # between chunks: only the state passes on
    def carry(h, inp):
        tot, add = inp
        return h * jnp.exp(tot)[:, None, None] + add, h      # h BEFORE chunk

    h_last, h_in = jax.lax.scan(carry, h0.astype(f32), (total, added))
    y = y + jnp.einsum(
        "cgrpn,cign->cigrp",
        h_in.reshape(nc, groups, rep, p, n).astype(x.dtype), cc,
        preferred_element_type=f32).reshape(nc, chunk, heads, p) \
        * jnp.exp(cum)[..., None]
    return y.reshape(nc * chunk, heads, p)[:t].astype(x.dtype), h_last


@device_span("ssm.decode_update")
def ssm_decode_update(h, x, dt, a, b, c):
    """One token a slot.  h [S, heads, P, N] (its own dtype, float32 as
    served), x [S, heads, P], dt [S, heads] (0 = leave the slot's state as
    it was), a [heads], b / c [S, groups, N] -> (y [S, heads, P] in x's
    dtype, h' in h's dtype).  The arithmetic is float32."""
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    rep = heads // groups
    f32 = jnp.float32
    dt = dt.astype(f32)
    bh = jnp.repeat(b.astype(f32), rep, axis=1)              # [S, heads, N]
    ch = jnp.repeat(c.astype(f32), rep, axis=1)
    new = h.astype(f32) * jnp.exp(dt * a.astype(f32))[:, :, None, None] \
        + (dt[:, :, None] * x.astype(f32))[..., None] * bh[:, :, None, :]
    y = (new * ch[:, :, None, :]).sum(-1)
    return y.astype(x.dtype), new.astype(h.dtype)


SCAN_BLOCK = 8      # tokens a loop step of `selective_scan`: a sublane tile


@device_span("ssm.selective_scan")
def selective_scan(u, dt, a, b, c, s0):
    """A run of T tokens of ONE sequence.  u, dt [T, D] (dt after softplus;
    0 = the token is padding), a [D, N] (negative), b / c [T, N], s0 [N, D]
    float32 -> (y [T, D] float32, s after the last token [N, D] float32).
    Sequential over tokens, ``SCAN_BLOCK`` of them a loop step (their rows
    are read and y's written a whole sublane tile at a time); the arithmetic
    is float32 whatever the inputs are."""
    t, d = u.shape
    n = a.shape[1]
    f32 = jnp.float32
    pad = -t % SCAN_BLOCK
    u, dt, b, c = (jnp.pad(v.astype(f32), ((0, pad), (0, 0)))
                   for v in (u, dt, b, c))
    nb = (t + pad) // SCAN_BLOCK
    at = a.astype(f32).T                                     # [N, D]
    blocks = (dt.reshape(nb, SCAN_BLOCK, d),
              (dt * u).reshape(nb, SCAN_BLOCK, d),
              b.reshape(nb, SCAN_BLOCK, n), c.reshape(nb, SCAN_BLOCK, n))

    def step(s, blk):
        dt_b, dtu_b, b_b, c_b = blk
        ys = []
        for i in range(SCAN_BLOCK):
            s = jnp.exp(dt_b[i][None, :] * at) * s \
                + b_b[i][:, None] * dtu_b[i][None, :]
            ys.append((s * c_b[i][:, None]).sum(0))
        return s, jnp.stack(ys)

    s, y = jax.lax.scan(step, s0.astype(f32), blocks)
    return y.reshape(nb * SCAN_BLOCK, d)[:t], s


@device_span("ssm.selective_update")
def selective_update(s, u, dt, a, b, c):
    """One token a slot.  s [S, N, D] (its own dtype, float32 as served),
    u, dt [S, D] (dt 0 = leave the slot's state as it was), a [D, N],
    b / c [S, N] -> (y [S, D] float32, s' in s's dtype): the state read and
    written once.  The arithmetic is float32."""
    f32 = jnp.float32
    dt, u = dt.astype(f32), u.astype(f32)
    new = jnp.exp(dt[:, None, :] * a.astype(f32).T[None]) * s.astype(f32) \
        + b.astype(f32)[:, :, None] * (dt * u)[:, None, :]
    y = (new * c.astype(f32)[:, :, None]).sum(1)
    return y, new.astype(s.dtype)
