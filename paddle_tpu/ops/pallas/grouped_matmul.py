"""Pallas TPU grouped matmul for groups of FEW rows: whole-K, wide-N weight
tiles.

``out[i] = xs[i] @ w[g(i)]`` for rows sorted by group, ``rows[g]`` of them in
group g — what ``jax.lax.ragged_dot`` computes, and the megablox algorithm
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): group offsets ride scalar
prefetch and drive the index maps, one grid visit per (row tile, group) pair
that HAS rows, a row tile that two groups share visited once for each with
the other's rows masked out of the store.  What differs is the tile.  XLA's
own lowering of ``ragged_dot`` (its tiling is not ours to set) reads a serving
batch's experts through 128 KB weight tiles — (tm, tk, tn) = (64, 512, 128)
for 704 rows over 128 experts of 1024 x 2688: ~5,000 grid steps a call, each
moving 0.16 us of HBM under a step that costs twice that (PERF.md section 6,
PR 34).  Here a weight tile is the WHOLE K by a wide N (`tiles`: the widest
column block under 6 MiB — at those widths an expert's whole matrix, 5.5 MB
in one contiguous DMA), so a call is one or two hundred steps and there is
no accumulation across steps.  On the chip (perf/grouped_matmul_probe.py,
PR 34) the decode shape's product runs at 90 % of its weight-read roofline
where XLA's runs at 37 %, and a 1,024-token chunk's in a third of the time.

Grid: (N / tn, visits), the column block OUTER: the visits of one group are
consecutive, so a group's weight block is fetched once a column block however
many row tiles it spans, and a group with no row is never visited — it costs
no weight DMA.  The visit count is static, ``M / tm + G - 1`` (every row tile
once, and once more for every group boundary that could fall inside one);
the visits past the counted ones name the block already resident and do
nothing.  The row tiles are re-read once a column block, which is why this
is for few rows a group: at 8,192 rows a group the rows outweigh the weights
and XLA's 512-cubed tiles are right.

Rows past ``sum(rows)`` belong to no group and hold whatever was there (a
row tile no group reaches is never written), as after ``ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "grouped_matmul_ref", "tiles", "weight_visits",
           "TRACE_LABEL"]

# what a device trace finds a grouped product by, whoever computes it: XLA
# writes it among the frontend attributes of its own ``%ragged-dot-*``
# kernel, this kernel inside its ``kernel_metadata`` (an "XLA Ops" event's
# name is the instruction's whole text)
TRACE_LABEL = "ragged_dot_tiling="

_VMEM_LIMIT = 64 << 20
_WEIGHT_TILE_BYTES = 6 << 20
_LANES = 128
# rows a group (the static row bound over the groups) up to which the row
# tile is 64, and up to which this kernel is used at all.  Measured on the
# chip (perf/grouped_matmul_probe.py --sweep, PERF.md section 5): at 5 rows a
# group row tiles of 32 and 64 read alike (16 is 2 % slower), at 88 a tile of
# 128 beats 64 and 256 by 5 %, at 176 it equals 256 and the kernel takes 36 %
# of XLA's time; past that nothing is measured, and at a train step's 1,024
# the rows outweigh the weights
_FEW_ROWS, _MOST_ROWS = 32, 256


def tiles(m, k, n, groups, itemsize=2):
    """(tm, tn) this kernel takes for ``[m, k] x [groups, k, n]``, or None
    where it should not be used — a function of the static shapes alone: the
    row bound a group says how many rows a tile can expect to use."""
    per_group = m // groups
    # python ints off array shapes  # graftlint: disable=TRACE001
    if per_group > _MOST_ROWS or k % _LANES or n % _LANES:
        return None
    tm = 64 if per_group <= _FEW_ROWS else 128  # graftlint: disable=TRACE001
    if m % tm:  # graftlint: disable=TRACE001
        return None
    blocks = n // _LANES
    tn = max(_LANES * b for b in range(1, blocks + 1)
             if blocks % b == 0
             and (b == 1 or k * _LANES * b * itemsize <= _WEIGHT_TILE_BYTES))
    return tm, tn


def _group_tiles(rows, m, tm):
    """rows int32 [G] -> (offsets int32 [G + 1] of the groups' rows, cut at
    m; first row tile of each group; row tiles each group has rows in)."""
    ends = jnp.minimum(jnp.cumsum(rows.astype(jnp.int32)), m)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = offsets[:-1] // tm
    spans = jnp.where(ends > offsets[:-1],
                      (ends - 1) // tm - first + 1, 0)
    return offsets, first, spans


def weight_visits(rows, m, tm):
    """The (row tile, group) pairs that have rows: the grid visits a column
    block of weights costs, int32 scalar.  Over the groups that have rows it
    is 1.0 when no group straddles a row tile."""
    return _group_tiles(rows, m, tm)[2].sum()


def _visit_plan(rows, m, tm):
    """The scalar-prefetch operands: (group of each visit [V], row tile of
    each visit [V], offsets [G + 1], counted visits [1]), V = m / tm + G - 1.
    A visit past the counted ones repeats the last counted one's blocks."""
    groups = rows.shape[0]
    offsets, first, spans = _group_tiles(rows, m, tm)
    stop = jnp.cumsum(spans)                  # visits up to and with group g
    counted = stop[-1]
    v = jnp.minimum(jnp.arange(m // tm + groups - 1, dtype=jnp.int32),
                    jnp.maximum(counted - 1, 0))
    # the first group whose visits reach past v, by comparison
    gid = jnp.minimum((stop[None, :] <= v[:, None]).sum(1, dtype=jnp.int32),
                      groups - 1)
    tid = first[gid] + v - (stop[gid] - spans[gid])
    return gid, tid, offsets, counted.reshape(1)


def _gmm_kernel(gid_ref, tid_ref, offsets_ref, counted_ref, x_ref, w_ref,
                o_ref, *, tm):
    v = pl.program_id(1)

    @pl.when(v < counted_ref[0])
    def _visit():
        g = gid_ref[v]
        acc = jnp.dot(x_ref[...], w_ref[0],
                      preferred_element_type=jnp.float32)
        row = tid_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        # another group's rows of this tile stay as its own visit left them
        # (the block is resident from one visit of a row tile to the next)
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)) \
            .astype(o_ref.dtype)


# jitted: a model calls it for every expert layer, and inside an outer jit
# the calls that agree in shapes and statics are traced and lowered ONCE (a
# pallas_call lowers in ~35 ms of Python whether or not the executable then
# comes from the compile cache: 880 call sites in `serve_reason_c64`'s 44
# executables were 27 s of every set-up; PERF.md section 6, PR 34)
@functools.partial(jax.jit, static_argnames=("tm", "tn", "role", "out_dtype",
                                             "interpret"))
def grouped_matmul(xs, w, rows, *, tm=None, tn=None, role=None,
                   out_dtype=None, interpret=False):
    """xs [M, K] sorted by group, w [G, K, N], rows int32 [G] (sum <= M) ->
    [M, N] in xs's dtype (or ``out_dtype``): products in the operands' dtype,
    accumulated in float32.  ``tm`` / ``tn`` default to `tiles`'; ``role``
    ("decode" | "prefill") goes into the trace label beside the tiling."""
    m, k = xs.shape
    groups, _, n = w.shape
    if tm is None or tn is None:
        chosen = tiles(m, k, n, groups, xs.dtype.itemsize)
        if chosen is None:
            raise ValueError(
                f"no tiling for [{m}, {k}] x [{groups}, {k}, {n}]: use "
                f"jax.lax.ragged_dot")
        tm, tn = tm or chosen[0], tn or chosen[1]
    if m % tm or n % tn:
        raise ValueError(f"tiles ({tm}, {tn}) do not divide [{m}, {n}]")
    gid, tid, offsets, counted = _visit_plan(rows, m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, gid.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, gid, tid, *_: (tid[v], 0)),
            pl.BlockSpec((1, k, tn),
                         lambda j, v, gid, tid, *_: (gid[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, v, gid, tid, *_: (tid[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or xs.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row tile's block carries from one group's visit to the next
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        metadata={"kernel": "grouped_matmul",
                  "tiling": f"{TRACE_LABEL}{tm},{k},{tn}",
                  **({"role": role} if role else {})},
    )(gid, tid, offsets, counted, xs, w.astype(xs.dtype))


def grouped_matmul_ref(xs, w, rows, out_dtype=None):
    """The same product by ``jax.lax.ragged_dot`` (XLA's lowering)."""
    return jax.lax.ragged_dot(
        xs, w.astype(xs.dtype), rows.astype(jnp.int32),
        preferred_element_type=out_dtype or xs.dtype)
