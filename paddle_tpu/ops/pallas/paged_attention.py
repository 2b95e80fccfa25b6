"""Pallas TPU ragged paged attention — ONE kernel for every serving path.

The TPU-native analog of the reference's `block_multihead_attention`
serving kernel (paddle/phi/kernels/fusion/gpu/block_multi_head_attention*)
in the shape Ragged Paged Attention (arxiv 2604.15464) describes: the KV
cache lives in fixed-size PAGES of `page_size` tokens, each sequence owns a
per-sequence page table, and each slot contributes a RAGGED QUERY SEGMENT
`(q_start, q_len, kv_len)` — `q_len` fresh query tokens whose absolute
positions are `q_start .. q_start + q_len - 1`, attending the slot's paged
context under an intra-segment causal mask.  The three serving dispatch
shapes are all special cases of the one grid:

  q_len = 1        decode       (the new token attends everything before it)
  q_len = K+1      spec verify  (pending + K draft tokens, causal between)
  q_len = chunk    chunked prefill (one chunk of the prompt, causal over
                                    cached context + earlier chunk tokens)

so decode, verify, and chunked prefill score through the SAME kernel body
(and, off-TPU, the same `*_ref`) — the impl-uniformity the speculative
losslessness guarantee rests on.

Layout (what Mosaic lowers for the v5e — compile-tested at Llama-7B widths in
tests/test_chip_compile.py; every block and every copy spans the last two
dims of its array whole, the one shape rule the lowering never refuses):

  q          [S, Qmax, Hq, D]    ragged query segments, right-padded to Qmax;
                                 relaid OUTSIDE the kernel to kv-head-major
                                 rows [S, Hkv, R, D] (R = Qmax*rep rounded up
                                 to 8 sublanes; block (1, Hb, R, D)), and the
                                 output laid back
  k_pages    [L, Hkv, NP, ps, D] the WHOLE page pool, every layer of it (or
  v_pages    [L, Hkv, NP, ps, D] one layer [Hkv, NP, ps, D]); last two dims
                                 are the (sublane, lane) tile => D=128-friendly
  k/v_scales [L, Hkv, NP, ps]    this layer's passed as [1, Hkv, NP, 1, lanes]:
                                 a (1, ps) tile padded to whole lane tiles
  layer      scalar int32        which layer of the pool this call attends
  page_table [S, P] int32        physical page of each logical page slot
  q_start    [S]   int32         absolute position of query 0 per slot
  q_len      [S]   int32         valid queries per slot (0 = inactive)
  kv_len     [S]   int32         total valid KV tokens (incl. the segment)

Grid: (S, Hkv / Hb) — one step per slot and block of Hb kv heads, Hb every
head that fits VMEM (`_choose_heads`: all 8 at the 7B decode, verify and
512-query chunk shapes).  Inside a step a `fori_loop` runs over the slot's
LIVE pages only, ceil(kv_len / ps) of them, with the per-slot online-softmax
scratch carried across the pages.  K/V stay in HBM (`pl.ANY`): the page
table, the segment descriptors and the layer index ride scalar prefetch
(`pltpu.PrefetchScalarGridSpec`), and the kernel itself copies the PHYSICAL
(layer, head block, page) tile of each live page into one of two VMEM
buffers (`pltpu.make_async_copy`, every head of the block in one strided
copy), one page ahead of the update that reads it and across the boundary
to the next step's first page — the indirection costs no kernel time, and
the model's layer loop never slices a layer out of the pool (a slice is a
copy of that layer, every layer, every step).  GQA is native: the q block of
a step is, per kv head, the R rows (every query of the segment x the
`Hq // Hkv` heads sharing that kv head), and K/V pages are fetched once per
kv head, never materialized per q head.

An update — one (kv head, page) of a step's loop — is three MXU products,
and their operands are the page tiles in the dtype they were copied in
(PR 40): where the queries and the pages share a 2-byte dtype (bf16 in every
serving cell) the scores are ONE product of the bf16 [R, D] query block and
the bf16 [ps, D] K tile accumulated in f32 (a product of two bf16 values is
exact in f32), and the values TWO products against the bf16 V tile, of the
probabilities' two bf16 pieces (`_weighted_values`: p = p_hi + p_lo to
2^-17), added in f32.  No tile and no query block is widened to f32; mask,
max, exp, the row sums, m, l, the accumulator and the finalizer are f32.
Any other pairing of dtypes — f32 pages, a query wider than its pages, the
int8/fp8 body — runs its two products on f32 operands, the program it was
before (tests/test_pallas_kernels.py holds its jaxpr to the parent's text);
on the chip Mosaic runs such a product as ONE bf16 pass (its default
precision), so there the f32 path rounds p to 2^-9 and the 2-byte path is
the more exact one.  The rule is the operands' dtypes at trace time;
nothing selects it.  Measured on the chip (perf/shared_kv_probe.py,
perf/ragged_kernel_probe.py; PERF.md sections 5 and 6, PR 40), parent ->
this form: the one K/V store of `serve_longreason_c64` (64 slots, ~4.3 k
live tokens, ten 128-wide rows, page 128) 3.12 -> 2.22 ms a call, 1.44 ->
1.02 us a loop step, 55 -> 77 % of the HBM roofline (50 % at a page of 64,
89 % at 256); the chat cell's decode call 0.111 -> 0.072 ms, every slot at
2,048 tokens 0.573 -> 0.367 (0.239 with the math emptied); the 512-query
chunk 0.636 -> 0.652 (its second value product is real MXU time).  What sets
the pace is not the operands' width — one bf16 piece of p, or the two
stacked into one [2R, ps] operand, run as slowly as the f32 products did —
but how many products an update has: two run 1.44 us a step, three 1.02,
four 2.77.  Read with the compiled body (every K tile is latched
TRANSPOSED, `xpose.packed.bf16`): a score product holds its MXU ~260 cycles
whatever the page's rows, and with three products a head the ten heads'
score products fall on all four MXUs in turn where with two they fall on
two of them.  What is left is that latch: K tiles stored transposed, or K
streamed against a resident q (ROADMAP A17).

A page past a slot's `kv_len` costs nothing: no grid step, no copy, and its
table entry is never read (so the cache manager may leave anything there);
partial pages and the causal frontier are mask-tailed inside the kernel.
Padding query rows (>= q_len) and inactive slots (q_len = 0) produce exact
zeros, matching the reference.

int8/fp8 pages (`k_scales`/`v_scales`) dequantize INSIDE the kernel for
every path — the per-(page, head, token-row) scale pages ride the same
page-table indirection, applied to the scores and probabilities rather than
to K and V, so a dequantized K/V tile never exists at all.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention", "ragged_paged_attention_ref",
           "ragged_paged_attention_decode", "paged_attention_decode_ref",
           "paged_gather_kv", "paged_gather_scales"]

NEG_INF = -1e30
_SUBLANES = 8      # f32 sublane count: query-row blocks pad to this


def _attend_page(q, k, v, mask, sm_scale, h, m_scr, l_scr, acc_scr,
                 k_scale=None, v_scale=None):
    """One online-softmax update over one K/V page — shared by the plain
    and fused-dequant bodies so the accumulator math can never drift
    between them.  ``q``, ``k`` and ``v`` arrive either all in the pages'
    2-byte dtype or all float32 (`_ragged_kernel.attend` decides from the
    refs' dtypes); scores, probabilities and the running state are float32
    either way.  ``q`` is the [R, D] query-row block (row r = query
    r // rep, head r % rep of the kv group), ``mask`` the [R, ps] validity
    of each (query row, kv position) pair, ``h`` the kv head's place in
    the step's [Hb, R, 1] / [Hb, R, 1] / [Hb, R, D] running max, sum and
    accumulator (indexed, not viewed: Mosaic refuses a ref slice of a
    lane-padded [.., 1] scratch); a row
    with no valid position EVER (a padding query or a sublane-padding row)
    keeps m = NEG_INF and l = 0, so the finalizer emits exact zeros for it.

    ``k_scale``/``v_scale`` ([1, ps], quantized pages only) are the page's
    per-row dequant scales, applied on the [R, ps] score side — q·(k_t·s_t)
    == (q·k_t)·s_t and Σ p_t·(v_t·s_t) == Σ (p_t·s_t)·v_t — so the scale
    row broadcasts along sublanes in the layout it was DMA'd in and the
    dequantized K/V tile never exists, not even in VMEM."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale     # [R, ps]
    if k_scale is not None:
        s = s * k_scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[h]                                      # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # re-mask p explicitly: on a row whose every position is masked,
    # exp(NEG_INF - NEG_INF) would be 1, silently averaging garbage V rows
    # into the padding-query output
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_scr[h] = acc_scr[h] * alpha + _weighted_values(p, v)
    m_scr[h] = m_new


def _weighted_values(p, v):
    """p [R, ps] float32 times the V tile [ps, D], float32.  A 2-byte tile
    goes to the MXU as it is stored and ``p`` beside it to f32 rounding, as
    TWO pieces of the tile's dtype (p = p_hi + p_lo to 2^-17 relative in
    bfloat16), each its own product against the tile, added in f32.  One
    piece alone (what `_mla_kernel` feeds, and what an f32 product IS on
    the chip: Mosaic runs it as one bf16 pass unless told "highest") is p
    to 2^-9.  The second product costs no time at the decode and verify
    shapes — on the v5e the update with THREE products runs a third faster
    than with two, and the pieces stacked into one [2R, ps] operand run as
    slowly as one piece (PERF.md section 6, PR 40: the K tile's transposed
    latch sets the pace, and three products a head spread the heads'
    score products over the four MXUs).  An f32 tile takes the one f32
    product it took before."""
    product = lambda left: jax.lax.dot_general(
        left, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if v.dtype.itemsize != 2:
        return product(p)
    p_hi = p.astype(v.dtype)
    p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
    return product(p_hi) + product(p_lo)


def _segment_mask(shape, i, page_size, rep, q_start, q_len, kv_len):
    """[R, ps] validity of page i's positions against the slot's
    ragged segment: kv position `col` is visible to query row `r` (query
    index r // rep) iff it is causally before-or-at that query's absolute
    position, the query is real, and the position holds valid KV."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = i * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    qi = row // rep
    return (col <= q_start + qi) & (qi < q_len) & (col < kv_len)


def _ragged_kernel(pt_ref, qs_ref, ql_ref, kl_ref, ly_ref, q_ref, *refs,
                   page_size, sm_scale, rep, heads, table_width, quant):
    """One grid step = one (slot b, block of ``heads`` kv heads): a loop
    over the slot's LIVE pages only, one ``_attend_page`` per (head, page)
    in page order — the update sequence a query row sees does not depend on
    ``heads`` (tests/test_pallas_kernels.py holds every blocking BIT-equal).
    The pages stay in HBM (``pl.ANY``) and the kernel copies them itself,
    double-buffered: page c of the step lands in buffer (base + c) % 2 —
    every head of the block in ONE strided copy — and is sent for while
    page c - 1 is attended; the step's last update runs over the NEXT
    step's first copy, so a slot boundary waits out no whole DMA either.
    ``base`` (SMEM) passes from step to step; whether a step's first page
    is already on its way it reads off the two steps' live pages.

    ``refs``: the HBM sources (k, v — or k, k_scale, v, v_scale with
    ``quant``), the output block, one [2, heads, ...] buffer per source,
    the DMA semaphores [source, buffer], ``base``, and the three scratch
    refs.

    ``quant``: K/V pages arrive in their int8/fp8 STORAGE dtype plus a
    per-row f32 absmax scale tile ([1, ps] per head and page), and the
    dequant happens here, on the page tile already resident in VMEM —
    quantized K/V never materialize as an f32 tensor anywhere (DTYPE001
    polices the host-side paths).  The row scales are applied on the score
    side (see ``_attend_page``) — the same products as
    ``serving.quant.dequantize_kv``'s astype-f32-times-row-scale, in another
    association order, so the kernel matches the jnp gather paths to f32
    rounding — on EVERY dispatch path, not just decode."""
    n_src = 4 if quant else 2
    srcs, o_ref = refs[:n_src], refs[n_src]
    bufs = refs[n_src + 1:2 * n_src + 1]
    sem, base_ref, m_scr, l_scr, acc_scr = refs[2 * n_src + 1:]
    b, hb = pl.program_id(0), pl.program_id(1)
    n_hb = pl.num_programs(1)
    n_steps = pl.num_programs(0) * n_hb
    step = b * n_hb + hb

    def live_pages(slot):
        # a table is all a slot can own, whatever kv_len claims
        return pl.cdiv(jnp.minimum(kl_ref[slot], table_width * page_size),
                       page_size)

    def copies(slot, head_block, col, buf):
        """the copies of logical page ``col`` of (slot, head block) into
        buffer ``buf``: ``heads`` [ps, D] tiles (and lane-padded [1, ps]
        scale tiles) each, a layer and a page apart in the pool"""
        page = pt_ref[slot, col]
        first = head_block * heads
        for t, (src, dst) in enumerate(zip(srcs, bufs)):
            # the scale sources hold this call's layer only
            lead = 0 if quant and t % 2 else ly_ref[0]
            yield pltpu.make_async_copy(
                src.at[lead, pl.ds(first, heads), page], dst.at[buf],
                sem.at[t, buf])

    def send(*page):
        for c in copies(*page):
            c.start()

    @pl.when(step == 0)
    def _first():
        base_ref[0] = 0

    base = base_ref[0]
    n_live = live_pages(b)
    # a step with live pages sends for the first page of the step after it,
    # if that one has any
    prev, nxt = jnp.maximum(step - 1, 0), jnp.minimum(step + 1, n_steps - 1)
    has_next = (step + 1 < n_steps) & (live_pages(nxt // n_hb) > 0)
    on_its_way = (step > 0) & (live_pages(prev // n_hb) > 0)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((n_live > 0) & jnp.logical_not(on_its_way))
    def _cold():                  # no step before this one sent for page 0
        send(b, hb, 0, base)

    q_start, q_len, kv_len = qs_ref[b], ql_ref[b], kl_ref[b]
    rows = q_ref.shape[2]
    # 2-byte queries over 2-byte pages of the same dtype go to the MXU as
    # they are stored; any other pairing as f32
    native = (not quant and q_ref.dtype == bufs[0].dtype
              and q_ref.dtype.itemsize == 2)
    as_operand = (lambda x: x) if native \
        else (lambda x: x.astype(jnp.float32))

    def attend(c, carry):
        buf = (base + c) % 2

        @pl.when(c + 1 < n_live)
        def _ahead():
            send(b, hb, c + 1, 1 - buf)

        @pl.when((c + 1 == n_live) & has_next)
        def _next_step():
            send(nxt // n_hb, nxt % n_hb, 0, 1 - buf)

        for cp in copies(b, hb, c, buf):
            cp.wait()
        mask = _segment_mask((rows, page_size), c, page_size, rep, q_start,
                             q_len, kv_len)
        for h in range(heads):
            tiles = [as_operand(dst[buf, h]) for dst in bufs]
            scales = dict(k_scale=tiles[1][:, :page_size],
                          v_scale=tiles[3][:, :page_size]) if quant else {}
            _attend_page(as_operand(q_ref[0, h]), tiles[0],
                         tiles[n_src // 2], mask, sm_scale, h, m_scr, l_scr,
                         acc_scr, **scales)
        return carry

    jax.lax.fori_loop(0, n_live, attend, 0)
    base_ref[0] = (base + n_live) % 2
    for h in range(heads):
        l = l_scr[h]
        inv = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0, h] = (acc_scr[h] * inv).astype(o_ref.dtype)


# What the kernel may use of the v5e's 128 MiB of VMEM (Mosaic's scoped
# default is 16 MiB), and what `_choose_heads` lets its own count reach: the
# q / out blocks (double-buffered by the pipeline), the page buffers, the
# scratch with its lane-padded m / l columns and one update's f32
# temporaries.  The count runs high (8 heads of the 512-query chunk shape
# count 44 MiB and ran on the chip under a 32 MiB limit: PERF.md section 6,
# PR 30), so three quarters of the limit is its room.
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4
_LANES = 128


def _choose_heads(rows_pad, hkv, d, page_size, q_bytes, out_bytes, kv_bytes,
                  *, quant):
    """kv heads one grid step carries, from the call's shapes alone: the
    most that divide ``hkv`` and fit the budget.

    The algorithm is one — an online-softmax update per (head, page) — but
    what a step holds differs by two orders of magnitude between the
    dispatch shapes: at decode and verify a head's query rows are 8-24, at
    the 512-query chunk shape 2,048, where one head's q, out, accumulator
    and m / l columns count 5 MiB.  More heads a step are fewer, larger
    page copies and independent work for the scheduler: on the chip every
    shape gained with every doubling (PERF.md section 5)."""
    wide = max(page_size, _LANES)
    per_head = rows_pad * (2 * d * q_bytes + 2 * d * out_bytes   # q, out
                           + 4 * d + 2 * 4 * _LANES)             # acc, m, l
    per_head += 2 * 2 * page_size * d * kv_bytes                 # K, V x 2
    if quant:
        per_head += 2 * 2 * _SUBLANES * wide * 4                 # scale tiles
    update = 4 * (rows_pad * (d + 3 * wide) + 2 * page_size * d)
    return max([h for h in range(1, hkv + 1) if hkv % h == 0
                and h * per_head + update <= _VMEM_BUDGET] or [1])


def ragged_paged_attention(q, k_pages, v_pages, page_table, q_start, q_len,
                           kv_len, sm_scale=None, interpret=False,
                           out_dtype=None, k_scales=None, v_scales=None,
                           *, role=None, kind=None, layer=None,
                           _heads=None):
    """Ragged-segment paged attention over each slot's page list.

    q [S, Qmax, Hq, D], page_table [S, P] int32 (entries past a slot's
    pages are never read here; the ``*_ref`` gathers the whole table, so
    callers of both keep them in range), q_start / q_len / kv_len [S] int32
    -> o [S, Qmax, Hq, D].  Query j of slot s sits
    at absolute position q_start[s] + j and attends kv positions <= its own
    (and < kv_len[s]); rows past q_len[s] — and every row of a q_len = 0
    slot — come back exactly zero.  Requires Hq % Hkv == 0.

    k_pages/v_pages come in one of two forms:

      layer=None     [Hkv, NP, ps, D]: ONE layer's pages (the decode-shape
                     wrapper, the parity tests);
      layer=<int32>  [L, Hkv, NP, ps, D]: the WHOLE pool, and ``layer`` (a
                     traced scalar: the model's layer loop index) names the
                     layer this call attends.  It rides scalar prefetch
                     after the four descriptors and every page copy
                     reads ``pool[layer, head block, page_table[b, c]]``,
                     so the layer is picked by the page DMA itself.
                     Slicing ``pool[layer]`` outside instead hands XLA a
                     layer-sized copy per call (PERF.md section 6, PR 28).

    Same grid, masks and arithmetic either way (the 4-D form is the 5-D one
    with L = 1).

    Blocking (PR 30).  The grid is (S, Hkv / Hb): a step is one slot and
    Hb kv heads — every head that fits, chosen by ``_choose_heads`` from
    the shapes (8 of 8 at the 7B decode, verify and 512-query chunk shapes)
    — and loops over the slot's live pages, ceil(kv_len / ps) of them,
    copying each out of the pool itself (``pl.ANY`` operands, a
    double-buffered ``make_async_copy`` of all Hb heads of a page).  The
    table's dead columns cost nothing: no step, no copy, and no read of
    what the table holds there.  The per-row arithmetic does not depend on
    Hb.  An update's products take 2-byte queries and pages AS STORED
    (PR 40; the module docstring has the form and what the probes read);
    f32 pages take the f32 products.

    The custom call keeps a fixed outline that the benchmark's trace
    readers match by shape: its FIRST operand is the [S, P] page table and
    it has ONE array result [S, Hkv, rows_pad, D] — so no
    ``input_output_aliases`` here (a tuple result), and new scalars go
    AFTER the four that exist.

    out_dtype: output dtype (default q.dtype).  Accumulation is f32 either
    way; pass jnp.float32 with bf16 inputs to read the un-downcast result
    (the parity tests' bf16→f32 bound).

    k_scales/v_scales (both or neither): per-row absmax scale pages
    [Hkv, NP, ps] f32 ([L, Hkv, NP, ps] with ``layer``) for
    int8/fp8-quantized k_pages/v_pages — dequant then FUSES into the kernel
    (each page tile dequantizes in VMEM right before its online-softmax
    update; the f32 K/V never exist outside the kernel).  The scale pages
    ride the same page-table indirection as the data pages.

    role: a LABEL for profiler traces ("decode" | "chunk" | "verify", from
    the model fn that builds the call) — it changes no code path.  The
    call's HLO text, which is the name of its event on a device trace's
    "XLA Ops" line, carries ``kernel_metadata={"kernel":
    "ragged_paged_attention","role":...}``.

    kind: a second LABEL, after the role, for a model whose attention
    layers are of several kinds over stores of their own ("window" | "full"
    | "cross": `models/sambay.py`); None leaves the metadata as it was.

    _heads: Hb forced — for the tests that hold every blocking bit-equal
    and for perf/ragged_kernel_probe.py; callers leave it alone.

    Head-sharded (TP) dispatch: every shape here may be the mp-LOCAL
    shard — Hq = nh/tp query heads against Hkv = nkv/tp KV-head pages.
    Nothing in the kernel knows about the mesh: the grid, the GQA
    replication factor (rep = Hq // Hkv), and the block specs all derive
    from the operand shapes, so the tensor-parallel serving engine calls
    the SAME dispatch per rank inside shard_map that the single-chip
    engine calls globally.  Correctness of the local GQA pairing needs
    mp | nkv (then local q head j reads local kv head j // rep, exactly
    the global mapping restricted to rank r's contiguous head block) —
    the divisibility guard below enforces the local ratio, the builder
    (models/llama.build_llama_paged_decode) enforces mp | nkv.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if layer is None:
        # one layer's pages are a pool of one layer (a bitcast, no copy)
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    s_slots, qmax, hq, d = q.shape
    _l, hkv, _np_, page_size, _d = k_pages.shape
    n_ptab = page_table.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"num q heads ({hq}) must be a multiple of kv "
                         f"heads ({hkv})")
    rep = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    out_dtype = out_dtype or q.dtype
    quant = k_scales is not None

    # kv-head-major query rows: [S, Qmax, Hq, D] -> [S, Hkv, R, D] with row
    # r = query r // rep, head r % rep of the group, zero-padded to a
    # sublane multiple.  The (1, Hb, R, D) block then spans the array's last
    # two dims whole, which is what the Mosaic lowering accepts for any
    # rep/qmax (a (qmax, rep, D) block out of [.., Hq, D] is refused: rep
    # is neither a multiple of 8 nor Hq), and the kernel body needs no
    # relayout.  Padding rows have query index >= qmax >= q_len, so the
    # segment mask zeroes them like any padding query.
    rows = qmax * rep
    rows_pad = -(-rows // _SUBLANES) * _SUBLANES
    qr = q.reshape(s_slots, qmax, hkv, rep, d).transpose(0, 2, 1, 3, 4) \
        .reshape(s_slots, hkv, rows, d)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows_pad - rows), (0, 0)))

    if _heads is not None and hkv % _heads:
        raise ValueError(f"{_heads} heads a step do not divide {hkv}")
    heads = _heads or _choose_heads(
        rows_pad, hkv, d, page_size, q.dtype.itemsize,
        jnp.dtype(out_dtype).itemsize, k_pages.dtype.itemsize, quant=quant)

    q_spec = pl.BlockSpec((1, heads, rows_pad, d),
                          lambda b, h, pt, qs, ql, kl, ly: (b, h, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    if quant:
        # scale pages ride as [1, Hkv, NP, 1, lanes]: a (1, ps) tile per
        # head and page, lane-major like the scores it scales, padded to
        # whole lane tiles (a copy out of HBM moves nothing narrower).  That
        # is a relayout of the array, so it is made of THIS layer's scales
        # only (4 B a token row against D codes: a 64th of a layer of int8
        # data) — the data pages are never sliced
        lanes = -(-page_size // _LANES) * _LANES

        def layer_tiles(scales):
            tiles = jax.lax.dynamic_index_in_dim(
                scales, layer, 0, keepdims=True)[:, :, :, None, :]
            return jnp.pad(tiles, ((0, 0),) * 4 + ((0, lanes - page_size),))

        inputs = (qr, k_pages, layer_tiles(k_scales),
                  v_pages, layer_tiles(v_scales))
        buffers = [pltpu.VMEM((2, heads, page_size, d), k_pages.dtype),
                 pltpu.VMEM((2, heads, 1, lanes), jnp.float32)] * 2
    else:
        inputs = (qr, k_pages, v_pages)
        buffers = [pltpu.VMEM((2, heads, page_size, d), k_pages.dtype)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_slots, hkv // heads),
        in_specs=[q_spec] + [in_hbm] * (len(inputs) - 1),
        out_specs=q_spec,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((len(buffers), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, rows_pad, 1), jnp.float32),
            pltpu.VMEM((heads, rows_pad, 1), jnp.float32),
            pltpu.VMEM((heads, rows_pad, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, sm_scale=sm_scale, rep=rep,
        heads=heads, table_width=n_ptab, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, hkv, rows_pad, d),
                                       out_dtype),
        compiler_params=pltpu.CompilerParams(
            # which buffer is next passes from step to step: in order
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        metadata={"kernel": "ragged_paged_attention",
                  **({"role": role} if role else {}),
                  **({"kind": kind} if kind else {})},
    )(page_table.astype(jnp.int32), q_start.astype(jnp.int32),
      q_len.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *inputs)
    return out[:, :, :rows].reshape(s_slots, hkv, qmax, rep, d) \
        .transpose(0, 2, 1, 3, 4).reshape(s_slots, qmax, hq, d)


def paged_gather_kv(pages, page_table):
    """Gather a slot-major dense view [S, P*ps, Hkv, D] out of the page pool
    (pages [Hkv, NP, ps, D], page_table [S, P]) — the XLA fallback's (and
    the parity tests') dense reconstruction."""
    g = pages[:, page_table]                      # [Hkv, S, P, ps, D]
    hkv, s, p, ps, d = g.shape
    return g.transpose(1, 2, 3, 0, 4).reshape(s, p * ps, hkv, d)


def paged_gather_scales(scales, page_table):
    """Scale-page analog of :func:`paged_gather_kv`: [Hkv, NP, ps] pages +
    [S, P] table -> slot-major [S, P*ps, Hkv] per-row scales."""
    g = scales[:, page_table]                     # [Hkv, S, P, ps]
    hkv, s, p, ps = g.shape
    return g.transpose(1, 2, 3, 0).reshape(s, p * ps, hkv)


def ragged_paged_attention_ref(q, k_pages, v_pages, page_table, q_start,
                               q_len, kv_len, sm_scale=None, out_dtype=None,
                               k_scales=None, v_scales=None, *, layer=None):
    """jnp reference/fallback with identical semantics to the ragged
    kernel (gathers pages dense, masks causally inside each slot's
    segment, zeros padding query rows and q_len-0 slots; with
    k_scales/v_scales the gathered int8/fp8 rows dequantize by the same
    astype-f32-times-row-scale expression the kernel fuses).  This is the
    CPU path the serving engine dispatches for decode, verify, AND
    chunked prefill — one implementation per engine, every path.  Like
    the kernel it is head-shard agnostic: under TP serving each rank
    passes its mp-local Hq/Hkv shapes and the ref computes that rank's
    heads exactly (same guard, same local GQA pairing).  With ``layer``
    the pages (and scales) are the whole [L, ...] pool and the ref reads
    ``pool[layer]`` — the kernel's 5-D form, spelled the plain way."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scales is not None:
            k_scales, v_scales = k_scales[layer], v_scales[layer]
    s_slots, qmax, hq, d = q.shape
    hkv = k_pages.shape[0]
    page_size = k_pages.shape[2]
    if hq % hkv != 0:
        raise ValueError(f"num q heads ({hq}) must be a multiple of kv "
                         f"heads ({hkv})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    k = paged_gather_kv(k_pages, page_table)      # [S, T, Hkv, D]
    v = paged_gather_kv(v_pages, page_table)
    if k_scales is not None:
        ks = paged_gather_scales(k_scales, page_table)   # [S, T, Hkv]
        vs = paged_gather_scales(v_scales, page_table)
        k = k.astype(jnp.float32) * ks.astype(jnp.float32)[..., None]
        v = v.astype(jnp.float32) * vs.astype(jnp.float32)[..., None]
        # round to the QUERY's compute dtype before attending: on a bf16
        # engine every jnp consumer then sees identical rounded rows — the
        # engine's self-exactness across decode/verify/chunk/re-prefill
        # paths needs one value per stored row.  No-op at f32.  (The fused
        # TPU kernel keeps f32 dequant in VMEM — each engine runs ONE impl
        # on every path, so per-engine exactness holds.)
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    if hq != hkv:
        repn = hq // hkv
        k = jnp.repeat(k, repn, axis=2)
        v = jnp.repeat(v, repn, axis=2)
    s = jnp.einsum("sqhd,sthd->shqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    t_pos = jnp.arange(s.shape[-1])[None, None, None, :]
    qi = jnp.arange(qmax)[None, None, :, None]
    ok = (t_pos <= q_start[:, None, None, None] + qi) \
        & (qi < q_len[:, None, None, None]) \
        & (t_pos < kv_len[:, None, None, None])
    # NEG_INF (not -inf): a fully masked row softmaxes to uniform garbage
    # instead of NaN, and the q_len mask below zeroes it either way
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shqt,sthd->sqhd", p, v.astype(jnp.float32))
    o = jnp.where(jnp.arange(qmax)[None, :, None, None]
                  < q_len[:, None, None, None], o, 0.0)
    return o.astype(out_dtype or q.dtype)


def ragged_paged_attention_decode(q, k_pages, v_pages, page_table, lengths,
                                  sm_scale=None, interpret=False,
                                  out_dtype=None, k_scales=None,
                                  v_scales=None):
    """Decode-shape convenience wrapper: one query per slot (`q [S, Hq,
    D]`, `lengths [S]` = valid KV INCLUDING the freshly written token) is
    the `q_len = 1` special case of :func:`ragged_paged_attention` — kept
    as an API so callers with a flat decode batch don't hand-build the
    segment descriptors.  A slot with length 0 produces exact zeros."""
    lengths = lengths.astype(jnp.int32)
    o = ragged_paged_attention(
        q[:, None], k_pages, v_pages, page_table,
        jnp.maximum(lengths - 1, 0), (lengths > 0).astype(jnp.int32),
        lengths, sm_scale=sm_scale, interpret=interpret,
        out_dtype=out_dtype, k_scales=k_scales, v_scales=v_scales)
    return o[:, 0]


def paged_attention_decode_ref(q, k_pages, v_pages, page_table, lengths,
                               sm_scale=None, out_dtype=None, k_scales=None,
                               v_scales=None):
    """Decode-shape wrapper over :func:`ragged_paged_attention_ref` — the
    same `q_len = 1` specialization as the kernel-side wrapper, so the
    decode pair stays a pure delegation to the ONE ragged pair."""
    lengths = lengths.astype(jnp.int32)
    o = ragged_paged_attention_ref(
        q[:, None], k_pages, v_pages, page_table,
        jnp.maximum(lengths - 1, 0), (lengths > 0).astype(jnp.int32),
        lengths, sm_scale=sm_scale, out_dtype=out_dtype,
        k_scales=k_scales, v_scales=v_scales)
    return o[:, 0]


# ---------------------------------------------------------------------------
# The LATENT-page form: absorbed multi-head latent attention (MLA).
#
# A latent store keeps ONE row a token and layer, ``[c | r]`` (the compressed
# K/V latent and the one rotary key every head shares), in pages
# ``[L, 1, NP, ps, Dk]``.  In the absorbed form every query head scores the
# WHOLE row (Dk columns: q'_i . c + q_rope_i . r) and sums the row's first
# ``dv`` columns (c) under the probabilities; K and V per head never exist.
# Fed to the ragged kernel above as one kv head it would fetch every page
# twice (once as K, once as V) and Dk = 576 is no whole number of lane tiles
# for its V side.  Here a page is fetched ONCE and used as both.


__all__ += ["mla_paged_attention", "mla_paged_attention_ref"]


def _mla_kernel(pt_ref, qs_ref, ql_ref, kl_ref, ly_ref, q_ref, pool_ref,
                o_ref, buf, sem, base_ref, m_scr, l_scr, acc_scr, *,
                page_size, sm_scale, rep, group, table_width, dv):
    """One grid step = one slot (a ragged query segment): a loop over the
    slot's LIVE pages, ``group`` of them an update — the pages of a group
    land side by side in one [group * ps, Dk] buffer, each by its own copy
    out of the pool (``pl.ANY``), and are scored as one key tile of
    group * ps columns.  Double-buffered like `_ragged_kernel`: group g of
    the step lands in buffer (base + g) % 2 while group g - 1 is attended,
    and the step's last update runs over the next step's first copies.  A
    group's places past the slot's last live page are filled with that last
    page again (no read of a dead table column, no stale buffer) and masked
    by position."""
    b = pl.program_id(0)
    n_steps = pl.num_programs(0)

    def live_pages(slot):
        return pl.cdiv(jnp.minimum(kl_ref[slot], table_width * page_size),
                       page_size)

    def copies(slot, g, which):
        last = live_pages(slot) - 1
        for j in range(group):
            page = pt_ref[slot, jnp.minimum(g * group + j, last)]
            yield pltpu.make_async_copy(
                pool_ref.at[ly_ref[0], 0, page],
                buf.at[which, pl.ds(j * page_size, page_size)],
                sem.at[which, j])

    def send(*where):
        for c in copies(*where):
            c.start()

    @pl.when(b == 0)
    def _first():
        base_ref[0] = 0

    base = base_ref[0]
    n_live = pl.cdiv(live_pages(b), group)
    prev, nxt = jnp.maximum(b - 1, 0), jnp.minimum(b + 1, n_steps - 1)
    has_next = (b + 1 < n_steps) & (live_pages(nxt) > 0)
    on_its_way = (b > 0) & (live_pages(prev) > 0)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((n_live > 0) & jnp.logical_not(on_its_way))
    def _cold():
        send(b, 0, base)

    q_start, q_len, kv_len = qs_ref[b], ql_ref[b], kl_ref[b]
    q = q_ref[0]                                       # [rows, Dk]
    width = group * page_size

    def attend(g, carry):
        which = (base + g) % 2

        @pl.when(g + 1 < n_live)
        def _ahead():
            send(b, g + 1, 1 - which)

        @pl.when((g + 1 == n_live) & has_next)
        def _next_step():
            send(nxt, 0, 1 - which)

        for cp in copies(b, g, which):
            cp.wait()
        rows_kv = buf[which]                           # [group * ps, Dk]
        mask = _segment_mask((q.shape[0], width), g, width, rep, q_start,
                             q_len, kv_len)
        s = jax.lax.dot_general(
            q, rows_kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(rows_kv.dtype), rows_kv[:, :dv],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_live, attend, 0)
    base_ref[0] = (base + n_live) % 2
    l = l_scr[...]
    inv = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
    o_ref[0] = (acc_scr[...] * inv).astype(o_ref.dtype)


def _mla_group(rows_pad, page_size, table_width):
    """Pages an update: keys enough to fill the MXU's columns where the
    query rows are many (a chunk's segment: 256 keys), and larger copies in
    flight where they are few and the page reads set the pace (decode: 512
    keys)."""
    # python ints off array shapes  # graftlint: disable=TRACE001
    keys = 512 if rows_pad <= 64 else 256
    return max(1, min(keys // page_size, table_width, 8))


def mla_paged_attention(q, latent_pages, page_table, q_start, q_len, kv_len,
                        *, dv, sm_scale, layer=None, role=None,
                        interpret=False, out_dtype=None, _group=None):
    """Absorbed latent attention over each slot's page list.

    q [S, Qmax, H, Dk] (per head ``[q' | q_rope]``: the query already
    carried into the latent space), latent_pages [L, 1, NP, ps, Dk] with
    ``layer`` (a traced scalar) or [1, NP, ps, Dk] for one layer, rows
    ``[c | r]`` with c the first ``dv`` columns; page_table [S, P], q_start
    / q_len / kv_len [S] as `ragged_paged_attention` has them ->
    o [S, Qmax, H, dv]: per head softmax(q . row * sm_scale) over the
    visible rows, times their c.  Padding queries and q_len = 0 slots come
    back exactly zero.

    Grid (S,): a step is one slot, its rows every (query, head) pair (row
    r = query r // H, head r % H); each live page is fetched ONCE, as keys
    (all Dk columns) and values (the first dv), ``_mla_group`` pages an
    update, products in the operands' dtype accumulated in float32.  The
    query block of a step must fit VMEM: a caller with a long run of
    queries cuts it into segments (`models/mla_moe.py`: 64 queries x 16
    heads a segment).  ``sm_scale`` has no default: it is 1 / sqrt(the
    EXPANDED q/k width), which the latent shapes do not show.

    The call's HLO text carries ``kernel_metadata={"kernel":
    "mla_paged_attention","role":...}`` ("decode" | "chunk").
    """
    if layer is None:
        latent_pages, layer = latent_pages[None], 0
    s_slots, qmax, heads, dk = q.shape
    _l, one, _np_, page_size, dk_p = latent_pages.shape
    if one != 1 or dk_p != dk or not 0 < dv <= dk:
        raise ValueError(f"latent pages {latent_pages.shape} do not hold "
                         f"rows of {dk} columns for one shared head (dv "
                         f"{dv})")
    n_ptab = page_table.shape[1]
    out_dtype = out_dtype or q.dtype
    rows = qmax * heads
    rows_pad = -(-rows // _SUBLANES) * _SUBLANES
    qr = jnp.pad(q.reshape(s_slots, rows, dk),
                 ((0, 0), (0, rows_pad - rows), (0, 0)))
    group = _group or _mla_group(rows_pad, page_size, n_ptab)
    block = lambda width: pl.BlockSpec(
        (1, rows_pad, width), lambda b, pt, qs, ql, kl, ly: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_slots,),
        in_specs=[block(dk), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=block(dv),
        scratch_shapes=[
            pltpu.VMEM((2, group * page_size, dk), latent_pages.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, 1), jnp.float32),
            pltpu.VMEM((rows_pad, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_kernel, page_size=page_size, sm_scale=sm_scale, rep=heads,
        group=group, table_width=n_ptab, dv=dv)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_slots, rows_pad, dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        metadata={"kernel": "mla_paged_attention",
                  **({"role": role} if role else {})},
    )(page_table.astype(jnp.int32), q_start.astype(jnp.int32),
      q_len.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qr, latent_pages)
    return out[:, :rows].reshape(s_slots, qmax, heads, dv)


def mla_paged_attention_ref(q, latent_pages, page_table, q_start, q_len,
                            kv_len, *, dv, sm_scale, layer=None,
                            out_dtype=None):
    """The plain form of :func:`mla_paged_attention` (pages gathered dense,
    float32): the CPU path of the serving engine for this family."""
    pages = latent_pages[layer, 0] if layer is not None else latent_pages[0]
    s_slots, qmax = q.shape[:2]
    g = pages[page_table]                         # [S, P, ps, Dk]
    kv = g.reshape(s_slots, -1, g.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("sqhd,std->shqt", q.astype(jnp.float32), kv) * sm_scale
    t_pos = jnp.arange(kv.shape[1])[None, None, None, :]
    qi = jnp.arange(qmax)[None, None, :, None]
    ok = (t_pos <= q_start[:, None, None, None] + qi) \
        & (qi < q_len[:, None, None, None]) \
        & (t_pos < kv_len[:, None, None, None])
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    o = jnp.einsum("shqt,std->sqhd", p, kv[..., :dv])
    o = jnp.where(jnp.arange(qmax)[None, :, None, None]
                  < q_len[:, None, None, None], o, 0.0)
    return o.astype(out_dtype or q.dtype)
