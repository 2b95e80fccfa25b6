"""Compile the main path's kernels for the REAL chip, with no chip attached
(on-chip-measurement guide §2, rehearsal 3).

The TPU compiler is installed in the sandbox and compiles for a topology
that is described (`v5e:2x2`), raising what the chip's compiler would raise.
Interpret mode cannot see any of it: the ragged serving kernel passed every
interpret-mode parity test for twenty PRs while Mosaic refused its q block
in every shape, and `rms_norm`'s backward asked for 18 MB of scoped VMEM at
H=4096.  These cases keep both closed, at the widths of `llama_config_7b()`
(32 heads, D=128, H=4096), about two seconds each.

Rules this file lives by (same guide): the topology is described inside a
fixture — never at import, in a `skipif`, in `parametrize` arguments or in
conftest.py — because only one process may load libtpu and every xdist
worker imports every test file; the compile runs in the test's own process
with the persistent compilation cache off; everything is in this ONE file so
one worker owns the library.  Nothing runs on a device: a compile that
passes is not a chip run.
"""
import dataclasses
import functools
import math
import os
import re
import sys

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

PAGE, TABLE, SLOTS, D = 64, 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device can be written to the persistent
    # cache but never read back without a chip — keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _shapes_on(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _fusions_under(region, text):
    """The fusion instructions of a compiled text that carry
    ``pt_region="<region>"`` (`paddle_tpu.profiler.device_span`)."""
    return [line for line in text.splitlines()
            if " fusion(" in line and f'pt_region="{region}"' in line]


_COMPILED = {}


def _compiled(program):
    """``(jitted fn, abstract args)`` compiled once a run of this file: the
    programs come from module fixtures, and two cases read each of the
    serving executables."""
    fn, args = program
    if id(fn) not in _COMPILED:
        _COMPILED[id(fn)] = fn.lower(*args).compile()
    return _COMPILED[id(fn)]


@pytest.mark.parametrize("hq,hkv", [(32, 32), (32, 8)],
                         ids=["mha32", "gqa32x8"])
@pytest.mark.parametrize("slots,qmax", [(SLOTS, 1), (1, 128), (SLOTS, 5)],
                         ids=["decode", "chunk", "verify"])
def test_ragged_paged_attention_compiles(one_chip, slots, qmax, hq, hkv):
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    sds = _shapes_on(one_chip)
    pages = sds((hkv, SLOTS * TABLE, PAGE, D), jnp.bfloat16)
    seg = sds((slots,), jnp.int32)
    role = {1: "decode", 128: "chunk", 5: "verify"}[qmax]
    text = _compiles_with_kernel(
        functools.partial(ragged_paged_attention, role=role),
        sds((slots, qmax, hq, D), jnp.bfloat16),
        pages, pages, sds((slots, TABLE), jnp.int32), seg, seg, seg)
    # the label a device trace finds the kernel by: an "XLA Ops" event's
    # name is this instruction's text (jax writes the JSON object with a
    # newline after every item, so a matcher allows white space there)
    assert ('kernel_metadata={"kernel":"ragged_paged_attention","role":"%s"}'
            % role) in "".join(text.split())
    assert re.search(r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention"',
                     text)


# name: (slots, q_len, Hq, Hkv, rows of a kv head padded to 8, role)
_CELL_KERNEL = {"decode": (16, 1, 32, 8, 8, "decode"),
                "chunk": (1, 512, 32, 8, 2048, "chunk"),
                "verify": (16, 5, 32, 8, 24, "verify"),
                "tp4_rank_decode": (16, 1, 8, 2, 8, "decode")}


@pytest.mark.parametrize("case", list(_CELL_KERNEL))
def test_ragged_kernel_keeps_its_outline_at_the_cells_shapes(one_chip, case):
    """`serve_chat_c16`'s own calls (PR 30): 16 slots x a table of 32 on the
    5-D pool `bf16[16, 8, 513, 64, 128]` with the layer TRACED, the 1 x 512
    chunk, the 16 x 5 verify and a TP=4 rank's two kv heads — whatever
    (heads, ring depth) the kernel chose for them.  The compiled call keeps
    the outline the benchmark's matchers read: the `[S, P]` page table its
    FIRST operand, the whole pool handed in (K and V once each, no slice),
    ONE array result `[S, Hkv, rows_pad, D]`, the label with its role."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    slots, q_len, hq, hkv, rows_pad, role = _CELL_KERNEL[case]
    sds = _shapes_on(one_chip)
    pool = sds((16, hkv, 16 * TABLE + 1, PAGE, D), jnp.bfloat16)
    seg = sds((slots,), jnp.int32)
    text = _compiles_with_kernel(
        lambda q, k, v, t, a, b, c, layer: ragged_paged_attention(
            q, k, v, t, a, b, c, role=role, layer=layer),
        sds((slots, q_len, hq, D), jnp.bfloat16), pool, pool,
        sds((slots, TABLE), jnp.int32), seg, seg, seg, sds((), jnp.int32))
    pool_text = r"bf16\[16,%d,513,64,128\]\{4,3,2,1,0\}" % hkv
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line]
    assert re.search(
        r"= bf16\[%d,%d,%d,128\]\S* custom-call\(" % (slots, hkv, rows_pad)
        + r"[^)]*\), custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{s32\[%d,32\]\{1,0\}, " % slots
        + r"(s32\[\d+\]\{0\}, ){4}bf16\[%d,%d,%d,128\]\{3,2,1,0\}, "
        % (slots, hkv, rows_pad) + pool_text + ", " + pool_text + r"\}",
        call), call[:900]
    # (jax writes the label's JSON with a newline after every item)
    assert ('kernel_metadata={"kernel":"ragged_paged_attention","role":"%s"}'
            % role) in "".join(text.split())


# name: (layers of the pool, pages a slot, kind)
_LONGREASON_KERNEL = {"store": (1, 73, "cross"), "ring": (8, 4, "window")}


@pytest.mark.parametrize("case", list(_LONGREASON_KERNEL))
def test_ragged_kernel_at_the_differential_decode_shapes(one_chip, case):
    """`serve_longreason_c64`'s decode calls (PR 40: the bf16 [8, 128] query
    block, the probabilities' bf16 pieces and the bf16 tiles go to the MXU
    as they are — half a bf16 sublane tile a left operand): 64 slots, 40
    query rows of 128 over 10 K/V pairs, pages of 128 — the ONE store's
    table of 73 and a window ring's 4 pages — float32 out.  The outline the
    benchmark's readers match stays: the `[S, P]` table the FIRST operand,
    ONE result `f32[64,10,8,128]` (`model.decode_ms_per_step` finds the
    decode module by `[d,d,8,d]`), and the label's `kind` and `role`
    ADJACENT (the compiled text sorts the keys; the two decode rooflines
    match `"kind":"…",\\s*"role":"decode"`)."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    layers, table, kind = _LONGREASON_KERNEL[case]
    slots, hq, pairs, page = 64, 40, 10, 128
    sds = _shapes_on(one_chip)
    pool = sds((layers, pairs, slots * table + 1, page, D), jnp.bfloat16)
    seg = sds((slots,), jnp.int32)
    text = _compiles_with_kernel(
        lambda q, k, v, t, a, b, c, layer: ragged_paged_attention(
            q, k, v, t, a, b, c, sm_scale=0.125, out_dtype=jnp.float32,
            role="decode", kind=kind, layer=layer),
        sds((slots, 1, hq, D), jnp.bfloat16), pool, pool,
        sds((slots, table), jnp.int32), seg, seg, seg, sds((), jnp.int32))
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line]
    pool_text = r"bf16\[%d,10,%d,128,128\]\{4,3,2,1,0\}" % (
        layers, slots * table + 1)
    assert re.search(
        r"= f32\[64,10,8,128\]\S* custom-call\([^)]*\), "
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{s32\[64,%d\]\{1,0\}, " % table
        + r"(s32\[\d+\]\{0\}, ){4}bf16\[64,10,8,128\]\{3,2,1,0\}, "
        + pool_text + ", " + pool_text + r"\}", call), call[:900]
    # the benchmark's own expressions (`benchmark/layer_metrics/kernel.
    # shared_kv_decode_roofline_pct.json`, `.window_attn_decode_…`)
    assert re.search(
        r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention",\s*'
        r'"kind":"%s",\s*"role":"decode"\s*\}' % kind, text)


@pytest.mark.parametrize("storage", [jnp.int8, jnp.float8_e4m3fn],
                         ids=["int8", "fp8"])
@pytest.mark.parametrize("slots,qmax", [(SLOTS, 1), (1, 128)],
                         ids=["decode", "chunk"])
def test_ragged_paged_attention_quant_body_compiles(one_chip, slots, qmax,
                                                    storage):
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    sds = _shapes_on(one_chip)
    hq, hkv, n_pages = 32, 8, SLOTS * TABLE
    pages = sds((hkv, n_pages, PAGE, D), storage)
    scales = sds((hkv, n_pages, PAGE), jnp.float32)
    seg = sds((slots,), jnp.int32)
    _compiles_with_kernel(
        lambda q, k, v, t, a, b, c, ks, vs: ragged_paged_attention(
            q, k, v, t, a, b, c, k_scales=ks, v_scales=vs),
        sds((slots, qmax, hq, D), jnp.bfloat16), pages, pages,
        sds((slots, TABLE), jnp.int32), seg, seg, seg, scales, scales)


@pytest.mark.parametrize("hidden", [4096, 1024])
def test_rms_norm_forward_backward_compiles(one_chip, hidden):
    from paddle_tpu.ops.pallas import fused

    def loss(x, w):
        return fused.rms_norm(x, w).astype(jnp.float32).sum()

    sds = _shapes_on(one_chip)
    _compiles_with_kernel(jax.grad(loss, argnums=(0, 1)),
                          sds((2, 2048, hidden), jnp.bfloat16),
                          sds((hidden,), jnp.bfloat16))


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["plain", "dropout"])
def test_flash_attention_forward_backward_compiles(one_chip, dropout):
    """[2, 2048, 32, 128] causal — and the in-kernel-dropout variant, whose
    pltpu PRNG has no interpret-mode lowering at all: this compile is the
    only guard it has off the chip."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v, seed):
        out = flash_attention(q, k, v, causal=True, dropout_rate=dropout,
                              dropout_seed=seed if dropout else None)
        return out.astype(jnp.float32).sum()

    sds = _shapes_on(one_chip)
    qkv = sds((2, 2048, 32, D), jnp.bfloat16)
    _compiles_with_kernel(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
                          sds((), jnp.int32))


def test_windowed_flash_attention_compiles_with_its_label(one_chip):
    """[1, 8192, 32 / 4 KV, 128], window 2048 — the AFMoE train cell's
    attention (PR 29): the band's clamped index maps lower through Mosaic,
    forward and both backward kernels carry ``"window":2048`` in the label a
    device trace finds them by, and ``window=None`` carries none."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(window):
        return lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    sds = _shapes_on(one_chip)
    q, kv = sds((1, 8192, 32, D), jnp.bfloat16), sds((1, 8192, 4, D),
                                                     jnp.bfloat16)
    text = "".join(_compiles_with_kernel(
        jax.grad(loss(2048), argnums=(0, 1, 2)), q, kv, kv).split())
    for pass_ in ("fwd", "dkv", "dq"):
        assert ('kernel_metadata={"kernel":"flash_attention","pass":"%s",'
                '"window":2048}' % pass_) in text
    plain = "".join(_compiles_with_kernel(
        jax.grad(loss(None), argnums=(0, 1, 2)), q, kv, kv).split())
    assert '"window"' not in plain
    assert 'kernel_metadata={"kernel":"flash_attention","pass":"dq"}' in plain


def test_dropless_expert_layer_compiles_to_grouped_matmul_kernels(one_chip):
    """8,192 tokens, top-8 of 128, 16 experts of width 1024 held (PR 29):
    XLA lowers every ``jax.lax.ragged_dot`` — forward and both gradients —
    to its own Mosaic grouped-matmul kernel, labelled by the attribute
    ``kernel.moe_gmm_*`` match, and the backward holds no scatter."""
    from paddle_tpu.incubate.distributed.models.moe import dropless

    def loss(u, router, bias, wg, wu, wd):
        sel, w = dropless.sigmoid_topk_route(u, router, bias, 8, 2.826)
        out, rows = dropless.dropless_expert_ffn(u, sel, w, wg, wu, wd, 0,
                                                 128)
        return out.astype(jnp.float32).sum() + rows.sum()

    sds = _shapes_on(one_chip)
    text = _compiles_with_kernel(
        jax.grad(loss, argnums=(0, 1, 3, 4, 5)),
        sds((8192, 2048), jnp.bfloat16), sds((2048, 128), jnp.bfloat16),
        sds((128,), jnp.float32), sds((16, 2048, 1024), jnp.bfloat16),
        sds((16, 2048, 1024), jnp.bfloat16),
        sds((16, 1024, 2048), jnp.bfloat16))
    assert text.count(dropless.TRACE_LABEL) >= 8
    assert not re.search(r"= \S+ scatter\(", text)
    # ~1,000 rows an expert: XLA's own 512-cubed tiles, never the serving
    # kernel of `ops/pallas/grouped_matmul.py` (PR 34)
    assert dropless.TRACE_LABEL + '"512,512,512"' in text
    assert '"kernel":"grouped_matmul"' not in "".join(text.split())


# serve_reason_c64's grouped products: (row bound, K, N) -> the tiling the
# static rule takes; 128 held experts (PR 34)
_CELL_PRODUCTS = {"decode up": (704, 1024, 2688, "64,1024,2688"),
                  "decode down": (704, 2688, 1024, "64,2688,1024"),
                  "decode up, second row bound": (1408, 1024, 2688,
                                                  "64,1024,2688"),
                  "chunk up": (11264, 1024, 2688, "128,1024,2688"),
                  "chunk down, second row bound": (22528, 2688, 1024,
                                                   "128,2688,1024")}


@pytest.mark.parametrize("case", list(_CELL_PRODUCTS))
def test_grouped_matmul_compiles_with_the_label_the_trace_reads(one_chip,
                                                                case):
    """The kernel lowers at the cell's shapes with whole-K weight tiles, and
    its instruction's text carries `TRACE_LABEL` + the tiling verbatim: the
    four ``kernel.moe_gmm_*`` metrics find it as they found XLA's."""
    from paddle_tpu.incubate.distributed.models.moe import dropless
    m, k, n, tiling = _CELL_PRODUCTS[case]
    sds = _shapes_on(one_chip)
    text = _compiles_with_kernel(
        functools.partial(dropless.grouped_relu2, kernel=True, role="decode"),
        sds((m, k), jnp.bfloat16), sds((128, k, n), jnp.bfloat16),
        sds((128, n, k), jnp.bfloat16), sds((128,), jnp.int32))
    assert "ragged-dot" not in text
    assert dropless.TRACE_LABEL + tiling in text
    flat = "".join(text.split())
    assert 'kernel_metadata={"kernel":"grouped_matmul"' in flat
    assert '"role":"decode"' in flat


# ---------------------------------------------------------------------------
# The KV page pool stays where it is (PR 28).  On the parent every serving
# executable sliced a layer out of the pool, relaid it out twice for the
# Mosaic kernel, stacked it back and copied the whole pool around the layer
# loop: 41 % of a decode step on the chip.  None of that is visible off the
# chip except HERE, in the compiled program's text.
# ---------------------------------------------------------------------------
# serve_chat_c16's engine sizes (benchmark/configs/mistral-7b-serve-1chip.json)
CELL = {"num_slots": 16, "max_pages_per_seq": TABLE, "page_size": PAGE,
        "decode_horizon": 8, "prefill_chunk": 512, "prompt_bucket": 128,
        "prompt_lens": [512]}
CELL_LAYERS, HKV = 2, 8                 # two layers keep a compile to seconds
_MOVERS = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
# result dtype, dims, minor-to-major (without tiling), opcode
_INSTR = re.compile(r"= (\w+)\[([\d,]*)\](?:\{([\d,]*)[^}]*\})? ([\w-]+)\(")


def _assert_pool_stays_in_place(compiled, pool_shape):
    """No instruction of the compiled program — fused or not — copies,
    slices or update-slices a value with as many elements as the bf16 pool
    ``pool_shape`` or one layer of it, whatever shape a bitcast gave it;
    every value of the pool's own shape is row-major; the donated pool is
    aliased to the output and no second one sits among the temporaries."""
    n_pool = math.prod(pool_shape)
    sizes = {n_pool, n_pool // pool_shape[0]}
    movers, layouts = [], set()
    for dtype, dims, layout, op in _INSTR.findall(compiled.as_text()):
        shape = tuple(int(n) for n in dims.split(",") if n)
        if op in _MOVERS and math.prod(shape) in sizes:
            movers.append((op, f"{dtype}[{dims}]{{{layout}}}"))
        if shape == pool_shape:
            layouts.add(layout)
    assert not movers, movers
    # the parameter's and the Mosaic kernel's layout, everywhere
    assert layouts == {"4,3,2,1,0"}, layouts
    pool_bytes = 2 * 2 * n_pool                    # K and V, bf16
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < pool_bytes // 2, m.temp_size_in_bytes


_SCATTER = re.compile(
    r"= \w+\[([\d,]*)\]\S* scatter\(%[\w.-]+, %([\w.-]+), %[\w.-]+\), "
    r"update_window_dims=\{([\d,]*)\}.*?index_vector_dim=(\d+)")


def _pool_scatters(text, pool_shape):
    """[(update window dims, number of updates)] of every scatter of the
    compiled program into a value with as many elements as the pool: the
    updates are counted on the shape of the scatter's INDICES operand, all
    of its dimensions but the index vector's."""
    found = []
    for dims, indices, window, vector_dim in _SCATTER.findall(text):
        if math.prod(int(n) for n in dims.split(",")) != math.prod(pool_shape):
            continue
        (shape,) = re.findall(
            r"^\s*%%%s = s32\[([\d,]*)\]" % re.escape(indices), text, re.M)
        shape = [int(n) for n in shape.split(",")]
        del shape[int(vector_dim):int(vector_dim) + 1]  # implicit when last
        found.append((tuple(int(n) for n in window.split(",")),
                      math.prod(shape)))
    return found


def _cell_programs(one_chip, **kw):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "perf"))
    import chip_fit
    from paddle_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(
        LlamaConfig(), hidden_size=4096, intermediate_size=14336,
        num_attention_heads=32, num_key_value_heads=HKV, vocab_size=32768,
        num_hidden_layers=CELL_LAYERS, max_position_embeddings=32768,
        rope_theta=1e6)
    place = chip_fit.placed_on(one_chip)
    return chip_fit.paged_programs(cfg, CELL, place, place, one_chip, **kw)


@pytest.fixture(scope="module")
def cell_programs(one_chip):
    """The engine's four paged executables at `serve_chat_c16`'s widths
    (Mistral-7B: H 4096, 32/8 heads, D 128), slots and pool (16 x 32 pages
    of 64 tokens: bf16[L,8,513,64,128] a side), lowered as `ServingEngine`
    jits them (each call's host state ONE packed argument), pool donated —
    by perf/chip_fit.py, which sizes the chip runs with the same
    programs."""
    return _cell_programs(one_chip)


@pytest.mark.parametrize("program", ["decode horizon", "dense prefill",
                                     "prefill chunk", "verify"])
def test_serving_executable_leaves_the_page_pool_in_place(cell_programs,
                                                          program):
    compiled = _compiled(*[v for k, v in cell_programs.items()
                           if k.startswith(program)])
    text = compiled.as_text()
    if program != "dense prefill":                 # dense attends locally
        # what the benchmark's shape matchers key on: the kernel call has
        # ONE array result [S, Hkv, rows, D] (no tuple: no
        # input_output_aliases) and the [S, P] page table is its FIRST
        # operand (the compiled text lists operand shapes here; a trace
        # event's name has them inline)
        assert re.search(
            r'= \w+\[\d+,8,\d+,128\]\S* custom-call\([^)]*\), '
            r'custom_call_target="tpu_custom_call", '
            r'operand_layout_constraints=\{s32\[\d+,32\]', text)
        assert re.search(
            r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention"', text)
        # ... and, traced inside `device_span("attn.proj")`, it keeps that
        # label and gains the region beside it (ISSUE 37)
        assert re.search(
            r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention"[^}]*\},'
            r'pt_region="attn\.proj"\}', text)
        # and the kernel takes the pool itself, K and V, not a slice of it
        # (it copies the pages it attends out of HBM on its own: PR 30)
        assert re.search(
            r"custom_call_target=\"tpu_custom_call\", "
            r"operand_layout_constraints=\{s32\[\d+,32\].*"
            r"(, bf16\[%d,8,513,64,128\]\{4,3,2,1,0\}){2}\}" % CELL_LAYERS,
            text)
    # the regions of `models/llama.py`'s paged fns are on the TPU compile's
    # fusions (found by label, never by an instruction's name)
    assert _fusions_under("head", text) and _fusions_under("block.mlp", text)
    pool = (CELL_LAYERS, HKV, CELL["num_slots"] * TABLE + 1, PAGE, D)
    _assert_pool_stays_in_place(compiled, pool)
    # how the fresh K/V rows reach the pool (PR 32), K and V a scatter each.
    # A prefill's tokens are one contiguous run: a page an update, window
    # (ps, D) — 512 rows may start inside a page, so 9 pages x 8 heads —
    # where the row form issued 512 x 8.  Decode and verify write per-slot
    # positions a row an update, as before: 16 (x 5) rows x 8 heads.
    rows, pages = CELL["prefill_chunk"], CELL["prefill_chunk"] // PAGE
    want = {"dense prefill": ((2, 3), (pages + 1) * HKV),
            "prefill chunk": ((2, 3), (pages + 1) * HKV),
            "decode horizon": ((2,), CELL["num_slots"] * HKV),
            "verify": ((3,), CELL["num_slots"] * 5 * HKV)}[program]
    scatters = _pool_scatters(text, pool)
    assert scatters == [want, want], scatters
    assert all(n < rows * HKV for _, n in scatters)


def test_tp4_paged_decode_step_compiles_with_kernel_and_allreduce(topo):
    """The TP=4 decode step on four described devices, as
    `build_llama_paged_decode(mesh=...)` builds it: the Pallas kernel under
    `shard_map(check_vma=False)` and the layer's one all-reduce must both be
    in the executable.  7B widths, two layers."""
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models.llama import (
        build_functional_llama, build_llama_paged_decode, llama_config_7b,
        llama_paged_page_spec, llama_paged_param_specs)

    cfg = dataclasses.replace(llama_config_7b(), num_hidden_layers=2)
    mesh = build_mesh({"mp": 4}, devices=topo.devices)
    assert sorted(d.id for d in mesh.devices.flat) == [0, 1, 2, 3]

    def placed(spec, a):
        return _shapes_on(NamedSharding(mesh, spec))(a.shape, a.dtype)

    params = jax.tree_util.tree_map(
        placed, llama_paged_param_specs("mp"),
        jax.eval_shape(
            lambda: build_functional_llama(cfg, dtype=jnp.bfloat16)[:3]),
        is_leaf=lambda s: isinstance(s, PartitionSpec))
    fam = build_llama_paged_decode(
        cfg, page_size=PAGE, num_pages=SLOTS * TABLE, dtype=jnp.bfloat16,
        attention_impl="pallas", mesh=mesh)
    cache = jax.tree_util.tree_map(
        lambda a: placed(llama_paged_page_spec("mp"), a),
        jax.eval_shape(fam.init_cache))
    rep = lambda shape, dtype: placed(PartitionSpec(),
                                      jax.ShapeDtypeStruct(shape, dtype))
    compiled = jax.jit(fam.decode_step, donate_argnums=(4,)).lower(
        params, rep((SLOTS,), jnp.int32), rep((SLOTS,), jnp.int32),
        rep((SLOTS, TABLE), jnp.int32), cache,
        rep((SLOTS,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    # each device's outputs are its QUARTER of the pool (plus the [S, V]
    # logits), not the whole of it
    pool = 2 * 2 * 32 * (SLOTS * TABLE + 1) * PAGE * D * 2
    out = compiled.memory_analysis().output_size_in_bytes
    assert pool // 4 <= out < pool // 4 + (4 << 20)
    # and each rank leaves its quarter where it is, like the one-chip engine
    _assert_pool_stays_in_place(
        compiled, (2, 32 // 4, SLOTS * TABLE + 1, PAGE, D))


# ---------------------------------------------------------------------------
# A family with recurrent state (PR 33): the three executables of the
# benchmark configuration nemotron-3-super-serve-1of4 at its published
# widths and full cut (11 layers, 128 experts a layer, 64 slots), lowered as
# `ServingEngine` jits them, the cache donated — by perf/chip_fit.py.
# ---------------------------------------------------------------------------
GIB = float(1 << 30)


def _family_programs(one_chip, family, **kw):
    """(programs, cache) of ``chip_fit.<family>_programs`` at its benchmark
    configuration's sizes."""
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.join(root, "perf"))
    import chip_fit
    from benchmark.run import load_json
    conf = load_json(root, "benchmark", "configs", {
        "hybrid": "nemotron-3-super-serve-1of4.json",
        "latent": "kimi-vl-a3b-serve-1of4.json",
        "sambay": "phi-4-mini-flash-serve-1chip.json"}[family])
    return getattr(chip_fit, family + "_programs")(
        conf, chip_fit.placed_on(one_chip), one_chip, **kw)


@pytest.fixture(scope="module")
def hybrid_programs(one_chip):
    return _family_programs(one_chip, "hybrid")


@pytest.mark.parametrize("program", ["decode horizon", "dense prefill",
                                     "prefill chunk"])
def test_hybrid_serving_executable_fits_and_leaves_its_cache_in_place(
        hybrid_programs, program):
    programs, cache = hybrid_programs
    compiled = _compiled(*[v for k, v in programs.items()
                           if k.startswith(program)])
    text = compiled.as_text()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # 9.3 GB of weights, 1.4 GB of state, 0.2 GB of pages: what the cell
    # holds, with room for the reference beside it (15.75 GiB a chip)
    assert 9.5 * GIB < need < 12.0 * GIB, need / GIB
    # the ragged kernel at 2 KV heads x 16 query heads a group lowers, with
    # its label, and takes the pool itself
    assert re.search(
        r'= \w+\[\d+,2,\d+,128\]\S* custom-call\([^)]*\), '
        r'custom_call_target="tpu_custom_call", '
        r'operand_layout_constraints=\{s32\[\d+,50\]', text)
    assert re.search(
        r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention"', text)
    assert re.search(
        r"operand_layout_constraints=\{s32\[\d+,50\].*"
        r"(, bf16\[1,2,3201,64,128\]\{4,3,2,1,0\}){2}\}", text)
    # the grouped products are the kernel of `ops/pallas/grouped_matmul.py`
    # at these rows a group, two an expert layer and row bound, under the
    # label XLA's own carried
    from paddle_tpu.incubate.distributed.models.moe import dropless
    assert text.count(dropless.TRACE_LABEL) >= 10
    assert "ragged-dot" not in text
    assert len(re.findall(r'"kernel":\s*"grouped_matmul"', text)) \
        == text.count(dropless.TRACE_LABEL)
    # nothing copies the page pool, a Mamba layer's SSM state (one leaf a
    # layer since PR 36), the selection log, a layer of the pool or the log,
    # or a layer's expert matrices (a static slice of a STACKED leaf was
    # copied out before every grouped product: 672 MB a matrix, PR 33)
    pool = math.prod(cache["k"].shape)
    layers = len(cache["ssm"])
    leaf = "f32[%s]" % ",".join(map(str, cache["ssm"][0].shape))
    ssm = math.prod(cache["ssm"][0].shape)
    log = math.prod(cache["sel"].shape)
    sizes = {pool, ssm, layers * ssm, 128 * 1024 * 2688,
             log, log // cache["sel"].shape[0]}
    copies = [(op, f"{dtype}[{dims}]")
              for dtype, dims, _, op in _INSTR.findall(text)
              if op in ("copy", "copy-start")
              and math.prod(int(n) for n in dims.split(",") if n) in sizes]
    assert not copies, copies
    # every leaf of the donated cache is aliased to the output
    cache_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.5 * GIB, m.temp_size_in_bytes / GIB
    # no instruction has the STACKED state's shape: what the accepted
    # `kernel.ssm_update_roofline_pct` finds its seconds by (it reads nothing
    # since PR 36; PERF.md section 7)
    assert "f32[%d,%s" % (layers, leaf[4:]) not in text
    if not program.startswith("decode horizon"):
        return
    # ONE pass over a Mamba layer's state a decode step: one fusion a layer
    # has both results, `y = h' C` and the new state ...
    both = re.findall(
        r"(%\S+) = \(bf16\[64,128,64\]\S*, " + re.escape(leaf)
        + r"\S*\) fusion\(", text)
    assert len(both) == layers, both
    # ... and it is found by NAME: the region of `ops/ssm.ssm_decode_update`
    # is on exactly those fusions' instructions (what
    # `kernel.ssm_update_named_roofline_pct` reads; ISSUE 37)
    named = [line.split(" = ")[0].strip()
             for line in _fusions_under("ssm.decode_update", text)
             if "f32[64,128,64,128]" in line.split(" fusion(")[0]]
    assert sorted(named) == sorted(both), (named, both)
    # ... and nothing else takes a state leaf as an operand: every
    # instruction that IS a leaf (the loop's carry, a fusion's second
    # result) feeds one such fusion or only the plumbing of tuples
    # (the instructions INSIDE a fusion's computation are not the program's)
    text = re.sub(r"(?m)^%fused_computation[^\n]*\{\n.*?^\}\n", "", text,
                  flags=re.S)
    is_leaf = set(re.findall(r"(%\S+) = " + re.escape(leaf), text))
    plumbing = ("tuple", "get-tuple-element", "while", "parameter",
                "opt-barrier")
    readers = [(name, op) for name, op, operands in re.findall(
        r"(%\S+) = [^=]*? ([\w-]+)\(([^)]*)\)", text)
        if op not in plumbing
        and is_leaf & {o.strip() for o in operands.split(",")}]
    assert sorted(readers) == sorted((name, "fusion") for name in both), \
        readers


@pytest.mark.parametrize("slots,qmax,table,role", [
    (64, 1, 146, "decode"), (16, 64, 128, "chunk"), (2, 64, 16, "chunk")],
    ids=["decode", "chunk_long", "chunk_short"])
def test_latent_page_kernel_compiles_with_its_label(one_chip, slots, qmax,
                                                    table, role):
    """`serve_longdoc_c64`'s own calls: 64 slots x a table of 146 pages on
    the latent store `bf16[9, 1, 9345, 64, 640]` with the layer traced, and
    a chunk's segments of 64 queries x 16 heads; the store handed in whole,
    once (keys and values are the same rows)."""
    from paddle_tpu.ops.pallas.paged_attention import mla_paged_attention
    sds = _shapes_on(one_chip)
    seg = sds((slots,), jnp.int32)
    text = _compiles_with_kernel(
        lambda q, pool, tab, a, b, c, ly: mla_paged_attention(
            q, pool, tab, a, b, c, dv=512, sm_scale=192 ** -0.5, layer=ly,
            role=role),
        sds((slots, qmax, 16, 640), jnp.bfloat16),
        sds((9, 1, 9345, 64, 640), jnp.bfloat16),
        sds((slots, table), jnp.int32), seg, seg, seg, sds((), jnp.int32))
    assert ('kernel_metadata={"kernel":"mla_paged_attention","role":"%s"}'
            % role) in "".join(text.split())
    # the store is ONE operand of the call: keys and values are the same rows
    (operands,) = re.findall(r"operand_layout_constraints=\{(.*?\})\}", text)
    assert operands.count("bf16[9,1,9345,64,640]") == 1, operands


@pytest.fixture(scope="module")
def latent_programs(one_chip):
    return _family_programs(one_chip, "latent")


@pytest.mark.parametrize("program", ["decode horizon", "dense prefill",
                                     "prefill chunk"])
def test_latent_serving_executable_fits_and_leaves_its_cache_in_place(
        latent_programs, program):
    programs, cache = latent_programs
    compiled = _compiled(*[v for k, v in programs.items()
                           if k.startswith(program)])
    text = compiled.as_text()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # 3.2 GB of weights, 6.9 GB of latent pages (rows of 640), 0.15 GB of
    # logs: what the cell holds (15.75 GiB a chip)
    assert 9.0 * GIB < need < 11.0 * GIB, need / GIB
    role = "decode" if program == "decode horizon" else "chunk"
    assert re.search(r'kernel_metadata=\{\s*"kernel":"mla_paged_attention",'
                     r'\s*"role":"%s"' % role, text)
    assert "ragged_paged_attention" not in text
    # the SwiGLU experts' products are `ops/pallas/grouped_matmul.py` where
    # a group has few rows: every one of a decode step's, and a chunk's
    # under the lower row bound (192 rows a group); past it, XLA's own
    from paddle_tpu.incubate.distributed.models.moe import dropless
    kernels = len(re.findall(r'"kernel":\s*"grouped_matmul"', text))
    assert kernels >= 8 * 3
    if program == "decode horizon":
        assert kernels == text.count(dropless.TRACE_LABEL)
        assert "ragged-dot" not in text
    # nothing copies the latent store, the selection log, a layer of
    # either, or a layer's expert matrices
    pool = math.prod(cache["latent"].shape)
    log = math.prod(cache["sel"].shape)
    sizes = {pool, pool // cache["latent"].shape[0], log,
             log // cache["sel"].shape[0], 16 * 2048 * 1408}
    copies = [(op, f"{dtype}[{dims}]")
              for dtype, dims, _, op in _INSTR.findall(text)
              if op in ("copy", "copy-start")
              and math.prod(int(n) for n in dims.split(",") if n) in sizes]
    assert not copies, copies
    layouts = {layout for _, dims, layout, _ in _INSTR.findall(text)
               if dims == ",".join(map(str, cache["latent"].shape))}
    # row-major (dim 1 has one element: where it stands changes nothing)
    assert layouts <= {"4,3,2,1,0", "4,3,2,0,1"}, layouts
    # every leaf of the donated cache is aliased to the output
    cache_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.5 * GIB, m.temp_size_in_bytes / GIB


@pytest.fixture(scope="module")
def sambay_programs(one_chip):
    return _family_programs(one_chip, "sambay")


SAMBAY_KINDS = {          # kernel calls by (role, kind) an executable
    "decode horizon": {("decode", "window"): 8, ("decode", "full"): 1,
                       ("decode", "cross"): 7},
    "dense prefill": {("chunk", "full"): 1, ("chunk", "cross"): 7},
    "prefill chunk C=1024": {("chunk", "full"): 1, ("chunk", "cross"): 7},
    "prefill chunk C=1024 not last": {}}


@pytest.mark.parametrize("program", list(SAMBAY_KINDS))
def test_sambay_serving_executable_fits_and_leaves_its_cache_in_place(
        sambay_programs, program):
    """`serve_longreason_c64`'s four executables at the published widths,
    32 layers, 64 slots x 146 pages: what fits, which kernel calls each
    holds (by role and KIND of layer), the regions the per-layer metrics
    read, and the three kinds of state left in place."""
    programs, cache = sambay_programs
    compiled = _compiled(programs[[k for k in programs
                                   if k.startswith(program)][0]]
                         if program != "prefill chunk C=1024"
                         else programs[program])
    text = compiled.as_text()
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    # 7.7 GB of weights, 3.06 GB of the one store, 1.35 GB of window rings,
    # 0.21 GB of recurrent state (15.75 GiB a chip); a chunk that is not
    # its prompt's last takes the first half's weights only
    low, high = (8.5, 9.5) if program.endswith("not last") else (11.0, 12.5)
    assert low * GIB < need < high * GIB, need / GIB
    flat = "".join(text.split())
    for (role, kind), n in SAMBAY_KINDS[program].items():
        # the compiled text sorts the label's keys
        label = ('kernel_metadata={"kernel":"ragged_paged_attention",'
                 '"kind":"%s","role":"%s"}' % (kind, role))
        assert flat.count(label) == n, (label, flat.count(label))
    assert flat.count('"kernel":"ragged_paged_attention"') \
        == sum(SAMBAY_KINDS[program].values())
    prefill = program != "decode horizon"
    last = not program.endswith("not last")
    regions = {"mamba.proj", "attn.window", "attn.shared_kv", "block.mlp",
               "ssm.selective_scan" if prefill else "ssm.selective_update"}
    if last:
        regions |= {"gmu", "head"}
    for region in regions:
        assert f'pt_region="{region}"' in text, region
    assert ('pt_region="gmu"' in text) == last
    assert ('pt_region="ssm.selective_update"' in text) == (not prefill)
    # nothing copies the one store, the rings, a layer of the rings or a
    # Mamba layer's state
    pool = math.prod(cache["k"].shape)
    rings = math.prod(cache["win_k"].shape)
    sizes = {pool, rings, rings // cache["win_k"].shape[0]}
    copies = [(op, f"{dtype}[{dims}]")
              for dtype, dims, _, op in _INSTR.findall(text)
              if op in ("copy", "copy-start") and dtype == "bf16"
              and math.prod(int(n) for n in dims.split(",") if n) in sizes]
    assert not copies, copies
    for leaf in ("k", "win_k"):
        layouts = {layout for _, dims, layout, _ in _INSTR.findall(text)
                   if dims == ",".join(map(str, cache[leaf].shape))}
        assert layouts <= {"4,3,2,1,0"}, (leaf, layouts)
    # every leaf of the donated cache is aliased to the output
    cache_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.5 * GIB, m.temp_size_in_bytes / GIB
    if program == "decode horizon":
        # one fusion a Mamba layer updates the state in place and reads y
        # out of it (two results), as the other recurrent family's does
        state = "f32[%s]" % ",".join(map(str, cache["ssm"][0].shape))
        both = [line for line in _fusions_under("ssm.selective_update", text)
                if re.search(r"= \(f32\[64,5120\]\S*, %s" % re.escape(state),
                             line)]
        assert len(both) == len(cache["ssm"]), len(both)


# ---------------------------------------------------------------------------
# One packed argument a call (PR 38).  A call's per-call host state arrives
# as ONE int32 buffer that the program takes apart with static slices, and
# the engine's key is split inside; the device's work must be what it was
# when every field was an argument of its own.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("program", ["decode horizon", "dense prefill",
                                     "prefill chunk"])
@pytest.mark.parametrize("cell", ["chat", "hybrid", "latent"])
def test_a_packed_call_is_the_unpacked_call_but_for_slices_and_the_split(
        cell, program, request, one_chip):
    """At the three serve cells' shapes, the packed executable's WORK — the
    multiset of (opcode, fusion kind or call target, result shape) over
    every instruction that does not merely route values, entry and fused
    (`perf/region_fit.work_signature`) — is that of the same call with
    every field an argument of its own: the unpack leaves slices of the
    packed buffer and the in-program split a handful of uint32 words, both
    no larger than the buffer, and nothing else.  (The PARENT's compiled
    horizon and chunk, from its checkout, agreed with the packed ones in
    the same comparison: PERF.md section 6, PR 38.)  The regions and
    kernel labels are on both; that the donated cache stays in place is
    held above, on these very executables."""
    if cell == "chat":
        packed = request.getfixturevalue("cell_programs")
        apart = _cell_programs(one_chip, packed=False)
        slots, table = CELL["num_slots"], CELL["max_pages_per_seq"]
    else:
        packed, cache = request.getfixturevalue(cell + "_programs")
        apart, _ = _family_programs(one_chip, cell, packed=False)
        slots, table = (cache["sel"].shape[1],
                        {"hybrid": 50, "latent": 146}[cell])
    import region_fit                    # perf/: the fixtures put it there
    (name, one), = [(k, v) for k, v in packed.items()
                    if k.startswith(program)]
    text = _compiled(one).as_text()
    fn, args = apart[name]
    other = fn.lower(*args).compile().as_text()
    small = (6 + table) * slots          # the horizon's buffer, the largest
    assert region_fit.work_signature(text, small) \
        == region_fit.work_signature(other, small)
    # the whole host state of a call is ONE int32 parameter (the horizon's
    # is the `small` above; it has the float row beside it): no per-field
    # parameter, of a slot's width or a table's, is left
    entry = text[text.index("\nENTRY "):]
    width, = [n for a in one[1] if getattr(a, "dtype", None) == jnp.int32
              for n in a.shape]
    assert width <= small and (program != "decode horizon" or width == small)
    params = re.findall(r"= (\w+\[[\d,]*\])\S* parameter\(", entry)
    assert params.count(f"s32[{width}]") == 1
    assert not {f"s32[{slots}]", f"pred[{slots}]", f"s32[{slots},{table}]",
                f"s32[{table}]"} & set(params)
    assert text.count("pt_region") == other.count("pt_region") > 0
    assert text.count("kernel_metadata") == other.count("kernel_metadata")


def test_the_optimizers_update_compiles_to_fusions_under_its_region(one_chip):
    """`AdamW.apply_gradients_functional` ALONE at two of the dense train
    cell's leaves: every fusion of the update carries
    ``pt_region="optimizer"`` — what `train.optimizer_ms_per_step` finds in
    a trace — by label, whatever XLA calls the instruction."""
    from paddle_tpu import optimizer
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=[])
    at = _shapes_on(one_chip)
    params = {"wq": at((4, 4096, 4096), jnp.bfloat16),
              "ln1": at((4, 4096), jnp.bfloat16)}
    state = jax.tree_util.tree_map(
        lambda a: at(a.shape, a.dtype),
        jax.eval_shape(opt.init_opt_state, params))
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    text = jax.jit(
        lambda p, g, o, lr: opt.apply_gradients_functional(p, g, o, lr=lr),
        donate_argnums=(0, 2)).lower(params, params, state, lr) \
        .compile().as_text()
    fusions = [line for line in text.splitlines() if " fusion(" in line]
    assert len(fusions) >= 2
    assert len(_fusions_under("optimizer", text)) == len(fusions), [
        line.split(" = ")[0] for line in fusions
        if 'pt_region="optimizer"' not in line]

