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
import os
import re

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
    init_pages, _, _, decode_step, _ = build_llama_paged_decode(
        cfg, page_size=PAGE, num_pages=SLOTS * TABLE, dtype=jnp.bfloat16,
        attention_impl="pallas", mesh=mesh)
    pages = jax.tree_util.tree_map(
        lambda a: placed(llama_paged_page_spec("mp"), a),
        jax.eval_shape(init_pages))
    rep = lambda shape, dtype: placed(PartitionSpec(),
                                      jax.ShapeDtypeStruct(shape, dtype))
    compiled = jax.jit(decode_step, donate_argnums=(4, 5)).lower(
        params, rep((SLOTS,), jnp.int32), rep((SLOTS,), jnp.int32),
        rep((SLOTS, TABLE), jnp.int32), pages["k"], pages["v"],
        rep((SLOTS,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    # each device's outputs are its QUARTER of the pool (plus the [S, V]
    # logits), not the whole of it
    pool = 2 * 2 * 32 * (SLOTS * TABLE + 1) * PAGE * D * 2
    out = compiled.memory_analysis().output_size_in_bytes
    assert pool // 4 <= out < pool // 4 + (4 << 20)
