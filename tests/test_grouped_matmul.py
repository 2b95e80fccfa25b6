"""The grouped-matmul kernel (`ops/pallas/grouped_matmul.py`) in interpret
mode on the CPU: parity with `jax.lax.ragged_dot` over the group layouts a
serving batch produces, the static rule that chooses between the two, and
the expert layer and the model fns on both paths.  Lowering for the chip is
`tests/test_chip_compile.py`'s; the chip itself `chip_smoke.py --hybrid`'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.models.nemotron_h import (build_functional_nemotron_h,
                                          nemotron_h_config_tiny)
from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                  grouped_matmul_ref, tiles,
                                                  weight_visits)

BF16_LIMIT = 2e-4       # tests/test_pallas_kernels.py's bf16 -> f32 bound


def decode_like(rng, groups, counted):
    """A decode batch's load scaled down: most groups 0-3 rows, one heavy,
    a third of them EMPTY."""
    rows = rng.multinomial(counted, rng.dirichlet(np.full(groups, 0.5)))
    rows[rng.choice(groups, groups // 3, replace=False)] = 0
    return rows


# name -> (M, K, N, rows, tm, tn); None: the rule's own choice
def _cases():
    rng = np.random.default_rng(34)
    straddle = [0, 10, 0, 30, 0, 0, 50, 6]         # 30 crosses row 32, 50 64
    return {
        "decode_shape_with_empty_groups":
            (128, 128, 384, decode_like(rng, 32, 60), None, None),
        "decode_shape_k_over_n":
            (128, 384, 128, decode_like(rng, 32, 60), None, None),
        "a_group_straddles_a_row_tile": (128, 128, 256, straddle, 32, 128),
        "a_group_spans_three_row_tiles":
            (128, 256, 128, [3, 0, 70, 5], 32, 128),
        "all_rows_in_one_group": (64, 128, 256, [0, 0, 64, 0], 16, 256),
        "all_rows_in_the_last_group": (64, 256, 128, [0, 0, 0, 40], 16, 128),
        "sum_under_the_bound": (256, 128, 256, [5, 0, 9, 1, 0, 0, 2, 3],
                                64, 128),
        "no_rows_at_all": (64, 128, 128, [0] * 8, 16, 128),
        "rule_row_tile_64": (256, 128, 256, decode_like(rng, 8, 200),
                             None, None),
        "rule_row_tile_128": (1024, 256, 128, decode_like(rng, 8, 700),
                              None, None),
        "one_row_a_group": (64, 128, 128, [1] * 16, 16, 128),
    }


CASES = _cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_parity_with_ragged_dot(name):
    m, k, n, rows, tm, tn = CASES[name]
    rng = np.random.default_rng(len(name))
    xs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(rows), k, n)) / np.sqrt(k),
                    jnp.bfloat16)
    rows = jnp.asarray(rows, jnp.int32)
    counted = int(rows.sum())
    assert counted <= m
    out = grouped_matmul(xs, w, rows, tm=tm, tn=tn, interpret=True,
                         out_dtype=jnp.float32)
    want = grouped_matmul_ref(xs, w, rows, out_dtype=jnp.float32)
    assert out.shape == (m, n) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out)[:counted],
                               np.asarray(want)[:counted],
                               rtol=BF16_LIMIT, atol=BF16_LIMIT)
    if counted:
        assert np.asarray(out)[:counted].any()
    # in the operands' dtype, as the expert layer calls it: the same f32
    # sums rounded once
    low = grouped_matmul(xs, w, rows, tm=tm, tn=tn, interpret=True)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(low, np.float32)[:counted],
        np.asarray(want.astype(jnp.bfloat16), np.float32)[:counted],
        rtol=2 ** -7, atol=BF16_LIMIT)


def test_the_rule_reads_the_static_shapes_alone():
    """The cell's shapes take the kernel with the issue's tiles, the train
    step's 1,024 rows an expert and unaligned widths do not."""
    assert tiles(704, 1024, 2688, 128) == (64, 2688)       # decode, up
    assert tiles(704, 2688, 1024, 128) == (64, 1024)       # decode, down
    assert tiles(1408, 1024, 2688, 128) == (64, 2688)      # its second tier
    assert tiles(11264, 1024, 2688, 128) == (128, 2688)    # a 1,024 chunk
    assert tiles(22528, 2688, 1024, 128) == (128, 1024)
    assert tiles(16384, 2048, 1024, 16) is None            # AFMoE train step
    assert tiles(704, 1000, 2688, 128) is None
    assert tiles(704, 1024, 2700, 128) is None
    assert tiles(96, 128, 128, 8) is None                  # 64 does not divide
    # a weight tile is the whole K and stays under 6 MiB
    for k, n in ((1024, 2688), (2688, 1024), (128, 128), (4096, 1024),
                 (8192, 4096)):
        tm, tn = tiles(512, k, n, 64)
        assert n % tn == 0 and tn % 128 == 0
        assert tn == 128 or k * tn * 2 <= 6 << 20
    assert tiles(512, 4096, 1024, 64)[1] == 512


@pytest.mark.parametrize("tm", [16, 64])
def test_weight_visits_counts_the_row_tile_group_pairs_with_rows(tm):
    rng = np.random.default_rng(tm)
    for _ in range(5):
        rows = decode_like(rng, 16, 100)
        ends = np.cumsum(rows)
        want = sum(len(range((e - r) // tm, (e - 1) // tm + 1))
                   for r, e in zip(rows, ends) if r)
        got = int(weight_visits(jnp.asarray(rows, jnp.int32), 128, tm))
        assert got == want >= (rows > 0).sum()
    assert int(weight_visits(jnp.zeros((16,), jnp.int32), 128, tm)) == 0


def _primitives(fn, *args):
    found = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            found.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_grouped_relu2_picks_its_product_by_rows_a_group():
    """Nothing but the static shapes (and the platform test the caller
    makes) chooses: few rows a group take the kernel, a train step's many
    keep ``ragged_dot``, and without ``kernel`` nothing does."""
    def traced(m, groups, **kw):
        sds = jax.ShapeDtypeStruct
        return _primitives(
            functools.partial(dropless.grouped_relu2, **kw),
            sds((m, 128), jnp.bfloat16), sds((groups, 128, 256), jnp.bfloat16),
            sds((groups, 256, 128), jnp.bfloat16), sds((groups,), jnp.int32))

    few = traced(64, 8, kernel=True, interpret=True)
    assert "pallas_call" in few and "ragged_dot_general" not in few \
        and "ragged_dot" not in few
    many = traced(4096, 4, kernel=True, interpret=True)
    assert "pallas_call" not in many
    assert {"ragged_dot", "ragged_dot_general"} & many
    off = traced(64, 8)
    assert "pallas_call" not in off
    # the three-matrix expert AS THE TRAIN STEP CALLS IT (no platform test
    # passed) is never the kernel's, at few rows a group either; a serving
    # path passes ``kernel`` (tests/test_mla_moe.py)
    sds = jax.ShapeDtypeStruct
    swiglu = _primitives(
        dropless.grouped_swiglu, sds((64, 128), jnp.bfloat16),
        sds((8, 128, 256), jnp.bfloat16), sds((8, 128, 256), jnp.bfloat16),
        sds((8, 256, 128), jnp.bfloat16), sds((8,), jnp.int32))
    assert "pallas_call" not in swiglu
    assert dropless.grouped_swiglu.__kwdefaults__["kernel"] is False


def test_expert_forward_is_the_same_on_both_paths():
    """`dropless_expert_forward` on one routing: the kernel's output, rows
    and beyond equal the ``ragged_dot`` path's, on both row bounds."""
    rng = np.random.default_rng(5)
    t, k, held, experts, latent, inter = 32, 4, 8, 32, 128, 256
    u = jnp.asarray(rng.standard_normal((t, latent)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    mats = (jnp.asarray(rng.standard_normal((held, latent, inter))
                        / np.sqrt(latent), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((held, inter, latent))
                        / np.sqrt(inter), jnp.bfloat16))
    spread = np.stack([rng.choice(experts, k, replace=False)
                       for _ in range(t)])
    crowded = spread % held          # every pair held: past the first bound
    for sel in (spread, crowded):
        sel = jnp.asarray(sel, jnp.int32)
        got, want = (jax.jit(functools.partial(
            dropless.dropless_expert_forward, offset=0, num_experts=experts,
            expert=functools.partial(dropless.grouped_relu2, **kw)))(
                u, sel, w, mats)
            for kw in (dict(kernel=True, interpret=True), {}))
        assert np.array_equal(got[1], want[1]) and int(got[2]) == 0 \
            and int(want[2]) == 0
        np.testing.assert_allclose(np.asarray(got[0], np.float32),
                                   np.asarray(want[0], np.float32),
                                   rtol=2 ** -6, atol=2 ** -6)
    assert int(jnp.asarray(crowded).size) > dropless.row_bounds(
        t, k, held, experts)[0]


def test_the_model_fns_agree_on_both_paths_and_count_the_visits():
    """Decode and dense prefill of a small hybrid with lane-aligned expert
    widths: the kernel path (interpret) against the ``ragged_dot`` path, and
    `moe_gmm_weight_visits_*` counted on the kernel's alone."""
    cfg = nemotron_h_config_tiny(
        hybrid_override_pattern="ME", num_hidden_layers=2,
        n_routed_experts=32, experts_held=(0, 8), moe_latent_size=128,
        moe_intermediate_size=256)
    params = jax.jit(lambda key: build_functional_nemotron_h(
        cfg, key, jnp.float32))(jax.random.PRNGKey(3))
    slots = 32
    ids = np.random.default_rng(0).integers(1, cfg.vocab_size, (slots,))
    tables = jnp.zeros((slots, 10), jnp.int32)
    seen = {}
    for impl in ("pallas", "ref"):
        fam = cfg.paged_family(page_size=4, num_pages=40, num_slots=slots,
                               dtype=jnp.float32, attention_impl=impl,
                               interpret=True)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :50] = np.arange(1, 51)
        logits_p, cache = jax.jit(fam.prefill)(
            params, jnp.asarray(padded), jnp.int32(50),
            jnp.arange(10, dtype=jnp.int32), jnp.int32(1), fam.init_cache())
        logits_d, cache = jax.jit(fam.decode_step)(
            params, jnp.asarray(ids, jnp.int32),
            jnp.zeros((slots,), jnp.int32), tables, cache,
            jnp.arange(slots) != 1)
        seen[impl] = (np.asarray(logits_p), np.asarray(logits_d),
                      fam.counters(cache))
    for got, want in zip(seen["pallas"][:2], seen["ref"][:2]):
        assert np.abs(got - want).max() < 1e-4
    kernel, plain = seen["pallas"][2], seen["ref"][2]
    for phase in ("decode", "prefill"):
        touched = kernel[f"moe_experts_touched_{phase}"]
        assert touched == plain[f"moe_experts_touched_{phase}"] > 0
        assert touched <= kernel[f"moe_gmm_weight_visits_{phase}"] \
            <= touched + 8
        assert plain[f"moe_gmm_weight_visits_{phase}"] == 0
    assert kernel["moe_rows_dropped"] == plain["moe_rows_dropped"] == 0
