"""AFMoE (Trinity) in the compiled train step against the plain reference
(`benchmark/reference_afmoe.py`: float32 jax.numpy, nothing of paddle_tpu)
at a small size on the CPU: hidden 64, 8 heads / 2 KV, window 8 over 32
tokens, 16 experts top-4, float32, the registry's fallbacks — and once with
the Pallas kernels interpreted.  Seeded weights; the last block's output, the
loss and the gradient of EVERY leaf; every deliberately wrong reference
fails the same assertion; the shares of an expert-parallel deployment add up
to the uncut layer; no row is dropped.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference_afmoe as ref                 # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (     # noqa: E402
    dropless_expert_ffn, sigmoid_topk_route, sort_pairs_by_held_expert)
from paddle_tpu.models.afmoe import (afmoe_config_tiny,      # noqa: E402
                                     build_functional_afmoe, is_buffer,
                                     layer_params)

OUT_TOL, LOSS_TOL, GRAD_TOL = 2e-5, 2e-6, 2e-4     # f32 against f32


def model_keys(cfg, held):
    """The configuration-file keys the reference reads, from the config."""
    return dict(hidden_size=cfg.hidden_size, head_dim=cfg.head_dim,
                num_hidden_layers=cfg.num_hidden_layers,
                num_dense_layers=cfg.num_dense_layers, num_experts=held[1],
                expert_offset=held[0],
                num_experts_per_tok=cfg.num_experts_per_tok,
                route_scale=cfg.route_scale,
                sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.rms_norm_eps,
                layer_types=list(cfg.kinds()))


def build(held=(0, 16), seq=32, seed=3, **cfg_kw):
    cfg = afmoe_config_tiny(**cfg_kw)
    ep, bp, hp, ea, ba, hl = build_functional_afmoe(
        cfg, jax.random.PRNGKey(seed), jnp.float32, experts_held=held,
        head_chunks=4)
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, seq + 1)).astype(np.int32)
    batch = (ids[:, :-1], ids[:, 1:])

    def forward(ep, bp):
        x, routed = ea(ep, batch)[0], []
        for i in range(cfg.num_hidden_layers):
            x, r = ba(layer_params(bp, i, cfg.num_dense_layers), x, i)
            routed += [r] if r is not None else []
        return x, routed

    def loss(ep, bp, hp):
        return hl(hp, forward(ep, bp)[0][None], batch)

    return cfg, (ep, bp, hp), batch, forward, loss


def every_leaf(params):
    embed, blocks, head = params
    return [("embed", "tok")] + [(g, k) for g in blocks for k in blocks[g]] \
        + [("head", k) for k in head]


def system_side(params, forward, loss):
    """(last block's output of the first sequence, loss, gradient trees)."""
    return (jax.jit(forward)(*params[:2])[0][0], float(jax.jit(loss)(*params)),
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*params))


def compare(system, params, model, batch, variant=None):
    """(output relative L2, loss relative difference, {leaf: gradient
    relative L2}) of the system against the reference."""
    x, got, grads = system
    want_x, _ = ref.hidden_states(params, model, batch[0][0], variant)
    want = ref.mean_nll(params, model, *batch, variant=variant)
    names = [n for n in every_leaf(params) if not is_buffer(n[1])]
    theirs = ref.gradients(params, model, *batch, names, variant=variant)
    return (ref.rel_l2(x, want_x), abs(got - want) / abs(want),
            {n: ref.rel_l2(ref.leaf(grads, n), g)
             for n, g in zip(names, theirs)})


@pytest.fixture(scope="module")
def share():
    """Rank 1 of 2: experts 8..15 of 16 held, routed over all 16."""
    cfg, params, batch, forward, loss = build(held=(8, 8))
    return (system_side(params, forward, loss), params,
            model_keys(cfg, (8, 8)), batch)


def test_output_loss_and_every_gradient_match_the_reference(share):
    out, dloss, grads = compare(*share)
    assert out < OUT_TOL and dloss < LOSS_TOL, (out, dloss)
    assert len(grads) == 35 and max(grads.values()) < GRAD_TOL, grads
    # the buffer has no gradient: the bias takes part in the selection only
    g = share[0][2][1]["moe"]["router_bias"]
    assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("variant", ref.VARIANTS)
def test_every_wrong_reference_fails_the_same_assertion(share, variant):
    out, _, grads = compare(*share, variant=variant)
    assert out > 1000 * OUT_TOL, (variant, out)
    assert max(grads.values()) > 100 * GRAD_TOL, (variant, grads)


def test_all_experts_held_and_no_dense_layer():
    cfg, params, batch, forward, loss = build(
        num_hidden_layers=4, num_dense_layers=0, layer_types=(
            "sliding_attention", "full_attention") * 2, seed=5)
    out, dloss, grads = compare(system_side(params, forward, loss), params,
                                model_keys(cfg, (0, 16)), batch)
    assert out < OUT_TOL and dloss < LOSS_TOL, (out, dloss)
    assert max(grads.values()) < GRAD_TOL, grads


def test_with_the_pallas_kernels_interpreted(monkeypatch):
    """The registry's Pallas entries as `register_all` places them on a TPU,
    interpreted: the windowed flash kernel (window 128 over 256 tokens, so
    the band and the skipping are exercised) and rms_norm."""
    from paddle_tpu.core import dispatch
    from paddle_tpu.ops.pallas import flash_attention as _  # noqa: F401
    from paddle_tpu.ops.pallas import fused
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_block_sizes", lambda s_q, s_k, d: (128, 128))
    fa._make_op.cache_clear()
    seen = []

    def _fa_causal(q, k, v, window=None):
        seen.append(window)
        return fa.flash_attention(q, k, v, causal=True, interpret=True,
                                  window=window)

    def _rms(x, w, epsilon=1e-6):
        out = fused.rms_norm(x, w, eps=epsilon, interpret=True)
        return out if out is not None else ((x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + epsilon)) * w)

    for name, fn in (("flash_attention_causal", _fa_causal),
                     ("rms_norm", _rms)):
        monkeypatch.setitem(dispatch._KERNELS, name,
                            {**dispatch._KERNELS.get(name, {}), "pallas": fn})
    cfg, params, batch, forward, loss = build(
        held=(0, 16), seq=256, hidden_size=128, num_hidden_layers=2,
        num_dense_layers=1, layer_types=("sliding_attention",
                                         "full_attention"),
        sliding_window=128, max_position_embeddings=256, seed=7)
    x, _ = jax.jit(forward)(*params[:2])
    want_x, _ = ref.hidden_states(params, model_keys(cfg, (0, 16)),
                                  batch[0][0])
    assert seen[:2] == [128, None]
    assert ref.rel_l2(x[0], want_x) < 10 * OUT_TOL
    names = [("dense", "wg", 0), ("moe", "router", 0), ("head", "ln_f")]
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*params)
    theirs = ref.gradients(params, model_keys(cfg, (0, 16)), *batch, names)
    for n, g in zip(names, theirs):
        assert ref.rel_l2(ref.leaf(grads, n), g) < 10 * GRAD_TOL, n
    fa._make_op.cache_clear()


def expert_layer_inputs(t=48, h=64, m=32, e=16, k=4, seed=0, bias=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (t, h), jnp.float32)
    lp = {"router": jax.random.normal(ks[1], (h, e)) / 8,
          "router_bias": bias if bias is not None
          else 0.01 * jax.random.normal(ks[2], (e,)),
          "we_gate": jax.random.normal(ks[3], (e, h, m)) / 8,
          "we_up": jax.random.normal(ks[4], (e, h, m)) / 8,
          "we_down": jax.random.normal(ks[5], (e, m, h)) / 6}
    keys = {"num_experts": e, "expert_offset": 0, "num_experts_per_tok": k,
            "route_scale": 2.826, "route_norm": True}
    return u, lp, keys


def routed_part(u, lp, keys, offset, held):
    sel, w = sigmoid_topk_route(u, lp["router"], lp["router_bias"],
                                keys["num_experts_per_tok"],
                                keys["route_scale"])
    cut = lambda a: a[offset:offset + held]
    return dropless_expert_ffn(u, sel, w, cut(lp["we_gate"]),
                               cut(lp["we_up"]), cut(lp["we_down"]),
                               offset, keys["num_experts"]) + (sel,)


def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    u, lp, keys = expert_layer_inputs()
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    shared = {"ws_gate": jax.random.normal(ks[0], (64, 32)) / 8,
              "ws_up": jax.random.normal(ks[1], (64, 32)) / 8,
              "ws_down": jax.random.normal(ks[2], (32, 64)) / 6}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._expert_mlp(u, {**lp, **shared}, keys, None,
                                   jnp.float32)
        total = ref._swiglu(u, shared["ws_gate"], shared["ws_up"],
                            shared["ws_down"])         # counted ONCE
        rows_seen = 0
        for rank in range(8):                          # 2 experts a rank
            part, rows, _ = routed_part(u, lp, keys, 2 * rank, 2)
            total = total + part
            rows_seen += int(rows.sum())
    assert rows_seen == 48 * 4                 # every pair on exactly 1 rank
    np.testing.assert_allclose(total, whole, atol=2e-5, rtol=2e-5)


def test_every_token_to_one_expert_loses_no_row_and_the_counts_say_so():
    bias = jnp.zeros((16,)).at[5].set(10.0)
    u, lp, keys = expert_layer_inputs(bias=bias)
    part, rows, sel = routed_part(u, lp, keys, 4, 4)   # experts 4..7 held
    assert (np.asarray(sel) == 5).any(-1).all()        # all 48 chose it
    assert int(rows[1]) == 48                          # and all 48 are there
    held = (np.asarray(sel) >= 4) & (np.asarray(sel) < 8)
    assert int(rows.sum()) == int(held.sum())
    with jax.default_matmul_precision("highest"):
        want, _ = ref._expert_mlp(
            u, {**lp, "ws_gate": jnp.zeros((64, 32)),
                "ws_up": jnp.zeros((64, 32)), "ws_down": jnp.zeros((32, 64)),
                **{k: lp[k][4:8] for k in ("we_gate", "we_up", "we_down")}},
            {**keys, "num_experts": 4, "expert_offset": 4}, None,
            jnp.float32)
    np.testing.assert_allclose(part, want, atol=2e-5, rtol=2e-5)
    # the sort: held pairs first, by expert; order and inverse are inverses
    order, inverse, mask, counted = sort_pairs_by_held_expert(sel, 4, 4)
    key = np.where(np.asarray(mask), np.asarray(sel) - 4, 4).reshape(-1)
    assert (np.diff(key[np.asarray(order)]) >= 0).all()
    assert (np.asarray(order)[np.asarray(inverse)] == np.arange(192)).all()
    assert (np.asarray(counted) == np.bincount(key, minlength=5)[:4]).all()


def test_gradients_through_the_dropless_layer_need_no_scatter():
    """The permutation's transposes are gathers: the backward of the expert
    layer holds no scatter-add (slow on a TPU at 65,536 rows)."""
    u, lp, keys = expert_layer_inputs()

    def f(u, lp):
        return routed_part(u, lp, keys, 0, 8)[0].sum()

    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(u, lp))
    assert "scatter" not in text


@pytest.mark.parametrize("to_held", [False, True])
def test_either_row_bound_gives_the_result_of_the_full_one(to_held):
    """The passes over the rows run at twice the rows the share expects
    (2 of 16 experts held, 48 tokens, top-4: 48 rows), or at T*k when the
    count passes that (a bias that sends every token to both held experts:
    96 rows), chosen on the device: both give what the one full bound gives,
    forward and backward."""
    from paddle_tpu.incubate.distributed.models.moe import dropless
    bias = jnp.zeros((16,)).at[:2].set(10.0) if to_held else None
    u, lp, keys = expert_layer_inputs(bias=bias)
    sel, w = sigmoid_topk_route(u, lp["router"], lp["router_bias"], 4, 2.826)
    sorting = sort_pairs_by_held_expert(sel, 0, 2)
    assert int(sorting[3].sum()) == 96 if to_held \
        else int(sorting[3].sum()) <= 48
    weigh = lambda out: (out * jnp.cos(jnp.arange(out.size)
                                       .reshape(out.shape))).sum()
    cut = lambda lp: tuple(lp[k][:2] for k in ("we_gate", "we_up", "we_down"))

    def tiered(u, w, lp):
        return weigh(dropless_expert_ffn(u, sel, w, *cut(lp), 0, 16)[0])

    def full(u, w, lp):
        return weigh(dropless._experts_within(48 * 4, (u, w) + cut(lp),
                                              sorting))

    for a, b in zip(*(jax.tree_util.tree_leaves(jax.value_and_grad(
            f, argnums=(0, 1, 2))(u, w, lp)) for f in (tiered, full))):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_the_balancing_rule_moves_the_bias_against_the_load():
    from paddle_tpu.incubate.distributed.models.moe.dropless import (
        balance_bias_update, expert_load)
    u, lp, _ = expert_layer_inputs()
    sel, _ = sigmoid_topk_route(u, lp["router"], lp["router_bias"], 4, 2.826)
    load = expert_load(sel, 16)
    assert (np.asarray(load) == ref.load_of(sel, 16)).all()
    assert int(load.sum()) == 48 * 4
    bias = jnp.stack([lp["router_bias"], -lp["router_bias"]])     # 2 layers
    new = balance_bias_update(bias, jnp.stack([load, load[::-1]]), 0.001)
    for b, n, l in zip(bias, new, (load, load[::-1])):
        np.testing.assert_allclose(n, ref.bias_after_step(b, l, 0.001),
                                   atol=1e-7)
        over = np.asarray(l) > np.asarray(l).mean()
        assert (np.asarray(n - b)[over] < 0).all()        # the busy ones down
        assert abs(float((n - b).sum())) < 1e-6           # centred
