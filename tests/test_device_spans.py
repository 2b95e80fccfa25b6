"""`paddle_tpu.profiler.device_span`: region names from the program on what
XLA compiles (ISSUE 37).

A device trace names an operation by its instruction's whole HLO text,
frontend attributes included; `device_span` writes ``pt_region="<name>"``
there (and the `jax.named_scope`), so the trace splits by region.  What
these cases hold: the attribute reaches the lowered AND the compiled text,
an inner span replaces the outer, a backward operation carries its
forward's name and the optimizer's its own, a Pallas kernel call keeps its
`kernel_metadata` beside the region, and the labels are text only — the
train step and one decode horizon of each served family give the same bits
with the helper turned into a no-op.  The TPU compile's side (labels on the
fusions of the described-v5e executables) is `tests/test_chip_compile.py`.
"""
import contextlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import optimizer, profiler
from paddle_tpu.models.llama import (build_functional_llama,
                                     llama_config_tiny,
                                     make_paged_decode_horizon,
                                     pack_decode_state)
from paddle_tpu.parallel.pipeline import _flatten, _unflatten
from paddle_tpu.profiler import device_span

REGION = re.compile(r'pt_region="([^"]+)"')


def _instructions(compiled_text):
    """[(op_name, region or None)] of the compiled instructions that carry
    an `op_name`."""
    out = []
    for line in compiled_text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if name:
            region = REGION.search(line)
            out.append((name[1], region and region[1]))
    return out


def test_the_attribute_is_in_the_lowered_and_the_compiled_text():
    def f(x, w):
        with device_span("block.mlp"):
            return jnp.tanh(x @ w)

    lowered = jax.jit(f).lower(jnp.ones((8, 16)), jnp.ones((16, 16)))
    assert 'pt_region = "block.mlp"' in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert 'frontend_attributes={pt_region="block.mlp"}' in compiled
    # the named scope rides along: the compiled text's op_name has it
    assert re.search(r'op_name="jit\(f\)/block\.mlp/', compiled)


def test_an_inner_span_replaces_the_outer_one():
    def f(x):
        with device_span("moe.layer"):
            y = jnp.sin(x)
            with device_span("moe.route"):
                y = jnp.cos(y)
            return jnp.exp(y)

    text = jax.jit(f).lower(jnp.ones((4,))).as_text()
    region_of = {op: REGION.search(line.replace(" = ", "=", 1)
                                   .replace('pt_region = ', 'pt_region='))[1]
                 for line in text.splitlines()
                 for op in ("sine", "cosine", "exponential")
                 if f"stablehlo.{op}" in line}
    assert region_of == {"sine": "moe.layer", "cosine": "moe.route",
                         "exponential": "moe.layer"}
    # a decorator is the same span
    g = device_span("head")(lambda x: x * 2)
    assert 'pt_region = "head"' in jax.jit(g).lower(jnp.ones((4,))).as_text()


def _tiny_train_step():
    cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=16)
    ep, bp, hp, ea, ba, hl = build_functional_llama(
        cfg, key=jax.random.PRNGKey(0), head_chunks=2)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=[])
    lr = jnp.asarray(1e-3, jnp.float32)

    def loss_fn(ep, bp, hp, batch):
        x = ea(ep, batch)[0]
        for i in range(cfg.num_hidden_layers):
            x = ba(jax.tree_util.tree_map(lambda v: v[i], bp), x)
        return hl(hp, x[None], batch)

    def step(ep, bp, hp, eo, bo, ho, batch):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            ep, bp, hp, batch)
        new = [opt.apply_gradients_functional(_flatten(p), _flatten(g), o,
                                              lr=lr)
               for p, g, o in zip((ep, bp, hp), grads, (eo, bo, ho))]
        return tuple(_unflatten(n[0], p)
                     for n, p in zip(new, (ep, bp, hp))) \
            + tuple(n[1] for n in new) + (loss,)

    state = (ep, bp, hp) + tuple(opt.init_opt_state(_flatten(p))
                                 for p in (ep, bp, hp))
    ids = np.random.default_rng(0).integers(0, 64, (2, 17)).astype(np.int32)
    return step, state, (jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:]))


def test_backward_carries_the_forwards_label_and_the_optimizer_its_own():
    step, state, batch = _tiny_train_step()
    found = _instructions(jax.jit(step).lower(*state, batch).compile()
                          .as_text())
    regions = {r for _, r in found if r}
    assert {"embed", "block.attn", "block.mlp", "head_loss",
            "optimizer"} <= regions
    for name in ("block.attn", "block.mlp", "head_loss"):
        forward = [r for op, r in found if f"/jvp({name})/" in op and r]
        backward = [r for op, r in found
                    if f"/transpose(jvp({name}))/" in op and r]
        assert forward and set(forward) == {name}, (name, set(forward))
        assert backward and set(backward) == {name}, (name, set(backward))
    # outside autodiff: the update's operations carry their own name and
    # none of them a model region's (the scalar broadcasts a lowering rule
    # emits beside its result carry no attribute at all)
    update = [r for op, r in found if "/optimizer/" in op and r]
    assert update and set(update) == {"optimizer"}, set(update)


def test_a_pallas_call_inside_a_span_keeps_its_kernel_metadata():
    """Lowered FOR the TPU (Mosaic lowers without a chip; nothing compiles
    or runs): forward and custom-vjp backward kernel calls carry the
    kernel's own label and the region side by side."""
    from paddle_tpu.ops.pallas.fused import rms_norm

    def loss(x, w):
        with device_span("block.attn"):
            return rms_norm(x, w).astype(jnp.float32).sum()

    x = jnp.ones((8, 256), jnp.bfloat16)
    w = jnp.ones((256,), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(x, w).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    for line, pass_ in zip(calls, ("fwd", "bwd")):
        attrs = re.search(r"mhlo\.frontend_attributes = \{(.*?)\}, ", line)[1]
        assert "kernel_metadata" in attrs and "rms_norm" in attrs \
            and pass_ in attrs, attrs
        assert 'pt_region = "block.attn"' in attrs, attrs


@contextlib.contextmanager
def _spans_off(monkeypatch):
    """`device_span` as a no-op: neither the attribute nor the scope."""
    with monkeypatch.context() as m:
        off = lambda *a, **kw: contextlib.nullcontext()
        m.setattr(profiler, "set_xla_metadata", off)
        m.setattr(jax, "named_scope", off)
        yield


def _same_bits(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def test_the_train_step_gives_the_same_bits_without_the_labels(monkeypatch):
    step, state, batch = _tiny_train_step()
    lowered = jax.jit(step).lower(*state, batch)
    assert "pt_region" in lowered.as_text()
    with_labels = lowered.compile()(*state, batch)
    with _spans_off(monkeypatch):
        step, _, _ = _tiny_train_step()    # jax keeps a function's trace
        bare = jax.jit(step).lower(*state, batch)
        assert "pt_region" not in bare.as_text()
        without = bare.compile()(*state, batch)
    _same_bits(with_labels, without)


def _family(name):
    """(config, params) of a served family at the CPU tests' size."""
    if name == "llama":
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=64)
        return cfg, build_functional_llama(
            cfg, key=jax.random.PRNGKey(1))[:3]
    if name == "nemotron_h":
        from paddle_tpu.models.nemotron_h import (
            build_functional_nemotron_h, nemotron_h_config_tiny)
        cfg = nemotron_h_config_tiny()
        return cfg, build_functional_nemotron_h(cfg, jax.random.PRNGKey(1),
                                                jnp.float32)
    from paddle_tpu.models.mla_moe import (build_functional_mla_moe,
                                           mla_moe_config_tiny)
    cfg = mla_moe_config_tiny()
    return cfg, build_functional_mla_moe(cfg, jax.random.PRNGKey(1),
                                         jnp.float32)


WANTED = {"llama": {"attn.proj", "block.mlp", "block.scan", "head"},
          "nemotron_h": {"mamba.proj", "ssm.decode_update", "attn.proj",
                         "moe.layer", "moe.shared", "moe.route",
                         "moe.dispatch", "moe.experts", "moe.combine",
                         "head"},
          "mla_moe": {"mla.project", "block.mlp", "moe.layer", "moe.shared",
                      "moe.route", "moe.dispatch", "moe.experts",
                      "moe.combine", "head"}}


@pytest.mark.parametrize("family", list(WANTED))
def test_a_decode_horizon_gives_the_same_bits_without_the_labels(
        family, monkeypatch):
    cfg, params = _family(family)
    S, P, K = 3, 4, 3
    kw = dict(page_size=4, num_pages=S * P, num_slots=S,
              max_pages_per_seq=P, dtype=jnp.float32, attention_impl="ref")
    rng = np.random.default_rng(5)
    args = lambda cache: (
        params, cache, jax.random.PRNGKey(0),
        jnp.asarray(pack_decode_state(
            toks=rng.integers(1, 60, (S,)), lengths=[5, 0, 9],
            remaining=np.full((S,), 8), eos_ids=np.full((S,), -1),
            active=[1, 0, 1], carried=np.zeros((S,)),
            page_tables=np.arange(S * P).reshape(S, P))),
        jnp.concatenate([jnp.zeros((S,), jnp.float32),
                         jnp.ones((S,), jnp.float32)]))

    def run():
        fam = cfg.paged_family(**kw)
        horizon = make_paged_decode_horizon(fam.decode_step)
        fn = jax.jit(lambda *a: horizon(*a, K=K, greedy=True))
        a = args(fam.init_cache())
        return fn.lower(*a).as_text(), fn(*a)

    rng = np.random.default_rng(5)
    text, with_labels = run()
    assert WANTED[family] <= set(re.findall(r'pt_region = "([^"]+)"', text))
    rng = np.random.default_rng(5)
    with _spans_off(monkeypatch):
        bare, without = run()
    assert "pt_region" not in bare
    _same_bits(with_labels, without)
