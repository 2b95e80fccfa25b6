"""Nemotron-H (Mamba-2 + attention + LatentMoE) at a small size on the CPU:
the paged model fns against `benchmark/reference_nemotron_h.py`, which imports
nothing from the program (sequential recurrence, every held expert computed
for every token)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_nemotron_h as ref
from paddle_tpu.models.nemotron_h import (build_functional_nemotron_h,
                                          latent_moe, layer_kinds,
                                          nemotron_h_config_tiny)
from paddle_tpu.ops.ssm import ssd_chunked_scan, ssm_decode_update

F32_LIMIT = 2e-5        # |logit| is ~1 here; float32 end to end


def model_keys(cfg):
    """The configuration-file view of a config object."""
    m = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    m["expert_offset"] = cfg.held()[0]
    return m


def make(cfg, seed=3):
    return jax.jit(lambda k: build_functional_nemotron_h(
        cfg, k, jnp.float32))(jax.random.PRNGKey(seed))


def family(cfg, **kw):
    kw = {"page_size": 4, "num_pages": 40, "num_slots": 3,
          "dtype": jnp.float32, "attention_impl": "ref", **kw}
    return cfg.paged_family(**kw)


def reference_logits(params, cfg, ids, fault=None):
    out = ref.forward(params, model_keys(cfg), ids, fault=fault)
    return ref.logits_at(params, model_keys(cfg), out["hidden"],
                         np.arange(len(ids)), fault), out


def prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        1, cfg.vocab_size, (n,)).astype(np.int32)


def dense_prefill(fam, params, ids, pad_to, slot=1, cache=None):
    padded = np.zeros((1, pad_to), np.int32)
    padded[0, :len(ids)] = ids
    return jax.jit(fam.prefill)(
        params, jnp.asarray(padded), jnp.int32(len(ids)),
        jnp.arange(10, dtype=jnp.int32), jnp.int32(slot),
        fam.init_cache() if cache is None else cache)


@pytest.mark.parametrize("pattern", ["M", "*", "E", "MM", "E*M"])
def test_each_mixer_against_the_reference(pattern):
    cfg = nemotron_h_config_tiny(hybrid_override_pattern=pattern,
                                 num_hidden_layers=len(pattern),
                                 experts_held=(4, 8))
    params = make(cfg)
    ids = prompt(cfg, 19)
    want, _ = reference_logits(params, cfg, ids)
    got, _ = dense_prefill(family(cfg), params, ids, 24)
    assert np.abs(np.asarray(got) - want[-1]).max() < F32_LIMIT


def sequential_scan(x, dt, a, b, c, h0):
    """The recurrence a token at a time, in numpy float64."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    h = np.asarray(h0, np.float64).copy()
    rep = x.shape[1] // b.shape[1]
    ys = []
    for t in range(x.shape[0]):
        bt, ct = np.repeat(b[t], rep, 0), np.repeat(c[t], rep, 0)
        h = h * np.exp(dt[t] * a)[:, None, None] \
            + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", h, ct))
    return np.stack(ys), h


@pytest.mark.parametrize("length", [128, 256, 1, 100, 131, 300])
def test_chunked_scan_against_the_sequential_recurrence(length):
    heads, p, groups, n = 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(length), 6)
    x = jax.random.normal(ks[0], (length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (length, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (length, groups, n))
    c = jax.random.normal(ks[4], (length, groups, n))
    h0 = jax.random.normal(ks[5], (heads, p, n))
    y, h = jax.jit(ssd_chunked_scan, static_argnames="chunk")(
        x, dt, a, b, c, h0, chunk=128)
    want_y, want_h = sequential_scan(x, dt, a, b, c, h0)
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4 * scale
    assert np.abs(np.asarray(h) - want_h).max() < 1e-4 * np.abs(want_h).max()


@pytest.mark.parametrize("shape,state,operands", [
    ((3, 4, 2, 8, 16), "float32", "float32"),
    # as the decode step calls it since PR 36 — a layer's whole state leaf,
    # donated, bfloat16 operands — at the cell's per-slot shape and an odd one
    ((2, 128, 8, 64, 128), "float32", "bfloat16"),
    ((2, 128, 8, 64, 128), "bfloat16", "bfloat16"),
    ((3, 6, 2, 8, 128), "float32", "bfloat16"),
    ((3, 6, 2, 8, 128), "bfloat16", "bfloat16")])
def test_decode_update_is_one_step_of_the_recurrence_and_skips_dt_zero(
        shape, state, operands):
    s, heads, groups, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(ks[0], (s, heads, p, n)).astype(state)
    x = jax.random.normal(ks[1], (s, heads, p)).astype(operands)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (s, heads)))
    dt = dt.at[1].set(0.0)                    # a dead slot
    a = -jnp.exp(jax.random.normal(ks[3], (heads,)))
    b = jax.random.normal(ks[4], (s, groups, n)).astype(operands)
    c = jax.random.normal(ks[5], (s, groups, n)).astype(operands)
    before = np.asarray(h)
    y, new = jax.jit(ssm_decode_update, donate_argnums=0)(h, x, dt, a, b, c)
    assert y.dtype == x.dtype and new.dtype == before.dtype
    # float32 arithmetic, then one rounding to the result's own dtype
    ulp = {"float32": 2.0 ** -23, "bfloat16": 2.0 ** -8}
    for i in range(s):
        want_y, want_h = sequential_scan(
            *(np.asarray(v, np.float32) for v in (
                x[i][None], dt[i][None], a, b[i][None], c[i][None])),
            before[i].astype(np.float32))
        assert np.abs(np.asarray(new[i], np.float32) - want_h).max() \
            <= 4 * ulp[state] * np.abs(want_h).max()
        assert np.abs(np.asarray(y[i], np.float32) - want_y[0]).max() \
            <= max(2 * ulp[operands], 1e-5) * np.abs(want_y).max()
    # a slot whose dt is 0 keeps its state bit for bit
    assert np.array_equal(np.asarray(new[1]), before[1])


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_a_decode_step_reads_each_layers_state_once(state):
    """ONE pass over the state a decode step (ISSUE 36): every Mamba
    layer's state is its own leaf of the cache, ONE equation of the step
    takes it, and nothing updates a slice of a stack of layers."""
    cfg = nemotron_h_config_tiny(ssm_state_dtype=state)
    params = make(cfg)
    fam = family(cfg)
    cache = fam.init_cache()
    layers = sum(kind == "mamba" for kind, _ in layer_kinds(cfg))
    assert isinstance(cache["ssm"], tuple) and len(cache["ssm"]) == layers > 1
    row = jnp.arange(10, dtype=jnp.int32)
    closed = jax.make_jaxpr(fam.decode_step)(
        params, jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
        jnp.stack([row, row + 10, row + 20]), cache,
        jnp.asarray([True, False, True]))
    shape = cache["ssm"][0].shape
    leaves = [v for v in closed.jaxpr.invars
              if v.aval.shape == shape and v.aval.dtype == jnp.dtype(state)]
    assert len(leaves) == layers
    for leaf in leaves:
        assert sum(leaf in eqn.invars for eqn in closed.jaxpr.eqns) == 1
    stacked = (layers,) + shape
    assert not [eqn for eqn in closed.jaxpr.eqns
                for v in eqn.outvars if v.aval.shape == stacked]
    # the new state leaves the step in the leaf's own dtype and place
    outs = [v for v in closed.jaxpr.outvars
            if v.aval.shape == shape and v.aval.dtype == jnp.dtype(state)]
    assert len(outs) == layers and not set(outs) & set(leaves)


def test_the_update_probe_rehearses_on_the_cpu(tmp_path, capsys):
    """`perf/ssm_update_probe.py --rehearse`: the chip probe's control flow
    at a tiny shape (its Pallas calls in interpret mode); every row agrees
    with the plain form, and a rehearsal reports no time."""
    import importlib.util
    import json
    path = os.path.join(os.path.dirname(__file__), "..", "perf",
                        "ssm_update_probe.py")
    spec = importlib.util.spec_from_file_location("ssm_update_probe", path)
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    assert probe.main(["--rehearse", "--tag", "t", "--out",
                       str(tmp_path)]) == 0
    rows = json.load(open(tmp_path / "ssm_update_probe.t.json"))["rows"]
    assert [r["impl"] for r in rows] == [
        "plain", "plain.stacked", "pallas.copy", "pallas.fused"]
    for r in rows:
        assert "error" not in r and "host_ms_per_call" not in r
        if r["impl"] != "pallas.copy":
            assert r["y_max_abs_diff_vs_plain"] < 1e-2
            assert r["state_max_abs_diff_vs_plain"] < 1e-5
    # without the flag a run that finds no TPU refuses
    assert probe.main(["--out", str(tmp_path)]) == 2


@pytest.fixture(scope="module")
def tiny():
    cfg = nemotron_h_config_tiny(experts_held=(4, 8))
    params = make(cfg)
    ids = prompt(cfg, 21)
    want, out = reference_logits(params, cfg, ids)
    return cfg, params, ids, want, out


def test_dense_prefill_logits_and_state_against_the_reference(tiny):
    cfg, params, ids, want, out = tiny
    fam = family(cfg)
    got, cache = dense_prefill(fam, params, ids, 24)
    assert np.abs(np.asarray(got) - want[-1]).max() < F32_LIMIT
    state = fam.slot_state(cache, 1)
    assert max(ref.state_errors(list(state["ssm"]), [
        np.asarray(h) for h in out["states"]])) < 1e-5
    # the other slots' state was not touched
    assert len(cache["ssm"]) == len(state["ssm"])     # one leaf a layer
    for h in cache["ssm"]:
        assert not np.asarray(h[0]).any() and not np.asarray(h[2]).any()


@pytest.mark.parametrize("chunks", [(8, 8, 5), (16, 5), (3, 8, 8, 2)])
def test_chunked_prefill_carries_the_state(tiny, chunks):
    cfg, params, ids, want, out = tiny
    fam = family(cfg)
    cache, pos = fam.init_cache(), 0
    run = jax.jit(fam.prefill_chunk)
    # the slot held another sequence before: position 0 must start from zero
    cache["ssm"] = tuple(h + 7.0 for h in cache["ssm"])
    cache["conv"] = cache["conv"] + 7.0
    for c in chunks:
        pad = -(-c // 8) * 8
        chunk = np.zeros((1, pad), np.int32)
        chunk[0, :c] = ids[pos:pos + c]
        logits, tok, cache = run(params, jnp.asarray(chunk), jnp.int32(pos),
                                 jnp.int32(c), jnp.arange(10, dtype=jnp.int32),
                                 jnp.int32(2), cache)
        assert np.abs(np.asarray(logits) - want[pos + c - 1]).max() \
            < F32_LIMIT
        assert int(tok) == int(np.argmax(np.asarray(logits)))
        pos += c
    state = fam.slot_state(cache, 2)
    assert max(ref.state_errors(list(state["ssm"]), [
        np.asarray(h) for h in out["states"]])) < 1e-5
    assert fam.counters(cache)["ssm_slot_resets"] == 1


def test_token_by_token_decode_against_the_reference(tiny):
    cfg, params, ids, want, out = tiny
    fam = family(cfg)
    _, cache = dense_prefill(fam, params, ids[:5], 8, slot=0)
    step = jax.jit(fam.decode_step)
    row = jnp.arange(10, dtype=jnp.int32)
    tables = jnp.stack([row, row + 10, row + 20])
    before = fam.slot_state(cache, 1)
    for t in range(5, len(ids)):
        logits, cache = step(
            params, jnp.asarray([ids[t], 7, 9], jnp.int32),
            jnp.asarray([t, 0, 0], jnp.int32), tables, cache,
            jnp.asarray([True, False, False]))
        assert np.abs(np.asarray(logits[0]) - want[t]).max() < F32_LIMIT
    state = fam.slot_state(cache, 0)
    assert max(ref.state_errors(list(state["ssm"]), [
        np.asarray(h) for h in out["states"]])) < 1e-5
    # dead slots leave their state as it was
    after = fam.slot_state(cache, 1)
    assert np.array_equal(before["ssm"], after["ssm"])
    assert np.array_equal(before["conv"], after["conv"])
    got = fam.counters(cache)
    assert got["moe_rows_dropped"] == 0
    assert got["moe_expert_layer_calls_decode"] == 2 * (len(ids) - 5)
    assert got["decode_state_bytes_moved"] == 2 * (
        got["ssm_state_bytes"] // 3) * (len(ids) - 5)


def test_every_consumed_token_leaves_its_selections_in_the_slots_log(tiny):
    """Chunked prefill (the last chunk padded past the end of the slot's
    row) then decode: the log holds the reference's own selections at every
    consumed position, a dead slot's row is untouched, and the reference
    routed BY the log is the reference."""
    cfg, params, ids, want, out = tiny
    fam = family(cfg, max_pages_per_seq=6)          # 24 positions a slot
    cache, pos = fam.init_cache(), 0
    for c, pad in ((8, 8), (9, 16)):                # 8 + 16 pads to 24 ...
        chunk = np.zeros((1, pad), np.int32)
        chunk[0, :c] = ids[pos:pos + c]
        _, _, cache = jax.jit(fam.prefill_chunk)(
            params, jnp.asarray(chunk), jnp.int32(pos), jnp.int32(c),
            jnp.arange(10, dtype=jnp.int32), jnp.int32(2), cache)
        pos += c
    chunk = np.zeros((1, 16), np.int32)             # ... and 17 + 16 past it
    chunk[0, :2] = ids[17:19]
    _, _, cache = jax.jit(fam.prefill_chunk)(
        params, jnp.asarray(chunk), jnp.int32(17), jnp.int32(2),
        jnp.arange(10, dtype=jnp.int32), jnp.int32(2), cache)
    row = jnp.arange(10, dtype=jnp.int32)
    for t in (19, 20):
        _, cache = jax.jit(fam.decode_step)(
            params, jnp.asarray([3, 5, ids[t]], jnp.int32),
            jnp.asarray([0, 0, t], jnp.int32),
            jnp.stack([row + 10, row + 20, row]), cache,
            jnp.asarray([False, False, True]))
    log = fam.slot_state(cache, 2)["moe_sel"]       # [Le, positions, k]
    assert log.shape == (2, 24, cfg.num_experts_per_tok)
    for layer, route in enumerate(out["routes"]):
        assert np.array_equal(np.sort(log[layer, :21], -1),
                              np.sort(np.asarray(route["own"]), -1))
    assert not fam.slot_state(cache, 0)["moe_sel"].any()
    given = ref.forward(params, model_keys(cfg), ids, given=list(log[:, :21]))
    assert np.abs(np.asarray(given["hidden"] - out["hidden"])).max() < 1e-6
    assert all(float(np.asarray(r["short"]).max()) == 0
               for r in given["routes"])


def test_the_reference_routed_by_other_selections_says_how_far_they_stray(
        tiny):
    cfg, params, ids, want, out = tiny
    own = [np.asarray(r["own"]) for r in out["routes"]]
    other = [np.array(o) for o in own]
    # token 4 of the first layer takes the expert ranked last of all
    # instead of its k-th choice
    unused = sorted(set(range(cfg.n_routed_experts)) - set(own[0][4]))
    other[0][4, -1] = unused[-1]
    got = ref.forward(params, model_keys(cfg), ids, given=other)
    assert float(np.asarray(got["routes"][0]["short"])[4]) > 0
    assert np.asarray(got["routes"][0]["short"])[:4].max() == 0
    assert np.abs(np.asarray(got["hidden"] - out["hidden"])).max() > 1e-4
    assert np.array_equal(np.asarray(got["routes"][0]["own"]), own[0])


def test_the_counters_carry_past_an_int32():
    """A counter is a (high, low) pair on the device: `counters` reads
    high x CARRY + low, and the low word never reaches CARRY."""
    from paddle_tpu.models import nemotron_h as nh
    cfg = nemotron_h_config_tiny()
    params = make(cfg)
    fam = family(cfg)
    cache = fam.init_cache()
    cache["ctr"]["moe_pairs"] = jnp.asarray(
        [[5, 0], [nh.CARRY - 3, 7]], jnp.int32)
    before = fam.counters(cache)["moe_pairs_held"]
    assert before == 5 * nh.CARRY + nh.CARRY - 3 + 7
    _, cache = dense_prefill(fam, params, prompt(cfg, 6), 8, cache=cache)
    got = fam.counters(cache)
    assert got["moe_pairs_held"] - before == 6 * cfg.num_experts_per_tok * 2
    assert int(np.asarray(cache["ctr"]["moe_pairs"])[1].max()) < nh.CARRY
    assert got["moe_rows_dropped"] == 0


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """What the shares of the routed experts give, the shared expert counted
    once, is what the uncut reference layer gives."""
    whole = nemotron_h_config_tiny(hybrid_override_pattern="E",
                                   num_hidden_layers=1)
    params = make(whole)
    lp = {name: leaf[0] for name, leaf in params[1]["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (13, whole.hidden_size))
    want, _ = ref.moe_layer(x, lp, model_keys(whole))
    want = np.asarray(want - x)
    valid = jnp.ones((13,), bool)
    total, pairs = 0.0, 0
    for rank in range(4):
        cfg = dataclasses.replace(whole, experts_held=(4 * rank, 4))
        share = dict(lp, we_up=lp["we_up"][4 * rank:4 * rank + 4],
                     we_down=lp["we_down"][4 * rank:4 * rank + 4])
        out, rows, beyond, sel = latent_moe(cfg, share, x, valid)
        # the rank's own share against the reference given the same share
        ref_out, route = ref.moe_layer(x, share, model_keys(cfg))
        assert np.abs(np.asarray(out) - np.asarray(ref_out - x)).max() < 1e-5
        assert np.array_equal(np.sort(sel, -1), np.sort(route["own"], -1))
        held = (np.asarray(sel) >= 4 * rank) & (np.asarray(sel) < 4 * rank + 4)
        assert int(rows.sum()) == int(held.sum()) and int(beyond) == 0
        pairs += int(rows.sum())
        total = total + np.asarray(out)
    shared = np.asarray(jnp.square(jax.nn.relu(
        ref._rms(x, lp["norm"], 1e-5) @ lp["ws_up"])) @ lp["ws_down"])
    assert pairs == 13 * whole.num_experts_per_tok
    assert np.abs(total - 3 * shared - want).max() < 1e-5


FAULTS = {"layers exchanged": {"layer_order": [1, 0, 2, 3, 4]},
          "D x_t dropped": {"drop_d": True},
          "conv bias dropped": {"drop_conv_bias": True},
          "routed_scaling_factor 1": {"route_scale": 1.0},
          "bfloat16 reference": {"dtype": "bfloat16"}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_reference_lands_far_from_the_system(tiny, fault):
    cfg, params, ids, want, out = tiny
    wrong, wrong_out = reference_logits(params, cfg, ids, FAULTS[fault])
    assert np.abs(wrong - want).max() > 100 * F32_LIMIT
    if fault == "bfloat16 reference":
        assert max(ref.state_errors(wrong_out["states"], [
            np.asarray(h) for h in out["states"]])) > 1e-3


def test_a_bfloat16_state_is_seen_in_its_bits(tiny):
    """A state kept in bfloat16 is a rounding away from the reference's —
    far inside what a bf16 engine strays by — but its bits show it, cast
    back to float32 or not."""
    cfg, params, ids, want, out = tiny
    honest = family(cfg)
    _, cache = dense_prefill(honest, params, ids, 24)
    assert ref.bfloat16_share(honest.slot_state(cache, 1)["ssm"]) < 0.001
    low = family(dataclasses.replace(cfg, ssm_state_dtype="bfloat16"))
    _, cache = dense_prefill(low, params, ids, 24)
    state = low.slot_state(cache, 1)["ssm"]
    assert state.dtype == jnp.bfloat16
    assert ref.bfloat16_share(state) == 1.0
    assert ref.bfloat16_share(np.asarray(state, np.float32)) == 1.0
    assert 1e-4 < max(ref.state_errors(list(state), [
        np.asarray(h) for h in out["states"]])) < 0.05


def test_config_refuses_what_the_path_does_not_have():
    with pytest.raises(ValueError):
        nemotron_h_config_tiny(hybrid_override_pattern="MX").validate()
    with pytest.raises(ValueError):
        nemotron_h_config_tiny(mlp_hidden_act="silu").validate()
    with pytest.raises(ValueError):
        nemotron_h_config_tiny(experts_held=(12, 8)).validate()
    cfg = nemotron_h_config_tiny()
    with pytest.raises(NotImplementedError, match="kv_dtype"):
        family(cfg, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="mesh"):
        family(cfg, mesh=object())
