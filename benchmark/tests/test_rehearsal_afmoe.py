"""CPU rehearsal of the AFMoE train cell (on-chip-measurement guide section
2, rehearsal 1): ``drivers/train_afmoe.py``'s path at the toy configuration
beside this file (hidden 64, 8 heads / 2 KV, window 8 over 32 tokens, 8 of 16
experts held from offset 4, top-4, float32), the registry's fallbacks for the
kernels, every deliberately wrong reference and the planted faults of
``tools/wrong_model_afmoe.py``.  By hand, not tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

No number from these runs is a device number.
"""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
from benchmark import flops_afmoe, peaks, reference_afmoe, run  # noqa: E402
from benchmark.drivers import train_afmoe                    # noqa: E402
from benchmark.drivers.train import batches                  # noqa: E402
from benchmark.tools import wrong_model_afmoe                # noqa: E402

PEAK = peaks.lookup("TPU v5 lite")
SEED = 2 ** 31 + 12345              # the driver's seeds pass 32 signed bits
# a float32 toy against the float32 reference agrees to rounding: hold it to
# a toy's limits, far under what any wrong model reads (0.1 and more)
TOY_LIMITS = {"OUTPUT_REL_L2": 1e-4, "OUTPUT_AGREEING_REL_L2": 1e-4,
              "SELECTION_DIFF_SHARE": 0.0, "GRAD_REL_L2": (1e-3,) * 4,
              "UPDATE_REL_L2": 1e-2}


def tiny(name):
    return run.load_json(HERE, name + ".json")


@pytest.fixture
def toy_limits(monkeypatch):
    for name, value in TOY_LIMITS.items():
        monkeypatch.setattr(reference_afmoe, name, value)


@pytest.fixture(scope="module")
def toy():
    """(the step's first call, the reference's side of it, and what makes
    another of either: params, conf, first, init_opt, step — not donated)."""
    conf, mix = tiny("tiny-train-afmoe"), tiny("tiny_batches")
    cfg, held = train_afmoe.model_config(conf)
    params = train_afmoe.build_params(cfg, held, SEED, jnp.float32)
    init_opt, step = train_afmoe.build_step(cfg, held, conf["step"])
    first = next(batches(mix, cfg.vocab_size, SEED))
    system, _ = train_afmoe.system_outputs(params, conf, init_opt,
                                           jax.jit(step), first)
    want = train_afmoe.reference_side(params, conf, first)
    return system, want, (params, conf, first, init_opt, step)


def test_afmoe_cell_path_prints_its_metrics(toy_limits):
    conf, mix = tiny("tiny-train-afmoe"), tiny("tiny_batches")
    out = train_afmoe.run(conf, mix, SEED, 1.0, False, time.perf_counter(),
                          jax.devices(), PEAK, check_kernels=False)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == out["facts"]["steps"] > 2
    facts = out["facts"]
    # no row dropped: in every step of the window the rows counted per held
    # expert add up to the pairs whose expert is held
    assert facts["moe.rows_dropped"] == 0 and out["check"]["rows_lost"] == 0
    assert facts["compiled_in_window"] == 0
    assert 0 < facts["moe.rows_per_token"] <= 4 * 4       # 4 layers x top-4
    assert facts["moe.load_max_over_mean"] >= 1.0
    assert facts["train_flops_per_token"] == \
        flops_afmoe.train_flops_per_token(conf, 32,
                                          facts["moe.rows_per_token"])
    names = ["train_tok_s", "setup_s"]
    layer = [(n, run.load_json(ROOT, "benchmark", "layer_metrics",
                               n + ".json"))
             for n in ("train.mfu_pct", "moe.expert_load_max_over_mean",
                       "kernel.moe_gmm_roofline_pct",
                       "kernel.window_attn_roofline_pct")]
    units = dict.fromkeys(names + [n for n, _ in layer], "x")
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = json.loads(json.dumps(
        run.result_line(out, names, [], units, 0, dict(device))))
    assert set(line["metrics"]) == set(names)
    # with no trace the counter metrics are there, the trace's are left out
    line = run.result_line(out, [], layer, units, 1, dict(device))
    assert set(line["metrics"]) == {"train.mfu_pct",
                                    "moe.expert_load_max_over_mean"}
    assert line["metrics"]["moe.expert_load_max_over_mean"]["value"] \
        == facts["moe.load_max_over_mean"]


def test_honest_reference_passes(toy, toy_limits):
    system, want, (_, conf, *_) = toy
    ok, got = train_afmoe.check(system, want, conf)
    assert ok, got
    assert got["selection_diff_share"] == 0.0 and got["rows_lost"] == 0
    # the step MOVED what it was to move: every compared leaf and the bias
    assert all((a != b).any() for a, b in zip(system["before"],
                                              system["after"]))
    assert (system["bias_after"] != system["bias_before"]).any()


@pytest.mark.parametrize("variant", reference_afmoe.VARIANTS)
def test_every_wrong_model_fails_the_check(toy, toy_limits, variant):
    system, _, (params, conf, first, *_) = toy
    ok, got = train_afmoe.check(system, train_afmoe.reference_side(
        params, conf, first, variant=variant), conf)
    assert not ok, got
    # by the output and by a gradient, never by the mean loss alone
    assert got["output_rel_l2"] > 100 * TOY_LIMITS["OUTPUT_REL_L2"], got
    assert max(got["grad_rel_l2"].values()) > 10 * 1e-3


def test_bfloat16_where_float32_is_stated_fails_the_check(toy, toy_limits):
    _, want, (params, conf, first, *_) = toy
    low = wrong_model_afmoe.as_system(train_afmoe.reference_side(
        params, conf, first, compute=jnp.bfloat16), params, conf)
    ok, got = train_afmoe.check(low, want, conf)
    assert not ok and got["output_rel_l2"] > TOY_LIMITS["OUTPUT_REL_L2"]


def test_the_reference_routed_by_the_steps_selections_agrees(toy, toy_limits):
    system, _, (params, conf, first, *_) = toy
    ok, got = train_afmoe.check(system, train_afmoe.reference_side(
        params, conf, first, given=list(system["sel"])), conf)
    assert ok, got


@pytest.mark.parametrize("fault", ["unchanged", "half_tokens"])
def test_a_planted_fault_in_the_step_fails_the_check(toy, toy_limits, fault):
    system, want, (params, conf, first, init_opt, step) = toy
    faulty, _ = train_afmoe.system_outputs(
        params, conf, init_opt,
        jax.jit(wrong_model_afmoe.planted(fault, step)), first)
    if fault == "half_tokens":
        faulty = {**system, **{k: faulty[k] for k in ("grads", "after")}}
    ok, got = train_afmoe.check(faulty, want, conf)
    assert not ok, got
    if fault == "unchanged":
        # a leaf left as it was reads exactly 1, and nothing else notices
        assert [got["update_rel_l2"][k] for k in (
            "moe.router.3", "moe.we_down.1.4", "moe.router_bias")] \
            == [1.0, 1.0, 1.0], got
        assert max(got["grad_rel_l2"].values()) < 1e-3
    else:
        assert max(got["grad_rel_l2"].values()) > 0.1, got
        assert max(got["update_rel_l2"].values()) < 1e-2, got


def test_the_configuration_keeps_every_published_width():
    m = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(c for c in m["configs"]
                 if c["name"] == "trinity-mini-train-1of8")
    conf = run.load_json(ROOT, entry["file"])
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "intermediate_size": 6144, "moe_intermediate_size": 1024,
              "num_experts_per_tok": 8, "sliding_window": 2048,
              "route_scale": 2.826}
    assert {k: conf[k] for k in widths} == widths
    assert conf["published"] == {"num_hidden_layers": 32,
                                 "num_dense_layers": 2, "num_experts": 128,
                                 "vocab_size": 200192}
    assert set(conf["reduced"]) == set(entry["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert conf["num_experts"] * 8 == conf["published"]["num_experts"]
    assert conf["vocab_size"] * 8 == conf["published"]["vocab_size"]
    cfg, held = train_afmoe.model_config(conf)
    assert cfg.num_experts == 128 and held == (0, 16)
    assert cfg.kinds() == ("sliding_attention",) * 4 + ("full_attention",)
    # the issue's arithmetic: 705.5 M parameters, ~2.2 GFLOP a token
    params = (flops_afmoe.dense_matmul_params(conf) + 25024 * 2048
              + 4 * 16 * flops_afmoe.expert_params(conf))
    assert abs(params - 705.5e6) < 1e6, params
    per_token = flops_afmoe.train_flops_per_token(conf, 8192, 4 * 1.0)
    assert 2.0e9 < per_token < 2.5e9, per_token
    cell, _, mix, names, layer, _ = run.load_cell(ROOT,
                                                  "train_trinity_b1_s8192")
    assert (mix["batch"], mix["seq"], cell["chips"]) == (1, 8192, 1)
    assert names == ["train_tok_s", "setup_s"]
    assert [n for n, _ in layer] == [
        "device.idle_pct.train", "train.mfu_pct",
        "kernel.window_attn_roofline_pct", "kernel.moe_gmm_roofline_pct",
        "kernel.moe_gmm_share_pct", "moe.expert_load_max_over_mean"]
