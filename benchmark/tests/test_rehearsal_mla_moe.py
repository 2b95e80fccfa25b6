"""CPU rehearsal of the latent-attention serve cell (on-chip-measurement
guide section 2, rehearsal 1): the driver's path at the tiny configuration
kept beside this file, the Pallas kernels interpreted, and the planted faults
of ``tools/wrong_model_mla_moe.py`` against a toy's limits.  No number from
these runs is a device number.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_mla_moe.py -q
"""
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))

import jax                                                   # noqa: E402
from benchmark import peaks, run                             # noqa: E402
from benchmark import reference_mla_moe as reference         # noqa: E402
from benchmark.drivers import serve_mla_moe as drv           # noqa: E402
import wrong_model_mla_moe as wrong                          # noqa: E402

PEAK = peaks.lookup("TPU v5 lite")
INTERPRET = dict(interpret=True, attention_impl="pallas")
SEED = 2 ** 31 + 12345              # the driver's seeds pass 32 signed bits
CELL = "serve_longdoc_c64"
NEW_METRICS = ("kernel.mla_decode_roofline_pct", "kernel.mla_attn_share_pct",
               "kernel.mla_prefill_roofline_pct")
JOINED = ("device.idle_pct.serve", "sched.decode_fill_pct",
          "model.horizon_ms_per_step", "serve.mfu_pct",
          "kernel.moe_gmm_weight_roofline_pct",
          "kernel.moe_gmm_serve_share_pct", "moe.experts_touched_pct",
          "model.prefill_dev_tok_s.tput")


def tiny(name):
    return run.load_json(HERE, name + ".json")


@pytest.fixture(autouse=True)
def toy_limits(monkeypatch):
    # toy logits are small and everything is float32: a toy's limits
    monkeypatch.setattr(reference, "SERVE_LOGIT_DELTA", 1e-4)
    monkeypatch.setattr(reference, "SERVE_LOGP_RMS", 1e-4)
    monkeypatch.setattr(reference, "SERVE_STRAY_SHARE", 0.0)
    monkeypatch.setattr(reference, "SERVE_STRAY_SHORT", 0.0)


def test_serve_cell_path_at_a_tiny_size():
    conf, mix = tiny("tiny-serve-mla-moe"), tiny("tiny_closed")
    out = drv.run(conf, mix, SEED, 1.5, False, time.perf_counter(),
                  jax.devices(), PEAK, **INTERPRET)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    facts, check = out["facts"], out["check"]
    assert facts["compiled_in_window"] == 0 and facts["moe_rows_dropped"] == 0
    assert check["check_positions"] == 3 * drv.CHECK_TOKENS
    assert check["selections_strayed"] == 0           # float32: no flip
    assert check["selections"] > 0 and check["reference_s"] > 0
    # the third check prompt's prefix came out of the second one's pages
    assert check["prefix_tokens_from_cache"] \
        == check["prefix_tokens_shared"] == 24
    assert facts["moe_pairs_held"] > 0 and facts["required_flops_window"] > 0
    assert facts["latent_tokens_attended_decode"] > 0
    assert facts["latent_pairs_attended_prefill"] > 0
    assert facts["latent_bytes_per_token"] == 3 * 40 * 4
    assert 0 < facts["moe_experts_touched_decode"] \
        <= facts["moe_experts_held"] * facts["moe_expert_layer_calls_decode"]
    names = ["out_tok_s", "tpot_p90_ms", "setup_s"]
    layer = [(n, run.load_json(ROOT, "benchmark", "layer_metrics",
                               n + ".json")) for n in NEW_METRICS + JOINED]
    units = dict.fromkeys(names + list(NEW_METRICS + JOINED), "%")
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = json.loads(json.dumps(run.result_line(
        out, names, layer, units, 0, dict(device))))
    assert set(line["metrics"]) == set(names)
    # without a trace the metrics that need one are left out, not zero
    line = json.loads(json.dumps(run.result_line(
        out, names, layer, units, 1, dict(device))))
    assert set(line["metrics"]) == {"serve.mfu_pct", "sched.decode_fill_pct",
                                    "moe.experts_touched_pct"}
    assert 0 < line["metrics"]["moe.experts_touched_pct"]["value"] <= 100


ARMS = list(wrong.REFERENCE_FAULTS)


@pytest.fixture(scope="module")
def arms():
    return tiny("tiny-serve-mla-moe"), {}


@pytest.mark.parametrize("arm", ARMS)
def test_every_planted_fault_fails_the_check_and_the_honest_engine_passes(
        arm, arms):
    conf, cache = arms
    if arm not in cache:            # one engine run serves every arm
        for name, ok, facts in wrong.arms_of(conf, SEED, ARMS, jax.devices(),
                                             **INTERPRET):
            cache[name] = (ok, facts)
    ok, facts = cache[arm]
    assert ok == (arm == "honest"), (arm, facts)
    if arm == "bf16_reference":
        # a precision below the stated one is refused by a COMPARISON
        assert facts["logp_rms_error"] > facts["logp_rms_limit"]


def test_the_manifest_holds_the_cell_and_its_files():
    m = run.load_json(ROOT, "BENCHMARK.json")
    cell, conf, mix, names, layer, units = run.load_cell(ROOT, CELL)
    assert conf["driver"] == "serve_mla_moe" and cell["chips"] == 1
    assert mix["clients"] == conf["engine"]["num_slots"] == 64
    assert mix["prompt_len"] == {"dist": "loguniform", "min": 2048,
                                 "max": 8192}
    assert names[-1] == "setup_s" and {"out_tok_s", "tpot_p90_ms"} <= set(
        names)
    assert set(NEW_METRICS + JOINED) == {n for n, _ in layer}
    entry = [c for c in m["configs"] if c["name"] == cell["config"]][0]
    assert set(entry["reduced"]) == set(conf["reduced"]) \
        == set(conf["published"])
    # every published width is kept
    for key, want in {"hidden_size": 2048, "intermediate_size": 11264,
                      "moe_intermediate_size": 1408, "kv_lora_rank": 512,
                      "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                      "v_head_dim": 128, "num_attention_heads": 16,
                      "num_experts_per_tok": 6, "n_shared_experts": 2,
                      "routed_scaling_factor": 2.446,
                      "rope_theta": 800000}.items():
        assert conf[key] == want, key
    assert conf["published"] == {"num_hidden_layers": 27,
                                 "n_routed_experts": 64,
                                 "vocab_size": 163840}
    cfg = drv.model_config(conf)
    assert cfg.held() == (0, 16) and cfg.n_routed_experts == 64
    # a prompt at its longest plus its answer fits a slot's pages
    eng = conf["engine"]
    assert eng["max_pages_per_seq"] * eng["page_size"] \
        >= mix["prompt_len"]["max"] + mix["output_len"]["max"]
