"""CPU rehearsal of the Nemotron-H serve cell (on-chip-measurement guide
section 2, rehearsal 1): the driver's path at the tiny configuration kept
beside this file, the Pallas kernel interpreted, and the planted faults of
``tools/wrong_model_nemotron_h.py`` against a toy's limits.  No number from
these runs is a device number.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_nemotron_h.py -q
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
from benchmark import reference_nemotron_h as reference      # noqa: E402
from benchmark.drivers import serve_nemotron_h as drv        # noqa: E402
import wrong_model_nemotron_h as wrong                       # noqa: E402

PEAK = peaks.lookup("TPU v5 lite")
INTERPRET = dict(interpret=True, attention_impl="pallas")
SEED = 2 ** 31 + 12345              # the driver's seeds pass 32 signed bits
NEW_METRICS = ("serve.mfu_pct", "moe.experts_touched_pct",
               "kernel.moe_gmm_weight_roofline_pct",
               "kernel.moe_gmm_serve_share_pct",
               "kernel.ssm_update_roofline_pct",
               "kernel.ssd_scan_roofline_pct",
               "model.prefill_dev_tok_s.tput")


def tiny(name):
    return run.load_json(HERE, name + ".json")


@pytest.fixture(autouse=True)
def toy_limits(monkeypatch):
    # toy logits are small and everything is float32: a toy's limits
    monkeypatch.setattr(reference, "SERVE_LOGIT_DELTA", 1e-4)
    monkeypatch.setattr(reference, "SERVE_STATE_RTOL", 1e-4)
    monkeypatch.setattr(reference, "SERVE_STRAY_SHARE", 0.0)
    monkeypatch.setattr(reference, "SERVE_STRAY_SHORT", 0.0)


def test_serve_cell_path_at_a_tiny_size():
    conf, mix = tiny("tiny-serve-nemotron-h"), tiny("tiny_closed")
    out = drv.run(conf, mix, SEED, 1.5, False, time.perf_counter(),
                  jax.devices(), PEAK, **INTERPRET)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    facts, check = out["facts"], out["check"]
    assert facts["compiled_in_window"] == 0 and facts["moe_rows_dropped"] == 0
    assert check["check_positions"] == 4 * drv.CHECK_TOKENS
    assert check["selections_strayed"] == 0           # float32: no flip
    assert check["selections"] > 0 and check["reference_s"] > 0
    assert facts["moe_pairs_held"] > 0 and facts["required_flops_window"] > 0
    assert facts["step_s.max"] >= facts["step_s.median"] > 0
    assert 0 <= facts["step_s.max_at"] <= facts["window_s"]
    assert 0 < facts["moe_experts_touched_decode"] \
        <= facts["moe_experts_held"] * facts["moe_expert_layer_calls_decode"]
    names = ["out_tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    layer = [(n, run.load_json(ROOT, "benchmark", "layer_metrics",
                               n + ".json")) for n in NEW_METRICS]
    units = dict.fromkeys(names + list(NEW_METRICS), "%")
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = json.loads(json.dumps(run.result_line(
        out, names, layer, units, 0, dict(device))))
    assert set(line["metrics"]) == set(names)
    # without a trace the metrics that need one are left out, not zero
    line = json.loads(json.dumps(run.result_line(
        out, names, layer, units, 1, dict(device))))
    assert set(line["metrics"]) == {"serve.mfu_pct",
                                    "moe.experts_touched_pct"}
    assert 0 < line["metrics"]["moe.experts_touched_pct"]["value"] <= 100


ARMS = list(wrong.REFERENCE_FAULTS) + list(wrong.ENGINE_FAULTS)


@pytest.fixture(scope="module")
def arms():
    conf = tiny("tiny-serve-nemotron-h")
    return conf, {}


@pytest.mark.parametrize("arm", ARMS)
def test_every_planted_fault_fails_the_check_and_the_honest_engine_passes(
        arm, arms):
    conf, cache = arms
    if arm not in cache:            # one engine run serves every reference arm
        for name, ok, facts in wrong.arms_of(conf, SEED, ARMS, jax.devices(),
                                             **INTERPRET):
            cache[name] = (ok, facts)
    ok, facts = cache[arm]
    assert ok == (arm == "honest"), (arm, facts)
    if arm == "bf16_state":
        assert facts["state_bf16_share"] == 1.0
    if arm in ("bf16_state", "bf16_reference"):
        # a precision below the stated one is refused by a COMPARISON
        assert facts["worst_state_error"] > facts["state_rtol"]


def test_the_manifest_holds_the_cell_and_its_files():
    m = run.load_json(ROOT, "BENCHMARK.json")
    cell, conf, mix, names, layer, units = run.load_cell(ROOT,
                                                         "serve_reason_c64")
    assert conf["driver"] == "serve_nemotron_h" and cell["chips"] == 1
    assert mix["clients"] == conf["engine"]["num_slots"] == 64
    # ttft_p90_ms is NOT reported: ~80 requests finish in a window and its
    # 90th percentile spread 6 % over six seeds, twice what admits a metric;
    # the prefill keeps a per-layer metric of its own in the cell
    assert names == ["out_tok_s", "tpot_p90_ms", "setup_s"]
    assert set(NEW_METRICS) <= {n for n, _ in layer}
    entry = [c for c in m["configs"] if c["name"] == cell["config"]][0]
    assert set(entry["reduced"]) == set(conf["reduced"]) \
        == set(conf["published"])
    # every published width is kept
    for key, want in {"hidden_size": 4096, "mamba_num_heads": 128,
                      "mamba_head_dim": 64, "n_groups": 8,
                      "ssm_state_size": 128, "conv_kernel": 4,
                      "num_attention_heads": 32, "num_key_value_heads": 2,
                      "head_dim": 128, "num_experts_per_tok": 22,
                      "routed_scaling_factor": 5, "moe_latent_size": 1024,
                      "moe_intermediate_size": 2688,
                      "moe_shared_expert_intermediate_size": 5376}.items():
        assert conf[key] == want, key
    assert conf["published"]["n_routed_experts"] == 512
    cfg = drv.model_config(conf)
    assert cfg.held() == (0, 128) and cfg.n_routed_experts == 512
