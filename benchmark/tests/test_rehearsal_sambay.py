"""CPU rehearsal of the SambaY serve cell (on-chip-measurement guide section
2, rehearsal 1): the driver's path at the tiny configuration kept beside this
file, the Pallas kernel interpreted, and the planted faults of
``tools/wrong_model_sambay.py`` against a toy's limits.  No number from these
runs is a device number.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_rehearsal_sambay.py -q
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
from benchmark import flops_sambay as flops                  # noqa: E402
from benchmark import peaks, run                             # noqa: E402
from benchmark import reference_sambay as reference          # noqa: E402
from benchmark.drivers import serve_sambay as drv            # noqa: E402
import wrong_model_sambay as wrong                           # noqa: E402

PEAK = peaks.lookup("TPU v5 lite")
INTERPRET = dict(interpret=True, attention_impl="pallas")
SEED = 2 ** 31 + 12345              # the driver's seeds pass 32 signed bits
CELL = "serve_longreason_c64"
NEW_METRICS = ("kernel.shared_kv_decode_roofline_pct",
               "kernel.shared_kv_attn_share_pct",
               "kernel.window_attn_decode_roofline_pct",
               "kernel.selective_update_roofline_pct",
               "kernel.selective_scan_roofline_pct",
               "model.prefill_cross_decoder_tokens_pct")
JOINED = ("device.idle_pct.serve", "sched.decode_fill_pct",
          "model.horizon_ms_per_step", "serve.mfu_pct",
          "model.prefill_dev_tok_s.tput")


def tiny(name):
    return run.load_json(HERE, name + ".json")


@pytest.fixture(autouse=True)
def toy_limits(monkeypatch):
    # toy logits are small and everything is float32: a toy's limits; and a
    # toy's decode (40 tokens pass a wrap of a 16-row ring twice)
    monkeypatch.setattr(reference, "SERVE_LOGIT_DELTA", 1e-4)
    monkeypatch.setattr(reference, "SERVE_STATE_RTOL", 1e-4)
    monkeypatch.setattr(reference, "SERVE_WINDOW_RTOL", 1e-4)
    monkeypatch.setattr(drv, "CHECK_TOKENS", 40)


def test_serve_cell_path_at_a_tiny_size():
    conf, mix = tiny("tiny-serve-sambay"), tiny("tiny_closed")
    out = drv.run(conf, mix, SEED, 1.5, False, time.perf_counter(),
                  jax.devices(), PEAK, **INTERPRET)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    facts, check = out["facts"], out["check"]
    assert facts["compiled_in_window"] == 0
    assert check["check_positions"] == 3 * drv.CHECK_TOKENS
    # a dense prefill, two chunks with a short last one, three chunks
    assert [-(-t // 16) for t in check["check_prompt_lens"]] == [1, 2, 3]
    assert check["state_bf16_share"] < 0.01 and check["reference_s"] > 0
    assert len(check["state_errors_by_layer"]) == 3
    assert len(check["window_errors_by_layer"]) == 2
    # one token a prompt prefilled entered the second half of the model
    assert 0 < facts["prefill_tokens_cross_decoder"] \
        < facts["prefill_tokens_self_decoder"] \
        == facts["prefill_tokens_dispatched"]
    assert facts["shared_kv_bytes_per_token"] == 2 * 4 * 8 * 4 \
        == flops.shared_kv_bytes_per_token(conf, itemsize=4)
    assert facts["shared_kv_tokens_attended_decode"] > 0
    assert facts["window_tokens_attended_decode"] > 0
    assert facts["required_flops_window"] > 0
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
                                    "model.prefill_cross_decoder_tokens_pct"}
    assert 0 < line["metrics"]["model.prefill_cross_decoder_tokens_pct"][
        "value"] < 100


ARMS = list(wrong.reference_faults({"sliding_window": 16}))


@pytest.fixture(scope="module")
def arms():
    return tiny("tiny-serve-sambay"), {}


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
    if arm in ("bf16_reference", "bf16_scan_operands", "window_511",
               "window_513", "lam0_next"):
        # refused by a COMPARISON of the state the slots are left with
        assert facts["worst_state_error"] > facts["state_rtol"]
    if arm == "m_after_gate":
        # nothing in the first half reads the memory: the logits alone
        assert facts["worst_logit_gap"] > facts["logit_delta"]
        assert facts["worst_state_error"] <= facts["state_rtol"]


def test_the_manifest_holds_the_cell_and_its_files():
    m = run.load_json(ROOT, "BENCHMARK.json")
    cell, conf, mix, names, layer, units = run.load_cell(ROOT, CELL)
    assert conf["driver"] == "serve_sambay" and cell["chips"] == 1
    assert mix["clients"] == conf["engine"]["num_slots"] == 64
    assert mix["prompt_len"] == {"dist": "loguniform", "min": 2048,
                                 "max": 8192}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert names[-1] == "setup_s" and {"out_tok_s", "tpot_p90_ms"} <= set(
        names) and "ttft_p90_ms" not in names
    assert set(NEW_METRICS + JOINED) <= {n for n, _ in layer}
    entry = [c for c in m["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == [] and conf["reduced"] == {}
    # every key of the catalog row, as published
    for key, want in {"embd_pdrop": 0, "hidden_act": "silu",
                      "hidden_size": 2560, "intermediate_size": 10240,
                      "layer_norm_eps": 1e-05,
                      "max_position_embeddings": 262144, "mb_per_layer": 2,
                      "model_type": "phi4flash", "num_attention_heads": 40,
                      "num_hidden_layers": 32, "num_key_value_heads": 20,
                      "resid_pdrop": 0, "sliding_window": 512,
                      "tie_word_embeddings": True, "mlp_bias": False,
                      "lm_head_bias": False, "vocab_size": 200064}.items():
        assert conf[key] == want, key
    cfg = drv.model_config(conf)
    assert (cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, cfg.head_dim) \
        == (5120, 16, 160, 64)
    # the issue's arithmetic: 3.85 B parameters, 5,120 B a token in the one
    # store, 3.2 MB of recurrent state a slot
    total = flops.self_decoder_params(conf) + flops.cross_decoder_params(
        conf) + flops.head_params(conf)
    assert 3.84e9 < total < 3.86e9
    assert flops.shared_kv_bytes_per_token(conf) == 5120
    assert flops.state_bytes_per_slot(conf) == 9 * (5120 * 16 * 4
                                                    + 3 * 5120 * 2)
    # a prompt at its longest plus its answer fits a slot's pages
    eng = conf["engine"]
    assert eng["max_pages_per_seq"] * eng["page_size"] \
        >= mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert conf["sliding_window"] % eng["page_size"] == 0
