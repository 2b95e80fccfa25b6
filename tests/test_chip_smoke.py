"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide §2, rehearsals
1 and 2): the script's phases, called as the plain functions they are, at a
tiny size on the forced-host CPU mesh with the Pallas kernels in interpret
mode — the four-chip phase on four of the virtual devices.  Finds wrong
paths, arguments and control flow before a chip call does, and stays as the
guard.  ALL steering lives here: chip_smoke.main() has no CPU branch, and
run plainly with no TPU it exits non-zero before any phase.
"""
import dataclasses
import json

import numpy as np
import pytest
import jax

import chip_smoke
from paddle_tpu.models.llama import LlamaConfig

SERVE = dict(num_slots=3, page_size=8, max_pages_per_seq=8, prefill_chunk=16,
             prompt_bucket=8, decode_horizon=4, prompt_lens=(5, 20, 12, 37),
             max_new_tokens=6, compare_tokens=3)


def _cfg(heads=4, kv_heads=2, layers=2, hidden=64):
    return LlamaConfig(vocab_size=96, hidden_size=hidden,
                       intermediate_size=128,
                       num_hidden_layers=layers, num_attention_heads=heads,
                       num_key_value_heads=kv_heads,
                       max_position_embeddings=128)


def test_main_without_a_tpu_exits_nonzero_before_any_phase(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--multichip"]) != 0
    out = capsys.readouterr()
    assert out.out == ""                      # no phase line, no result line
    assert "no TPU" in out.err


def test_sizes_are_7b_widths_cut_by_depth_only():
    from paddle_tpu.models.llama import llama_config_7b
    full = llama_config_7b()
    for kind, sizes in chip_smoke.SIZES.items():
        depths = [sizes["serve"]["layers"], sizes["train"]["layers"]] \
            + [arm["layers"] for arm in sizes["multichip"]["arms"]]
        for layers in depths:
            cut = chip_smoke.cut_config(layers)
            assert dataclasses.replace(
                cut, num_hidden_layers=full.num_hidden_layers) == full, kind
        # the f32 arm is where the repo's TP contract (tokens equal) binds
        assert [(a["dtype"], a.get("matmul_precision"), a["min_agreement"])
                for a in sizes["multichip"]["arms"]][-1] == \
            ("float32", "highest", 1.0)
        assert sizes["train"]["seq"] == 2048
        assert sizes["serve"]["page_size"] * \
            sizes["serve"]["max_pages_per_seq"] >= max(
                sizes["serve"]["prompt_lens"]) \
            + sizes["serve"]["max_new_tokens"]


def test_kernel_parity_phase_interpret():
    facts = chip_smoke.kernel_parity_phase(_cfg(), SERVE, interpret=True)
    assert set(facts["cases"]) == {"decode", "chunk", "verify"}
    assert facts["cases"]["chunk"]["q"] == [1, SERVE["prefill_chunk"], 4, 16]
    json.dumps(facts)                          # every fact is printable


def test_serve_phase_interpret():
    lines = []
    facts = chip_smoke.serve_phase(
        _cfg(), SERVE, attention_impl="pallas", interpret=True,
        report=lambda phase, **f: lines.append(phase))
    assert lines == ["serve", "serve_vs_ref"]  # facts first, verdict after
    ran = facts["executables"]
    assert ran["prefill"] and ran["prefill_chunk"] and ran["decode_step"]
    assert facts["tokens_generated"] == \
        len(SERVE["prompt_lens"]) * SERVE["max_new_tokens"]
    assert facts["depth"] == 2 and facts["tp_degree"] == 1
    # interpret mode lowers the kernel to plain HLO: main() fails the run on
    # exactly this fact when it is False on the chip
    assert facts["decode_has_tpu_custom_call"] is False
    assert facts["vs_ref_engine"]["agreement"] >= \
        chip_smoke.KERNEL_VS_REF_FLOOR
    json.dumps(facts)


def test_train_phase_with_interpret_kernels(monkeypatch):
    from paddle_tpu.core import dispatch
    from paddle_tpu.ops.pallas import fused
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def _fa_causal(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def _rms(x, w, epsilon=1e-6):
        return fused.rms_norm(x, w, eps=epsilon, interpret=True)

    # what register_all() does on a TPU, with the kernels interpreted
    for name, fn in (("flash_attention_causal", _fa_causal),
                     ("rms_norm", _rms)):
        fn.__module__ = "paddle_tpu.ops.pallas.interpreted_for_rehearsal"
        monkeypatch.setitem(dispatch._KERNELS, name,
                            {**dispatch._KERNELS.get(name, {}), "pallas": fn})
    kernels = chip_smoke.train_kernels()
    assert set(kernels) == {"flash_attention_causal", "rms_norm"}
    # hidden 128, seq 128: the smallest shapes both kernels tile
    facts = chip_smoke.train_phase(
        _cfg(hidden=128), dict(batch=1, seq=128, steps=3))
    assert len(facts["losses"]) == 3
    assert facts["losses"][-1] < facts["losses"][0]
    assert facts["tpu_custom_calls"] == 0      # interpreted here
    json.dumps(facts)


def test_train_kernels_refuses_the_jnp_defaults():
    # on the CPU the registry holds no Pallas override: the check that
    # guards the chip run must say so, not pass
    with pytest.raises(RuntimeError, match="Pallas"):
        chip_smoke.train_kernels()


def test_multichip_phase_on_four_virtual_devices():
    sizes = dict(SERVE, tp=4, prompt_lens=(5, 20, 12), max_new_tokens=5,
                 arms=(dict(dtype="bfloat16", layers=2, min_agreement=0.25),
                       dict(dtype="float32", layers=1,
                            matmul_precision="highest", min_agreement=1.0)))
    lines = []
    arms = chip_smoke.multichip_phase(
        lambda layers: _cfg(heads=4, kv_heads=4, layers=layers), sizes,
        jax.devices()[:4], attention_impl="pallas", interpret=True,
        report=lambda phase, **f: lines.append(phase))
    assert lines == [f"multichip/{d}/{part}"
                     for d in ("bfloat16", "float32")
                     for part in ("tp_engine", "one_chip_engine",
                                  "tp_vs_one_chip")]
    for dtype, layers in (("bfloat16", 2), ("float32", 1)):
        tp = arms[dtype]["tp_engine"]
        assert tp["tp_degree"] == 4 and tp["decode_has_all_reduce"]
        assert tp["dtype"] == dtype and tp["depth"] == layers
        assert tp["matmul_precision"] == \
            ("highest" if dtype == "float32" else "default")
        for name, axis in (("pages_k", 1), ("wq", 2), ("wgate", 2),
                           ("wdown", 1)):
            placed = tp["placed"][name]
            assert len(placed["devices"]) == 4
            assert placed["shard"][axis] * 4 == placed["global"][axis]
        assert arms[dtype]["one_chip_engine"]["tp_degree"] == 1
    assert arms["float32"]["tp_vs_one_chip"]["tokens_equal"]
    json.dumps(arms)


def test_multichip_phase_fails_an_arm_below_its_floor(monkeypatch):
    # the verdict binds: an arm whose engines disagree ends the run
    monkeypatch.setattr(chip_smoke, "token_agreement", lambda a, b: {
        "tokens_equal": False, "first_break": [0], "agreement": 0.0})
    sizes = dict(SERVE, tp=2, prompt_lens=(5,), max_new_tokens=2,
                 arms=(dict(dtype="float32", layers=1, min_agreement=1.0),))
    with pytest.raises(RuntimeError, match="TP=2 engine vs one-chip"):
        chip_smoke.multichip_phase(
            lambda layers: _cfg(heads=4, kv_heads=4, layers=layers), sizes,
            jax.devices()[:2], attention_impl="ref",
            report=lambda phase, **f: None)


def test_token_agreement_reports_first_break():
    a = [[1, 2, 3, 4], [5, 6, 7, 8]]
    b = [[1, 2, 9, 4], [5, 6, 7, 8]]
    got = chip_smoke.token_agreement(a, b)
    assert got == {"tokens_equal": False, "first_break": [2, None],
                   "agreement": 0.75}
    assert chip_smoke.token_agreement(a, a)["tokens_equal"] is True


def test_device_phase_fetches_a_complex_array(tmp_path):
    facts = chip_smoke.device_phase(str(tmp_path))
    assert facts["platform"] == "cpu" and len(facts["complex64_fetch"]) == 3
    assert np.complex64(facts["complex64_fetch"][0]) == np.complex64(-3 + 4j)


def test_hybrid_phase_interpret():
    """`chip_smoke.py --hybrid`'s phase at the recurrent family's tiny
    size: its three executables run through the engine, nothing dropped."""
    from paddle_tpu.models.nemotron_h import nemotron_h_config_tiny
    seen = {}
    out = chip_smoke.hybrid_phase(
        nemotron_h_config_tiny(vocab_size=96, experts_held=(4, 8)), SERVE,
        attention_impl="pallas", interpret=True, dtype="float32",
        report=lambda phase, **facts: seen.update({phase: facts}))
    assert set(seen) == {"hybrid", "hybrid_vs_ref"}
    assert out["family"] == "nemotron_h" and out["moe_pairs_held"] > 0
    assert out["vs_ref_engine"]["tokens_equal"]
    json.dumps(seen)


def test_grouped_product_phase_interpret():
    """`chip_smoke.py --hybrid` calls the grouped-matmul kernel and
    ``ragged_dot`` directly on one set of operands before its engine runs:
    both products, a decode and a prefill row bound, empty groups."""
    from paddle_tpu.models.nemotron_h import nemotron_h_config_tiny
    cfg = nemotron_h_config_tiny(n_routed_experts=32, experts_held=(8, 8),
                                 moe_latent_size=128,
                                 moe_intermediate_size=256)
    seen = chip_smoke.grouped_product_phase(cfg, (32, 256), interpret=True)
    assert sorted(seen) == ["decode.128x256", "decode.256x128",
                            "prefill.128x256", "prefill.256x128"]
    assert [seen[k]["tiles"][0] for k in sorted(seen)] == [64, 64, 128, 128]
    assert all(0 < v["experts_touched"] < 8 and v["rows_counted"]
               < v["rows_bound"] for v in seen.values())
    json.dumps(seen)


def test_hybrid_sizes_name_a_benchmark_configuration_at_published_widths():
    for kind, sizes in chip_smoke.SIZES.items():
        cfg, conf = chip_smoke.hybrid_config(sizes["hybrid"]["config"])
        assert (cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.ssm_state_size, cfg.num_key_value_heads,
                cfg.num_experts_per_tok, cfg.moe_latent_size) == (
                    4096, 128, 64, 128, 2, 22, 1024), kind
        hy = sizes["hybrid"]
        assert hy["page_size"] * hy["max_pages_per_seq"] >= max(
            hy["prompt_lens"]) + hy["max_new_tokens"]
        assert min(hy["prompt_lens"]) <= hy["prefill_chunk"] \
            < max(hy["prompt_lens"])


def _latent_cfg(**kw):
    from paddle_tpu.models.mla_moe import mla_moe_config_tiny
    return mla_moe_config_tiny(vocab_size=96, experts_held=(4, 8), **kw)


def test_latent_kernel_phase_interpret():
    """`chip_smoke.py --latent` first holds the latent-page kernel to its
    plain form: decode and a run's segments, ragged, an idle slot."""
    facts = chip_smoke.latent_kernel_phase(
        _latent_cfg(kv_lora_rank=120),
        dict(SERVE, num_slots=4, max_pages_per_seq=16),
        interpret=True)
    assert sorted(facts["cases"]) == ["chunk", "decode"]
    assert facts["row"] == [128, 128]
    assert all(c["max_abs_err"] <= chip_smoke.KERNEL_TOL
               for c in facts["cases"].values())
    json.dumps(facts)


def test_latent_phase_interpret():
    """... then runs the family's engine: three executables, a prefix hit,
    the kernel engine's tokens those of the plain engine."""
    seen = {}
    sizes = dict(SERVE, prompt_lens=(5, 20, 12, 37), max_pages_per_seq=8)
    out = chip_smoke.latent_phase(
        _latent_cfg(), sizes, attention_impl="pallas", interpret=True,
        dtype="float32",
        report=lambda phase, **facts: seen.update({phase: facts}))
    assert set(seen) == {"latent", "latent_vs_ref"}
    assert out["family"] == "mla_moe" and out["moe_pairs_held"] > 0
    assert out["prefix_tokens_from_cache"] >= 32
    assert out["prefix_hit_vs_cold"]["tokens_equal"]
    assert out["vs_ref_engine"]["tokens_equal"]
    json.dumps(seen)


def test_latent_sizes_name_a_benchmark_configuration_at_published_widths():
    for kind, sizes in chip_smoke.SIZES.items():
        la = sizes["latent"]
        cfg, conf = chip_smoke.latent_config(la["config"], la["layers"])
        assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank,
                cfg.qk_rope_head_dim, cfg.qk_nope_head_dim, cfg.v_head_dim,
                cfg.moe_intermediate_size, cfg.num_experts_per_tok,
                cfg.num_hidden_layers) == (2048, 16, 512, 64, 128, 128, 1408,
                                           6, la["layers"]), kind
        assert la["layers"] > cfg.first_k_dense_replace
        assert la["page_size"] * la["max_pages_per_seq"] >= max(
            la["prompt_lens"]) + la["max_new_tokens"]
        assert min(la["prompt_lens"]) <= la["prefill_chunk"] \
            and 2 * la["prefill_chunk"] < max(la["prompt_lens"])


def test_sambay_phase_interpret():
    """`chip_smoke.py --sambay`: the family's engine (dense prefill, both
    chunk executables, the horizon past a wrap of the window's ring) held to
    the plain reference's logits, states and window rows, and the kernel
    engine's tokens those of the plain engine."""
    from paddle_tpu.models.sambay import sambay_config_tiny
    cfg = sambay_config_tiny(vocab_size=96)
    conf = {"layer_norm_eps": cfg.layer_norm_eps, "num_hidden_layers": 8,
            "num_attention_heads": 8, "num_key_value_heads": 4,
            "sliding_window": 16}
    seen = {}
    sizes = dict(SERVE, page_size=8, prompt_lens=(5, 20, 12, 60),
                 prefill_chunk=24, max_pages_per_seq=12, max_new_tokens=20)
    out = chip_smoke.sambay_phase(
        cfg, conf, sizes, attention_impl="pallas", interpret=True,
        dtype="float32", limits=dict(logit=1e-4, state=1e-4, window=1e-4),
        report=lambda phase, **facts: seen.update({phase: facts}))
    assert set(seen) == {"sambay", "sambay_vs_ref"}
    assert out["family"] == "sambay" and out["depth"] == 8
    assert out["prefill_tokens_cross_decoder"] == 4
    assert out["executables"]["prefill_chunk"] >= 2
    assert out["vs_ref_engine"]["tokens_equal"]
    json.dumps(seen)


def test_sambay_sizes_name_a_benchmark_configuration_at_published_widths():
    for kind, sizes in chip_smoke.SIZES.items():
        sa = sizes["sambay"]
        cfg, conf = chip_smoke.sambay_config(sa["config"], sa["layers"])
        assert (cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.intermediate_size,
                cfg.vocab_size, cfg.sliding_window, cfg.d_inner,
                cfg.mamba_d_state, cfg.num_hidden_layers) == (
                    2560, 40, 20, 10240, 200064, 512, 5120, 16,
                    sa["layers"]) and conf["num_hidden_layers"] == 8, kind
        assert sa["page_size"] * sa["max_pages_per_seq"] >= max(
            sa["prompt_lens"]) + sa["max_new_tokens"]
        assert any(cfg.sliding_window < t <= sa["prefill_chunk"]
                   for t in sa["prompt_lens"])
        assert max(sa["prompt_lens"]) > 2 * sa["prefill_chunk"]
        # a request's decode passes a multiple of the window: the ring wraps
        assert any(t // cfg.sliding_window
                   != (t + sa["max_new_tokens"]) // cfg.sliding_window
                   for t in sa["prompt_lens"])
