"""Tensor-parallel paged serving (ISSUE 18, ROADMAP item 1): the serving
engine sharded over an `mp` mesh axis must preserve every single-chip
guarantee — greedy outputs BIT-EXACT vs the single-chip engine across the
parity scenarios (cache on/off, chunked prefill, speculative K=4), one
AllReduce per transformer layer, and a quantized (EQuARX int8) AllReduce
arm whose greedy outputs still match.

The mesh is 2 of the 8 forced-host CPU devices conftest pins; echo-biased
params (the test_spec_decode / recompile-budget trick) give the greedy
argmax enough margin that the one per-layer psum's reassociation-level
drift (~1e-7 on this geometry) can never flip a token.

quant_collectives is tested the way every Pallas kernel is: the
shard_map collective against its single-device jnp ``*_ref`` (bit-exact),
the ref against the f32 reduction (within the documented
``R * max_chunk_absmax / (2*qmax)`` error bound).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.distributed.quant_collectives import (
    DEFAULT_QMAX, allreduce, fake_quant_chunks, quantized_allreduce,
    quantized_allreduce_ref)
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.models.llama import (build_functional_llama,
                                     llama_config_tiny)


def _mesh(n=2):
    return build_mesh({"mp": n}, devices=jax.devices()[:n])


def _cfg():
    # nkv=2 heads: mp=2 shards one KV head (and 2 q heads) per rank
    return llama_config_tiny(vocab=96, hidden=32, layers=2, heads=4,
                             seq=128)


def _echo_params(cfg, seed=11):
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    bp = {k: (v * 0.05 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    return ep, bp, hp


def _drive(params, cfg, mesh=None, **kw):
    eng = ServingEngine(params, cfg, num_slots=3, page_size=8, num_pages=64,
                        prompt_bucket=16, decode_horizon=4,
                        attention_impl="ref", mesh=mesh, **kw)
    r = np.random.default_rng(7)
    prompts = [r.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
               for t in (5, 8, 13)]
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    done = eng.run()
    outs = [[int(t) for t in done[i].generated] for i in rids]
    eng.check_invariants()
    return outs, eng


# ---------------------------------------------------------------------------
# quant_collectives: the ref pairing + error bound (the PAR001 convention)
# ---------------------------------------------------------------------------
class TestQuantCollectives:
    def test_fake_quant_chunk_error_bound(self):
        r = np.random.default_rng(0)
        x = jnp.asarray(r.normal(0, 3.0, (5, 97)).astype(np.float32))
        fq = fake_quant_chunks(x, chunk=64)
        assert fq.shape == x.shape and fq.dtype == x.dtype
        # symmetric absmax rounding: per-element error <= scale/2, with
        # the global absmax an upper bound on every chunk's absmax
        bound = float(jnp.max(jnp.abs(x))) / (2 * DEFAULT_QMAX) + 1e-7
        assert float(jnp.max(jnp.abs(fq - x))) <= bound
        # zeros round-trip exactly (the padded tail's contract)
        assert float(jnp.max(jnp.abs(
            fake_quant_chunks(jnp.zeros((3, 5)))))) == 0.0

    def test_ref_error_bound_vs_f32_sum(self):
        r = np.random.default_rng(1)
        partials = jnp.asarray(r.normal(0, 2.0, (4, 33)).astype(np.float32))
        q = quantized_allreduce_ref(partials, chunk=16)
        exact = partials.sum(axis=0)
        R = partials.shape[0]
        bound = R * float(jnp.max(jnp.abs(partials))) / (2 * DEFAULT_QMAX) \
            + 1e-6
        err = float(jnp.max(jnp.abs(q - exact)))
        assert 0 < err <= bound, (err, bound)

    def test_quantized_allreduce_matches_ref_under_shard_map(self):
        mesh = _mesh(2)
        r = np.random.default_rng(2)
        partials = jnp.asarray(r.normal(0, 1.0, (2, 48)).astype(np.float32))
        from jax.sharding import PartitionSpec as P

        def body(p):  # graftlint: spmd=mp
            return quantized_allreduce(p[0], "mp", chunk=16)

        out = jax.shard_map(body, mesh=mesh, in_specs=(P("mp"),),
                            out_specs=P(), check_vma=False)(partials)
        ref = quantized_allreduce_ref(partials, chunk=16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_f32_escape_hatch_is_plain_psum(self):
        mesh = _mesh(2)
        r = np.random.default_rng(3)
        partials = jnp.asarray(r.normal(0, 1.0, (2, 32)).astype(np.float32))
        from jax.sharding import PartitionSpec as P

        def body(p):  # graftlint: spmd=mp
            return allreduce(p[0], "mp", quantized=False)

        out = jax.shard_map(body, mesh=mesh, in_specs=(P("mp"),),
                            out_specs=P(), check_vma=False)(partials)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(partials.sum(axis=0)))


# ---------------------------------------------------------------------------
# the TP engine: greedy bit-exactness vs single-chip, scenario matrix
# ---------------------------------------------------------------------------
class TestTPEngineBitExact:
    def test_plain_decode(self):
        cfg = _cfg()
        params = _echo_params(cfg)
        ref, _ = _drive(params, cfg)
        tp, eng = _drive(params, cfg, mesh=_mesh(2))
        assert ref == tp
        st = eng.stats()
        assert st["tp_degree"] == 2
        assert st["quantized_allreduce"] is False

    @pytest.mark.slow
    def test_no_prefix_cache(self):
        cfg = _cfg()
        params = _echo_params(cfg, seed=12)
        ref, _ = _drive(params, cfg, prefix_cache=False)
        tp, _ = _drive(params, cfg, mesh=_mesh(2), prefix_cache=False)
        assert ref == tp

    @pytest.mark.slow
    def test_chunked_prefill_and_speculative_k4(self):
        cfg = _cfg()
        params = _echo_params(cfg, seed=13)
        kw = dict(prefill_chunk=4, speculative=4)
        ref, _ = _drive(params, cfg, **kw)
        tp, eng = _drive(params, cfg, mesh=_mesh(2), **kw)
        assert ref == tp
        assert eng.verify_steps > 0, "speculative verify never dispatched"

    def test_quantized_allreduce_arm_greedy_parity(self):
        # the EQuARX arm is LOSSY on logits but must hold greedy parity on
        # the margin-boosted params (the unit-sized cousin of the
        # exact-match gate over the whole scenario set, next test)
        cfg = _cfg()
        params = _echo_params(cfg, seed=14)
        f32, _ = _drive(params, cfg, mesh=_mesh(2))
        q, eng = _drive(params, cfg, mesh=_mesh(2), quantized_allreduce=True)
        assert eng.stats()["quantized_allreduce"] is True
        assert f32 == q

    def test_quantized_allreduce_parity_over_the_scenario_set(self):
        # the parity harness re-aimed through its per-arm seam: both arms
        # TP, pages and weights f32, so the one difference under the
        # exact-match gate is the int8 grid of the per-layer AllReduce
        from paddle_tpu.serving.quant import parity_report
        cfg = _cfg()
        mesh = _mesh(2)
        rep = parity_report(
            _echo_params(cfg, seed=14), cfg, kv_dtype=None, quantize=None,
            drift_prompts=0, ref_engine_kw={"mesh": mesh},
            q_engine_kw={"mesh": mesh, "quantized_allreduce": True})
        assert rep["scenarios"] >= 8
        assert rep["exact_match"] >= 0.99, rep

    @pytest.mark.slow
    def test_logit_drift_seam_measures_quantized_collectives(self):
        # parity_report/logit_drift's ref_build_kw/q_build_kw seam: drift
        # of the quantized-AllReduce build vs the f32-collective build is
        # nonzero (it measures the int8 grid) and tiny on this geometry
        from paddle_tpu.serving.quant import logit_drift
        cfg = _cfg()
        params = _echo_params(cfg, seed=15)
        mesh = _mesh(2)
        prompt = np.arange(1, 7, dtype=np.int32)
        drift, per_step = logit_drift(
            params, params, cfg, [prompt], kv_dtype=None, steps=3,
            ref_build_kw={"mesh": mesh},
            q_build_kw={"mesh": mesh, "quantized_allreduce": True})
        assert 0 < drift < 0.1, drift
        assert len(per_step[0]) == 3


# ---------------------------------------------------------------------------
# geometry guards + mesh-aware accounting
# ---------------------------------------------------------------------------
class TestTPGuards:
    def test_head_divisibility_guard(self):
        cfg = _cfg()                      # nkv=2: mp=3 cannot shard it
        params = _echo_params(cfg)
        with pytest.raises(ValueError, match="num_key_value_heads"):
            ServingEngine(params, cfg, mesh=_mesh(3), attention_impl="ref")

    def test_page_bytes_is_per_chip(self):
        cfg = _cfg()
        params = _echo_params(cfg)
        single = ServingEngine(params, cfg, num_slots=2, page_size=8,
                               num_pages=16, attention_impl="ref")
        tp = ServingEngine(params, cfg, num_slots=2, page_size=8,
                           num_pages=16, attention_impl="ref", mesh=_mesh(2))
        assert tp.page_bytes == single.page_bytes // 2
        assert tp.tp == 2 and single.tp == 1
        assert single.stats()["tp_degree"] == 1
