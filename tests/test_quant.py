"""Quantized serving plane (ISSUE 15 tentpole): int8/fp8 KV pages with
per-(page, head, row) absmax scales, per-channel quantized serving
weights, and the parity harness.

Acceptance pinned here:
  * per-channel `quantize_weight`/`dequantize_weight` round-trips (the
    satellite — per-tensor scales are too coarse for attention
    projections);
  * the KV codec round-trips within its grid resolution and is
    write-order independent (one row quantizes the same everywhere);
  * the QUANTIZED engine keeps every self-exactness invariant the f32
    engine holds: cache on/off, chunked prefill, preemption re-prefill,
    speculative decoding, overlap — all bit-equal against the plain
    quantized engine (parity vs f32 is exact-match gated, not
    bit-equality);
  * snapshot/restore round-trips per-page scales EXACTLY — full_kv and
    compact, including restore into a different-geometry pool (and a
    different kv_dtype) falling back to re-prefill — and the conftest
    refcount leak guard runs on every quantized engine built here;
  * `Telemetry.sample_memory` reports pool occupancy in BYTES for the
    active kv_dtype;
  * a warmed quantized engine performs ZERO steady-state recompiles with
    the same per-fn variant counts as the f32 engine (PERF.md §12:
    per-dtype engines each hold the documented table — no new variants).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.models.llama import (llama_config_tiny,
                                     build_functional_llama)
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.quantization import dequantize_weight, quantize_weight
from paddle_tpu.observability import Telemetry
from paddle_tpu.resilience import inject
from paddle_tpu.serving import (AutoscalePolicy, ElasticFleet,
                                EngineSnapshotManager, ReplicaFleet,
                                VirtualClock, make_scenario, replay_fleet)
from paddle_tpu.serving.quant import (dequantize_kv, kv_spec, page_bytes,
                                      parity_report, parity_scenarios,
                                      quantize_kv, quantize_params)

rng = np.random.default_rng(15)

CFG = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        ep, bp, hp, *_ = build_functional_llama(CFG,
                                                key=jax.random.PRNGKey(1))
        _PARAMS = (ep, bp, hp)
    return _PARAMS


def _mk(**kw):
    base = dict(num_slots=2, page_size=4, num_pages=40, max_pages_per_seq=16,
                attention_impl="ref", prompt_bucket=8, decode_horizon=2,
                kv_dtype="int8")
    base.update(kw)
    return ServingEngine(_params(), CFG, **base)


# one prompt bucket (lengths <= prompt_bucket=8): every engine compiles ONE
# dense-prefill executable — tier-1 budget is compile-dominated on CPU
_PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32) for t in (5, 7, 3, 6)]
_REF_CACHE: dict = {}


def _q_refs(kv_dtype="int8", n_new=8):
    """Uninterrupted plain quantized-engine outputs — the bit-equality bar
    every quantized feature intersection is held to."""
    key = (kv_dtype, n_new)
    if key not in _REF_CACHE:
        eng = _mk(kv_dtype=kv_dtype)
        rids = [eng.submit(p, max_new_tokens=n_new) for p in _PROMPTS]
        done = eng.run()
        _REF_CACHE[key] = [list(done[r].generated) for r in rids]
    return _REF_CACHE[key]


# ---------------------------------------------------------------------------
# quantization/: per-channel absmax round-trips (the satellite)
# ---------------------------------------------------------------------------
class TestPerChannelWeights:
    def test_per_tensor_default_unchanged(self):
        w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        q, scale = quantize_weight(w)
        assert q.dtype == jnp.int8 and np.ndim(scale) == 0
        deq = dequantize_weight(q, scale)
        assert float(jnp.max(jnp.abs(deq - w))) <= float(scale) * 0.5 + 1e-7

    def test_per_channel_roundtrip_bound(self):
        w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        q, scale = quantize_weight(w, axis=-2)
        assert scale.shape == (1, 8)          # keepdims: broadcast-ready
        deq = dequantize_weight(q, scale)
        # per-channel bound: each column's error <= half ITS OWN step
        err = np.asarray(jnp.max(jnp.abs(deq - w), axis=0))
        assert (err <= np.asarray(scale)[0] * 0.5 + 1e-7).all()

    def test_per_channel_beats_per_tensor_on_skewed_channels(self):
        # one hot column: a per-tensor scale flattens every other column's
        # resolution — the reason attention projections need per-channel
        w = rng.normal(size=(32, 6)).astype(np.float32)
        w[:, 0] *= 100.0
        w = jnp.asarray(w)
        qt, st = quantize_weight(w)
        qc, sc = quantize_weight(w, axis=-2)
        cold = np.s_[:, 1:]
        err_t = float(jnp.max(jnp.abs(dequantize_weight(qt, st)[cold]
                                      - w[cold])))
        err_c = float(jnp.max(jnp.abs(dequantize_weight(qc, sc)[cold]
                                      - w[cold])))
        assert err_c < err_t / 10

    def test_stacked_block_weights_axis(self):
        # [L, in, out] serving blocks quantize per (layer, out channel)
        w = jnp.asarray(rng.normal(size=(3, 8, 4)).astype(np.float32))
        q, scale = quantize_weight(w, axis=-2)
        assert scale.shape == (3, 1, 4)
        deq = dequantize_weight(q, scale)
        assert float(jnp.max(jnp.abs(deq - w))) \
            <= float(jnp.max(scale)) * 0.5 + 1e-7

    def test_quantize_params_snaps_matmul_weights_only(self):
        ep, bp, hp = _params()
        ep2, bp2, hp2 = quantize_params(_params(), bits=8)
        # norm gains untouched; matmul weights land ON the int grid
        np.testing.assert_array_equal(np.asarray(bp2["ln1"]),
                                      np.asarray(bp["ln1"]))
        np.testing.assert_array_equal(np.asarray(hp2["ln_f"]),
                                      np.asarray(hp["ln_f"]))
        for leaf in (bp2["wq"], hp2["lm"]):
            q, s = quantize_weight(leaf, axis=-2)
            np.testing.assert_array_equal(np.asarray(dequantize_weight(q, s)),
                                          np.asarray(leaf))
        assert bp2["wq"].shape == bp["wq"].shape
        assert bp2["wq"].dtype == bp["wq"].dtype


# ---------------------------------------------------------------------------
# serving/quant.py: the KV codec
# ---------------------------------------------------------------------------
class TestKvCodec:
    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_roundtrip_bound_and_zero_rows(self, kv_dtype):
        storage, qmax = kv_spec(kv_dtype)
        x = rng.normal(size=(6, 2, 16)).astype(np.float32)
        x[2] = 0.0                            # zero row round-trips exactly
        xj = jnp.asarray(x)
        q, s = quantize_kv(xj, qmax=qmax, dtype=storage)
        assert q.dtype == storage and s.shape == (6, 2)
        deq = np.asarray(dequantize_kv(q, s))
        absmax = np.abs(x).max(axis=-1, keepdims=True)
        # int8: half a step; fp8 e4m3: one part in 2^3 of magnitude range
        bound = absmax * (0.5 / qmax if kv_dtype == "int8" else 0.0625)
        assert (np.abs(deq - x) <= bound + 1e-7).all()
        assert not deq[2].any()

    def test_write_order_independence(self):
        # quantizing rows one at a time == quantizing the batch at once:
        # the property the whole self-exactness matrix rests on
        storage, qmax = kv_spec("int8")
        x = jnp.asarray(rng.normal(size=(5, 2, 8)).astype(np.float32))
        q_all, s_all = quantize_kv(x, qmax=qmax, dtype=storage)
        for i in range(5):
            q_i, s_i = quantize_kv(x[i], qmax=qmax, dtype=storage)
            np.testing.assert_array_equal(np.asarray(q_all[i]),
                                          np.asarray(q_i))
            np.testing.assert_array_equal(np.asarray(s_all[i]),
                                          np.asarray(s_i))

    def test_kv_spec_rejects_unknown(self):
        with pytest.raises(ValueError):
            kv_spec("int4")

    def test_page_bytes_accounting(self):
        # f32 page vs int8+scales page, from the geometry alone
        pb_f = page_bytes(CFG, 4)
        pb_q = page_bytes(CFG, 4, kv_dtype="int8")
        L, hkv, d = 2, 4, 8
        assert pb_f == 2 * L * hkv * 4 * d * 4
        assert pb_q == 2 * L * hkv * 4 * d + 2 * L * hkv * 4 * 4
        assert pb_f / pb_q > 2.0


# ---------------------------------------------------------------------------
# the quantized engine's self-exactness matrix
# ---------------------------------------------------------------------------
class TestQuantEngineExactness:
    def test_cache_on_off_chunked_bit_equal(self):
        refs = _q_refs()
        for kw in (dict(prefix_cache=False), dict(prefill_chunk=4)):
            eng = _mk(**kw)
            rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
            done = eng.run()
            assert [list(done[r].generated) for r in rids] == refs, kw
            eng.check_invariants()

    def test_preemption_reprefill_step_exact(self):
        refs = _q_refs()
        eng = _mk(telemetry=Telemetry())
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        with inject({"serve.pool_pressure": dict(action="trigger",
                                                 after=1, count=3)}):
            for _ in range(6):
                eng.step()
        done = eng.run()
        assert eng.preemptions >= 1, "drill never preempted"
        assert [list(done[r].generated) for r in rids] == refs
        # the degradation ladder keeps its order on quantized pages: the
        # cache is evicted before a request is preempted
        ev = [e["event"] for e in eng.telemetry.flight.events()]
        assert ev.index("evict") < ev.index("preempt"), ev
        eng.check_invariants()

    def test_speculative_and_overlap_bit_equal(self):
        refs = _q_refs()
        for kw in (dict(speculative=4), dict(overlap=True)):
            eng = _mk(**kw)
            rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
            done = eng.run()
            assert [list(done[r].generated) for r in rids] == refs, kw
            eng.check_invariants()

    @pytest.mark.slow
    def test_fp8_deterministic_and_distinct_store(self):
        a = _q_refs("fp8")
        b = _q_refs("fp8")          # cached — re-derive one fresh run
        eng = _mk(kv_dtype="fp8")
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        done = eng.run()
        assert [list(done[r].generated) for r in rids] == a == b
        assert eng._pages_k["q"].dtype == jnp.float8_e4m3fn


# ---------------------------------------------------------------------------
# snapshot/restore: scales round-trip exactly
# ---------------------------------------------------------------------------
class TestQuantSnapshot:
    def _mid_flight(self, **kw):
        eng = _mk(**kw)
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        for _ in range(3):
            eng.step()
        return eng, rids

    def test_full_kv_roundtrip_bit_exact_and_scales_exact(self):
        refs = _q_refs()
        eng, rids = self._mid_flight()
        state = eng.snapshot(mode="full_kv")
        # the snapshot ships data AND scale planes for every referenced
        # page, in the storage dtype
        assert state["kv_k_q"].dtype == np.int8
        assert state["kv_k_s"].dtype == np.float32
        assert state["kv_k_q"].shape[:2] == (2, 4)      # [L, Hkv, ...]
        eng2 = _mk()
        assert eng2.restore(state) == "full_kv"
        # restored scale planes equal the snapshot's EXACTLY
        ids = jnp.asarray(state["kv_pages"].astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(eng2._pages_k["s"][:, :, ids]), state["kv_k_s"])
        np.testing.assert_array_equal(
            np.asarray(eng2._pages_v["q"][:, :, ids]), state["kv_v_q"])
        done = eng2.run()
        assert [list(done[r].generated) for r in rids] == refs
        eng.check_invariants()
        eng2.check_invariants()

    def test_compact_roundtrip_reprefill(self):
        refs = _q_refs()
        eng, rids = self._mid_flight()
        state = eng.snapshot(mode="compact")
        assert "kv_k_q" not in state and "kv_k" not in state
        eng2 = _mk()
        assert eng2.restore(state) == "reprefill"
        done = eng2.run()
        assert [list(done[r].generated) for r in rids] == refs
        eng2.check_invariants()

    def test_full_kv_into_different_geometry_falls_back(self):
        refs = _q_refs()
        eng, rids = self._mid_flight()
        state = eng.snapshot(mode="full_kv")
        eng2 = _mk(num_pages=24)              # smaller pool
        assert eng2.restore(state) == "reprefill"
        done = eng2.run()
        assert [list(done[r].generated) for r in rids] == refs
        eng2.check_invariants()

    @pytest.mark.parametrize(
        "other",
        [None, pytest.param("fp8", marks=pytest.mark.slow)])
    def test_full_kv_into_different_kv_dtype_falls_back(self, other):
        # int8 pages cannot scatter into an f32 (or fp8) store: the raw
        # codes mean different things — restore must re-prefill, which
        # requantizes for the new store
        eng, rids = self._mid_flight()
        state = eng.snapshot(mode="full_kv")
        eng2 = _mk(kv_dtype=other)
        assert eng2.restore(state) == "reprefill"
        done = eng2.run()
        assert len(done) == len(rids)
        eng2.check_invariants()

    @pytest.mark.parametrize(
        "kv_dtype",
        ["int8", pytest.param("fp8", marks=pytest.mark.slow)])
    def test_disk_roundtrip_storage_dtypes(self, tmp_path, kv_dtype):
        # the checkpoint writer/loader must carry int8 and float8 arrays
        # (dtype strings resolve through jnp.dtype on load)
        refs = _q_refs(kv_dtype)
        eng = _mk(kv_dtype=kv_dtype)
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        for _ in range(3):
            eng.step()
        mgr = EngineSnapshotManager(str(tmp_path))
        mgr.save_engine(eng, mode="full_kv")
        eng2 = _mk(kv_dtype=kv_dtype)
        _path, applied = mgr.restore_engine(eng2)
        assert applied == "full_kv"
        done = eng2.run()
        assert [list(done[r].generated) for r in rids] == refs
        eng.check_invariants()
        eng2.check_invariants()


# ---------------------------------------------------------------------------
# telemetry: pool occupancy in BYTES
# ---------------------------------------------------------------------------
def test_sample_memory_reports_bytes():
    tel = Telemetry()
    eng = _mk(telemetry=tel)
    eng.submit(_PROMPTS[0], max_new_tokens=4)
    eng.run()
    rows = tel.memory.rows()
    assert rows, "no memory samples recorded"
    last = rows[-1]
    pb = eng.page_bytes
    assert pb == page_bytes(CFG, 4, kv_dtype="int8")
    assert last["page_bytes"] == pb
    assert last["pool_allocated_bytes"] == eng.pool.num_allocated * pb
    assert last["pool_capacity_bytes"] == eng.pool.num_pages * pb
    assert tel.registry.gauge("mem.pool_capacity_bytes").value \
        == eng.pool.num_pages * pb


# ---------------------------------------------------------------------------
# parity harness: shape and determinism (the gate itself is further down)
# ---------------------------------------------------------------------------
_PARITY_KW = dict(drift_prompts=1, drift_steps=4,
                  engine_kw=dict(page_size=4, prompt_bucket=8,
                                 decode_horizon=2))


def test_parity_report_smoke():
    # tier-1 smoke: ONE scenario, drift pass skipped (the engines alone
    # dominate compile time) — the 3-scenario + drift run and the
    # determinism double-run live in the slow lane
    scen = parity_scenarios(CFG.vocab_size, page_size=4)[:1]
    rep = parity_report(_params(), CFG, kv_dtype="int8", quantize=None,
                        scenarios=scen, drift_prompts=0,
                        engine_kw=_PARITY_KW["engine_kw"])
    for k in ("kv_dtype", "weight_bits", "scenarios", "exact_match",
              "token_match", "max_logit_drift", "mismatched"):
        assert k in rep, k
    assert rep["scenarios"] == 1
    assert 0.0 <= rep["exact_match"] <= 1.0


@pytest.mark.slow
def test_parity_report_shape():
    scen = parity_scenarios(CFG.vocab_size, page_size=4)[:3]
    rep = parity_report(_params(), CFG, kv_dtype="int8", quantize=None,
                        scenarios=scen, **_PARITY_KW)
    assert rep["scenarios"] == 3
    assert 0.0 <= rep["exact_match"] <= 1.0
    assert rep["max_logit_drift"] > 0.0      # quantization is lossy


@pytest.mark.slow
def test_parity_report_deterministic():
    scen = parity_scenarios(CFG.vocab_size, page_size=4)[:3]
    rep = parity_report(_params(), CFG, kv_dtype="int8", quantize=None,
                        scenarios=scen, **_PARITY_KW)
    rep2 = parity_report(_params(), CFG, kv_dtype="int8", quantize=None,
                         scenarios=scen, **_PARITY_KW)
    assert rep == rep2


# ---------------------------------------------------------------------------
# the plane's four contracts with a deployment: parity with the f32 engine,
# users at fixed pool bytes, and the failover and elastic drills re-run
# on quantized pages
# ---------------------------------------------------------------------------
def test_parity_gate_on_the_margin_model():
    """int8 pages + int8 weights keep >= 0.99 of the greedy outputs of the
    f32 engine over the whole scenario set.  The model is margin-
    engineered (blocks x 0.15, LM head tied to the embedding x 4): argmax
    under perturbation on raw random weights measures the noise floor of
    near-uniform logits, not serving quality."""
    ep, bp, hp = _params()
    bp = {k: (v * 0.15 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    rep = parity_report((ep, bp, hp), CFG, kv_dtype="int8", quantize=8,
                        drift_prompts=0, engine_kw=_PARITY_KW["engine_kw"])
    assert rep["scenarios"] == len(parity_scenarios(CFG.vocab_size,
                                                    page_size=4))
    assert rep["exact_match"] >= 0.99, rep


def test_capacity_at_fixed_pool_bytes():
    """Both arms get the SAME byte budget (about three users' worth of
    f32 pages); the int8 arm fits more pages in it and holds >= 1.8 x the
    concurrent users, and neither arm loses a request."""
    n_users, max_new, ps = 12, 8, 4
    r = np.random.default_rng(3)
    prompts = [r.integers(1, 64, (int(t),)).astype(np.int32)
               for t in r.integers(5, 9, n_users)]
    per_user = max((len(p) + max_new - 1 + ps - 1) // ps for p in prompts)
    pool_bytes = (3 * per_user + 1) * page_bytes(CFG, ps)
    peak = {}
    for kv_dtype in (None, "int8"):
        eng = _mk(kv_dtype=kv_dtype, num_slots=n_users,
                  num_pages=pool_bytes // page_bytes(CFG, ps,
                                                     kv_dtype=kv_dtype),
                  max_pages_per_seq=per_user + 1)
        assert eng.pool.num_pages * eng.page_bytes <= pool_bytes
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        peak[kv_dtype] = 0
        while eng._queue or eng.num_active or eng.inflight_depth:
            eng.step()
            peak[kv_dtype] = max(peak[kv_dtype], eng.num_active)
        assert all(len(eng._finished[rid].generated) == max_new
                   for rid in rids), kv_dtype
        eng.check_invariants()
    assert peak["int8"] >= 1.8 * peak[None], peak


def test_fleet_failover_on_quantized_pages(tmp_path):
    """The failover drill with int8 replicas and full-KV snapshots (data
    and scale planes ship together): the crash fires once, no request is
    lost, outputs equal the uninterrupted quantized engine."""
    fleet = ReplicaFleet(lambda: _mk(telemetry=Telemetry()), num_replicas=2,
                         snapshot_root=str(tmp_path), snapshot_every=2,
                         snapshot_mode="full_kv")
    with inject({"serve.crash": dict(match={"engine": "r0"},
                                     at=3)}) as plan:
        frids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
        done = fleet.run()
    assert plan.fired("serve.crash") == 1
    assert fleet.stats()["failovers"] == 1
    assert len(done) == len(frids), "lost requests"
    assert [list(done[f].generated) for f in frids] == _q_refs()


def test_elastic_fleet_on_quantized_pages():
    """The elastic drill with int8 replicas on the round-virtual clock:
    the fleet scales up and back down, no request is lost or empty, and
    every stream equals the uninterrupted quantized engine's."""
    sc = make_scenario("flood", seed=3, n_requests=14, vocab=64,
                       arrival="poisson", mean_interarrival_s=0.2,
                       prompt_len=(3, 8), max_new=(6, 10))
    ref = _mk()
    ref_rids = [ref.submit(q.prompt, max_new_tokens=q.max_new_tokens)
                for q in sc.requests]
    ref_done = ref.run()
    refs = {q.idx: list(ref_done[rid].generated)
            for q, rid in zip(sc.requests, ref_rids)}
    vc = VirtualClock(0.5)
    fleet = ElasticFleet(
        lambda: _mk(telemetry=Telemetry()),
        policy=AutoscalePolicy(
            min_replicas=1, max_replicas=3, queue_growth=2.0,
            queue_min_depth=3.0, growth_window_s=2.0, growth_fire_frac=0.34,
            idle_per_replica=1.0, idle_window_s=2.5, min_samples=3,
            scale_cooldown_s=1.5, dt_per_round=0.5),
        clock=vc)
    res = replay_fleet(fleet, sc, slo_ttft_s=5.0, virtual_clock=vc,
                       collect_tokens=True)
    lost = [rec["idx"] for rec in res["records"]
            if rec["rejected"] or rec["tokens"] == 0]
    assert not lost, lost
    for rec in res["records"]:
        assert rec["stream"] == refs[rec["idx"]], rec["idx"]
    st = fleet.stats()
    assert st["scale_ups"] >= 1 and st["scale_downs"] >= 1, st


# ---------------------------------------------------------------------------
# recompile budget: per-dtype engines hold the SAME variant table
# ---------------------------------------------------------------------------
def test_quant_engine_zero_steady_state_recompiles():
    from paddle_tpu.analysis import sanitize
    eng = _mk(prefill_chunk=4)
    p0 = rng.integers(1, 64, (3,)).astype(np.int32)    # <= chunk: dense
    p1 = rng.integers(1, 64, (6,)).astype(np.int32)    # > chunk: chunked
    p2 = rng.integers(1, 64, (7,)).astype(np.int32)
    tail = rng.integers(1, 64, (3,)).astype(np.int32)

    def trace():
        # p1 first, alone: its retirement parks 2 full pages + a partial
        # tail (3 generated tokens) in the cache.  p3 then extends exactly
        # that written prefix, so its admission attaches the cached
        # PARTIAL page and fires the COW copy — page_copy must land in
        # the warm variant table.  Deterministic: p3 is rebuilt from the
        # (identical) round's own outputs.
        rid1 = eng.submit(p1, max_new_tokens=6)
        done1 = eng.run()
        gen1 = [int(t) for t in done1[rid1].generated]
        p3 = np.concatenate([p1, np.asarray(gen1[:5], np.int32), tail])
        rids = [rid1] + [eng.submit(p, max_new_tokens=6)
                         for p in (p0, p2, p3)]
        done = eng.run()
        eng.release_cache()
        return [list(done[r].generated) for r in rids]

    first = trace()                          # warm every executable
    assert eng.cow_copies >= 1, "trace never exercised the COW copy"
    warm = dict(eng.jit_variants())
    # the per-dtype variant table equals the documented f32 table for the
    # fns this trace exercises (PERF.md §12): ONE executable each — the
    # quantized store adds pytree leaves, not compile keys
    assert warm["prefill"] == 1
    assert warm["prefill_chunk"] == 1
    assert warm["decode_step"] == 1
    assert warm["page_copy"] == 1
    with sanitize(budget=0):
        second = trace()
    assert second == first
    assert eng.jit_variants() == warm
    eng.check_invariants()
