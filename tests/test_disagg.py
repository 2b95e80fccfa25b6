"""Disaggregated prefill/decode handoff drills (ISSUE 19).

The transfer primitive (`ServingEngine.export_kv`/`import_kv` — the PR 9
full-KV gather/scatter scoped to a request subset, scale planes included)
and the fleet orchestration above it (`ReplicaFleet(roles=...)`: prefill
replicas export after the first token, decode replicas splice and finish).
Edge cases pinned here: a partial tail page mid-chunked-prefill, int8 AND
fp8 scale planes, a handoff racing its deadline retirement, and every
geometry mismatch falling back to re-prefill with the ladder order
preserved (route -> queue -> reject; migrations never dropped).  The
conftest leak guard re-checks page refcounts on every engine, spliced
destinations included."""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models.llama import (llama_config_tiny,
                                     build_functional_llama, llama_generate)
from paddle_tpu.inference.paged import KVHandoffError, ServingEngine
from paddle_tpu.observability.telemetry import Telemetry
from paddle_tpu.serving import (AutoscalePolicy, ElasticFleet, ReplicaFleet)
from paddle_tpu.serving.routing import PrefixAffinityRouter

rng = np.random.default_rng(41)

CFG = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        ep, bp, hp, *_ = build_functional_llama(CFG,
                                                key=jax.random.PRNGKey(1))
        _PARAMS = (ep, bp, hp)
    return _PARAMS


def _mk(params=None, **kw):
    base = dict(num_slots=2, page_size=4, num_pages=40, max_pages_per_seq=16,
                attention_impl="ref", prompt_bucket=8, decode_horizon=2)
    base.update(kw)
    return ServingEngine(params or _params(), CFG, **base)


_PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32)
            for t in (5, 7, 3, 6)]
_REF_CACHE: dict = {}


def _refs(n_new=8):
    if n_new not in _REF_CACHE:
        _REF_CACHE[n_new] = [
            np.asarray(llama_generate(_params(), CFG, p[None],
                                      max_new_tokens=n_new))[0]
            for p in _PROMPTS]
    return _REF_CACHE[n_new]


def _handoff_one(src, dst, rid, *, steps_first=1):
    """Drive `src` until `rid` is exportable, then export -> cancel ->
    import into `dst`; returns the dst-side rid."""
    for _ in range(steps_first):
        src.step()
    for _ in range(32):
        if src.handoff_ready(rid):
            break
        src.step()
    assert src.handoff_ready(rid), "request never became exportable"
    packet = src.export_kv([rid])
    src.cancel(rid)
    return dst.import_kv(packet)[rid]


# ---------------------------------------------------------------------------
# the transfer primitive
# ---------------------------------------------------------------------------
class TestHandoffPrimitive:
    def test_mismatch_guards_raise_typed(self):
        """Every never-splices-here mismatch is a typed KVHandoffError —
        version, page geometry, kv dtype, and the mp degree whose equality
        is what makes head-sharded planes land rank-local."""
        src = _mk()
        rid = src.submit(_PROMPTS[0], max_new_tokens=4)
        src.step()
        assert src.handoff_ready(rid)
        packet = src.export_kv([rid])
        # unknown rid: typed KeyError, engine untouched
        with pytest.raises(KeyError):
            src.export_kv([rid + 999])
        dst = _mk()
        for field, val, needle in [
                ("version", 0, "version"),
                ("page_size", 8, "page_size"),
                ("kv_dtype", "int8", "kv_dtype"),
                ("tp", 2, "mp degree")]:
            bad = dict(packet, **{field: val})
            with pytest.raises(KVHandoffError, match=needle):
                dst.import_kv(bad)
        # the pristine packet still splices: guards are read-only
        rid2 = dst.import_kv(packet)[rid]
        src.cancel(rid)
        done = dst.run()
        np.testing.assert_array_equal(done[rid2].output_ids, _refs(4)[0])

    def test_mid_chunked_prefill_partial_tail(self):
        """Export mid-chunked-prefill: the 13-token prompt (page_size=4 ->
        a partially filled tail page) has executed one 4-token chunk when
        it ships; the destination resumes the REMAINING chunks and the
        decode, bit-exact vs the uninterrupted engine."""
        n_new = 6
        prompt = rng.integers(1, 64, (13,)).astype(np.int32)
        ref = np.asarray(llama_generate(_params(), CFG, prompt[None],
                                        max_new_tokens=n_new))[0]
        src = _mk(prefill_chunk=4, prompt_bucket=16)
        rid = src.submit(prompt, max_new_tokens=n_new)
        src.step()                       # exactly one chunk executed
        slot = next(sl for sl in src._slots if sl is not None)
        assert slot.prefill_pos is not None, "prefill already finished"
        assert not src.handoff_ready(rid)   # fleet policy would wait...
        packet = src.export_kv([rid])       # ...but the primitive ships it
        assert any(e["prefill_pos"] is not None
                   for e in packet["requests"])
        src.cancel(rid)
        dst = _mk(prefill_chunk=4, prompt_bucket=16)
        rid2 = dst.import_kv(packet)[rid]
        done = dst.run()
        np.testing.assert_array_equal(done[rid2].output_ids, ref)

    @pytest.mark.slow
    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_quantized_scale_planes_travel(self, kv_dtype):
        """Quantized stores ship codes AND scales; the spliced request
        decodes bit-exact vs the same quantized engine uninterrupted."""
        src = _mk(kv_dtype=kv_dtype)
        ref_eng = _mk(kv_dtype=kv_dtype)
        n_new = 6
        rid_r = ref_eng.submit(_PROMPTS[1], max_new_tokens=n_new)
        ref = ref_eng.run()[rid_r].output_ids
        rid = src.submit(_PROMPTS[1], max_new_tokens=n_new)
        src.step()
        packet = src.export_kv([rid])
        keys = set(packet["planes"])
        assert keys == {"kv_k_q", "kv_k_s", "kv_v_q", "kv_v_s"}, keys
        src.cancel(rid)
        dst = _mk(kv_dtype=kv_dtype)
        rid2 = dst.import_kv(packet)[rid]
        done = dst.run()
        np.testing.assert_array_equal(done[rid2].output_ids, ref)

    @pytest.mark.slow
    def test_speculative_draft_rebuilt_on_destination(self):
        """Drafting is the DESTINATION's capability: a greedy request
        spliced into a speculative engine grows a draft there and still
        matches the plain greedy reference."""
        src = _mk()
        dst = _mk(speculative=4)
        rid = src.submit(_PROMPTS[0], max_new_tokens=8)
        rid2 = _handoff_one(src, dst, rid)
        slot = next(sl for sl in dst._slots if sl is not None)
        assert slot.spec_k == 4 and slot.draft is not None
        done = dst.run()
        np.testing.assert_array_equal(done[rid2].output_ids, _refs(8)[0])


# ---------------------------------------------------------------------------
# fleet orchestration: roles, fallbacks, races
# ---------------------------------------------------------------------------
def _factory(**kw):
    def make(role="any"):
        return _mk(telemetry=True, **kw)
    return make


def _tp_factory_and_refs(mp, n_new):
    """Each role on an mp-wide submesh of its own (equal degree, so every
    handoff stays rank-local), and the single-chip references to hold the
    fleet to.  The params are margin-engineered (blocks x 0.15, LM head
    tied to the embedding x 4): the argmax stays above the reassociation
    noise of the per-layer psum."""
    ep, bp, hp = _params()
    bp = {k: (v * 0.15 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    params = (ep, bp, hp)
    devs = jax.devices()

    def make(role="any"):
        sub = devs[:mp] if role == "prefill" else devs[mp:2 * mp]
        return _mk(params, telemetry=True,
                   mesh=build_mesh({"mp": mp}, devices=sub))
    refs = [np.asarray(llama_generate(params, CFG, p[None],
                                      max_new_tokens=n_new))[0]
            for p in _PROMPTS]
    return make, refs


class TestDisaggFleet:
    def test_roles_validation(self):
        def boom(role="any"):
            raise AssertionError("factory must not run on invalid roles")
        with pytest.raises(ValueError, match="one entry per replica"):
            ReplicaFleet(boom, num_replicas=2, roles=["prefill"])
        with pytest.raises(ValueError, match="unknown replica roles"):
            ReplicaFleet(boom, num_replicas=2, roles=["prefill", "verif"])
        with pytest.raises(ValueError, match="decode-capable"):
            ReplicaFleet(boom, num_replicas=2,
                         roles=["prefill", "prefill"])

    @pytest.mark.parametrize("mp", [1, 2])
    def test_disagg_bit_exact_with_kv_transfer_attribution(self, mp):
        """The tentpole path: prefill replica hands every request to the
        decode replica after the first token; outputs bit-equal the
        single-engine references; the transfer is rank-local (equal mp),
        counted, and visible as a kv_transfer attribution segment.  At
        mp=2 both replicas are tensor-parallel, each over its own two
        devices, and the pages that travel are head-sharded."""
        factory, refs = (_factory(), _refs(8)) if mp == 1 \
            else _tp_factory_and_refs(mp, 8)
        fleet = ReplicaFleet(factory, num_replicas=2,
                             roles=["prefill", "decode"],
                             router=PrefixAffinityRouter())
        rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
        done = fleet.run()
        assert len(done) == len(rids), "lost requests"
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        st = fleet.stats()
        assert st["roles"] == {"r0": "prefill", "r1": "decode"}
        assert st["handoffs"] == len(rids)
        assert st["handoff_fallbacks"] == 0 and st["handoffs_pending"] == 0
        kv = st["kv_transfer"]
        assert kv["pages"] > 0 and kv["bytes"] > 0
        assert kv["rank_local_hit_rate"] == 1.0     # equal mp degree
        assert kv["transfer_s"]["count"] == len(rids)
        # router saw both role dimensions on the PR 14 seam
        roles_routed = fleet.router.stats()["routed_by_role"]
        assert roles_routed["prefill"] >= len(rids)
        assert roles_routed["decode"] >= len(rids)
        # the handoff gap classifies as kv_transfer — an EXACT segment
        # (every stitched trace still decomposes with zero residual)
        rep = fleet.attribution_report(top_k=len(rids))
        assert rep["requests"] == len(rids)
        assert rep["exact_requests"] == len(rids)
        assert rep["segments"]["kv_transfer"]["total_s"] > 0.0
        ev = [e["event"] for e in fleet.flight.events()]
        assert "handoff_export" in ev and "handoff" in ev

    def test_mismatch_falls_back_to_reprefill_ladder_intact(self):
        """Decode replica with a different KV geometry: every handoff
        raises typed KVHandoffError, the fleet re-prefills via the normal
        migration rung (never drops, never double-streams), and outputs
        stay bit-exact."""
        def fac(role="any"):
            return _mk(telemetry=True,
                       page_size=4 if role != "decode" else 8)
        fleet = ReplicaFleet(fac, num_replicas=2,
                             roles=["prefill", "decode"])
        rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
        done = fleet.run()
        assert len(done) == len(rids)
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        st = fleet.stats()
        assert st["handoffs"] == 0
        assert st["handoff_fallbacks"] == len(rids)
        assert st["migrations"] >= len(rids)     # the fallback rung
        fb = [e for e in fleet.flight.events()
              if e["event"] == "handoff_fallback"]
        assert fb and "page_size" in fb[0]["reason"]

    @pytest.mark.slow
    def test_handoff_races_deadline_retirement(self):
        """The deadline fires between export and the destination's first
        decode step: the request still resolves exactly once (timed out,
        zero loss), and later requests keep flowing."""
        t = [0.0]

        def clock():
            return t[0]

        def fac(role="any"):
            return _mk(telemetry=Telemetry(clock=clock))

        fleet = ReplicaFleet(fac, num_replicas=2,
                             roles=["prefill", "decode"], clock=clock)
        doomed = fleet.submit(_PROMPTS[0], max_new_tokens=8, timeout=5.0)
        fleet.step()                  # prefill + first token; phase B exports
        assert fleet._pending_handoffs, "expected an in-flight packet"
        t[0] = 10.0                   # deadline passes mid-transfer
        done = fleet.run()
        assert done[doomed].timed_out
        assert len(done[doomed].generated) >= 1   # first token was banked
        # the fleet is not wedged: a fresh request completes bit-exact
        rid = fleet.submit(_PROMPTS[1], max_new_tokens=8)
        done = fleet.run()
        np.testing.assert_array_equal(done[rid].output_ids, _refs(8)[1])

    @pytest.mark.slow
    def test_chunked_prefill_spec_decode_disagg(self):
        """Chunked prefill on the prefill replica, speculative decode on
        the decode replica — the roles keep their own capabilities and
        greedy outputs stay bit-exact."""
        def fac(role="any"):
            if role == "prefill":
                return _mk(telemetry=True, prefill_chunk=4)
            return _mk(telemetry=True, speculative=4)
        fleet = ReplicaFleet(fac, num_replicas=2,
                             roles=["prefill", "decode"])
        rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
        done = fleet.run()
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        assert fleet.stats()["handoffs"] == len(rids)

    @pytest.mark.slow
    def test_elastic_role_policies_scale_independently(self):
        """ElasticFleet(role_policies=...): per-role sentinels — decode
        pressure (pending packets + decode queues) grows the decode pool
        without touching prefill, and scale events carry the role."""
        fleet = ElasticFleet(
            _factory(),
            role_policies={
                "prefill": AutoscalePolicy(min_replicas=1, max_replicas=2,
                                           queue_min_depth=2.0,
                                           growth_window_s=3.0,
                                           scale_cooldown_s=2.0),
                "decode": AutoscalePolicy(min_replicas=1, max_replicas=2,
                                          queue_min_depth=2.0,
                                          growth_window_s=3.0,
                                          scale_cooldown_s=2.0)})
        prompts = _PROMPTS * 3
        rids = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        done = fleet.run()
        assert len(done) == len(rids)
        for rid, ref in zip(rids, _refs(8) * 3):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        st = fleet.stats()
        assert st["handoffs"] >= 1
        assert set(st["autoscale"]["per_role"]) == {"prefill", "decode"}
        for ev in fleet.scale_events:
            assert ev["role"] in ("prefill", "decode")
        with pytest.raises(TypeError, match="not both"):
            ElasticFleet(_factory(), policy=AutoscalePolicy(),
                         role_policies={"any": AutoscalePolicy()})
