"""Engine durability + replica-fleet failover (ISSUE 9 tentpole).

Acceptance: drain -> snapshot -> restore and injected crash -> migrate both
yield greedy outputs bit-equal to the uninterrupted engine, in full-KV and
compact modes, with the prefix cache on/off and mid-speculation /
mid-preemption / mid-chunked-prefill states covered; `serve.snapshot`-torn
snapshots are rejected via manifest and failover falls back to the previous
intact one; the fleet loses zero requests.  The conftest leak guard
additionally re-checks every engine's page-refcount accounting (restored
engines included) after each test."""
import os

import numpy as np
import pytest
import jax

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.models.llama import (llama_config_tiny,
                                     build_functional_llama, llama_generate)
from paddle_tpu.inference.paged import (AdmissionRejected,
                                        EngineStalledError, Request,
                                        ServingEngine)
from paddle_tpu.resilience import InjectedFault, inject
from paddle_tpu.serving import (EngineSnapshotManager, FleetFailedError,
                                ReplicaFleet)

rng = np.random.default_rng(33)

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
                attention_impl="ref", prompt_bucket=8, decode_horizon=2)
    base.update(kw)
    return ServingEngine(_params(), CFG, **base)


# one prompt bucket (all lengths <= prompt_bucket=8): every engine then
# compiles ONE dense-prefill executable — the suite is compile-dominated
# on CPU and tier-1 budget is tight
_PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32)
            for t in (5, 7, 3, 6)]
_REF_CACHE: dict = {}


def _refs(n_new=8):
    key = n_new
    if key not in _REF_CACHE:
        _REF_CACHE[key] = [
            np.asarray(llama_generate(_params(), CFG, p[None],
                                      max_new_tokens=n_new))[0]
            for p in _PROMPTS]
    return _REF_CACHE[key]


# the feature intersections the acceptance criteria name; each is a set of
# extra ServingEngine kwargs (mid-preemption is a fault drill, not a kwarg)
FEATURES = {
    "default": {},
    "cache_off": dict(prefix_cache=False),
    "chunked": dict(prefill_chunk=4),
    "spec": dict(speculative=4),
}


# ---------------------------------------------------------------------------
# ServingEngine.snapshot()/restore()
# ---------------------------------------------------------------------------
class TestEngineSnapshotRestore:
    def _roundtrip(self, mode, feature_kw, pressure=False, n_new=8,
                   steps=3):
        """Run partway, snapshot mid-flight, restore into a fresh engine,
        finish — outputs must equal the uninterrupted reference."""
        refs = _refs(n_new)
        eng = _mk(**feature_kw)
        rids = [eng.submit(p, max_new_tokens=n_new) for p in _PROMPTS]
        if pressure:
            # mid-preemption: a pool-pressure window forces a victim into
            # the requeued-with-emitted-tokens state before the snapshot
            with inject({"serve.pool_pressure": dict(action="trigger",
                                                     after=1, count=3)}):
                for _ in range(6):
                    eng.step()
            assert eng.preemptions >= 1
        else:
            for _ in range(steps):
                eng.step()
        state = eng.snapshot(mode=mode)
        eng2 = _mk(**feature_kw)
        applied = eng2.restore(state)
        assert applied == ("full_kv" if mode == "full_kv" else "reprefill")
        done = eng2.run()
        assert len(done) == len(rids)
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        eng.check_invariants()
        eng2.check_invariants()
        return eng, eng2

    @pytest.mark.parametrize("mode", ["full_kv", "compact"])
    def test_roundtrip_bit_exact(self, mode):
        self._roundtrip(mode, FEATURES["default"])

    @pytest.mark.parametrize("feature", ["cache_off", "chunked", "spec"])
    def test_roundtrip_full_kv_feature_intersections(self, feature):
        self._roundtrip("full_kv", FEATURES[feature])

    @pytest.mark.slow
    @pytest.mark.parametrize("feature", ["cache_off", "spec"])
    def test_roundtrip_compact_feature_intersections(self, feature):
        # tier-1 covers compact at the default intersection; the full
        # matrix below sweeps the rest (slow lane — budget)
        self._roundtrip("compact", FEATURES[feature])

    @pytest.mark.slow
    @pytest.mark.parametrize("mode", ["full_kv", "compact"])
    @pytest.mark.parametrize("feature", sorted(FEATURES))
    def test_roundtrip_full_matrix(self, mode, feature):
        for steps in (1, 2, 4):      # snapshot at varied mid-flight points
            self._roundtrip(mode, FEATURES[feature], steps=steps)

    def test_roundtrip_mid_preemption(self):
        eng, _ = self._roundtrip("full_kv", FEATURES["default"],
                                 pressure=True)
        assert eng.preemptions >= 1

    @pytest.mark.slow
    def test_roundtrip_mid_preemption_compact(self):
        eng, _ = self._roundtrip("compact", FEATURES["default"],
                                 pressure=True)
        assert eng.preemptions >= 1

    def test_full_kv_restore_runs_zero_reprefill(self):
        """Fast restore must CONTINUE decode: no prefill dispatch happens
        after restore when every request was already past prefill."""
        eng = _mk(num_slots=4)
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        for _ in range(2):
            eng.step()
        assert all(sl is None or sl.prefill_pos is None
                   for sl in eng._slots)
        state = eng.snapshot(mode="full_kv")
        eng2 = _mk(num_slots=4)
        assert eng2.restore(state) == "full_kv"
        pre = eng2.prefill_tokens
        done = eng2.run()
        assert eng2.prefill_tokens == pre    # nothing re-prefilled
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_sampled_requests_resume_on_seeded_key_stream(self):
        """Full-KV restore carries the engine PRNG key: a sampled request
        continues on the SAME seeded stream the uninterrupted engine
        used."""
        def go(split):
            eng = _mk(seed=7)
            rids = [eng.submit(p, max_new_tokens=5, temperature=0.8,
                               top_p=0.9) for p in _PROMPTS[:1]]
            if split:
                for _ in range(2):
                    eng.step()
                eng2 = _mk(seed=123)   # different seed: the SNAPSHOT key
                eng2.restore(eng.snapshot(mode="full_kv"))  # must win
                eng = eng2
            done = eng.run()
            return [done[r].output_ids for r in rids]
        for a, b in zip(go(False), go(True)):
            np.testing.assert_array_equal(a, b)

    def test_restore_into_smaller_pool_falls_back_to_reprefill(self):
        """Satellite: a full-KV snapshot restored into a smaller pool must
        fall back to re-prefill (compact semantics), keep the degradation
        ladder order, and stay bit-exact."""
        from paddle_tpu.observability import Telemetry
        eng = _mk()
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        for _ in range(3):
            eng.step()
        state = eng.snapshot(mode="full_kv")
        tel = Telemetry()
        eng2 = _mk(num_pages=20, telemetry=tel)
        assert eng2.restore(state) == "reprefill"
        with inject({"serve.pool_pressure": dict(action="trigger", after=1,
                                                 count=2)}):
            done = eng2.run()
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        # ladder order preserved on the restored engine: the eviction rung
        # was walked before any preemption
        names = tel.flight.event_names()
        if "preempt" in names:
            assert "evict" in names
            assert names.index("evict") < names.index("preempt")
        eng2.check_invariants()

    def test_restore_requires_fresh_engine(self):
        eng = _mk()
        eng.submit(_PROMPTS[0], max_new_tokens=4)
        state = eng.snapshot(mode="compact")
        with pytest.raises(RuntimeError, match="freshly constructed"):
            eng.restore(state)

    def test_snapshot_version_checked(self):
        eng = _mk()
        state = eng.snapshot(mode="compact")
        import json
        meta = json.loads(state["meta"])
        meta["version"] = 99
        state["meta"] = json.dumps(meta)
        with pytest.raises(ValueError, match="version"):
            _mk().restore(state)

    def test_cancel_releases_everywhere(self):
        """cancel() drops a request from queue, slot, or the finished
        record without leaking pages — the router's zombie-pruning hook
        after a snapshot restore."""
        eng = _mk()
        rids = [eng.submit(p, max_new_tokens=6) for p in _PROMPTS[:3]]
        eng.step()                       # 2 slots busy, 1 queued
        assert eng.cancel(rids[2])       # queued
        assert all(r.rid != rids[2] for r in eng._queue)
        assert eng.cancel(rids[0])       # running: pages park in the cache
        eng.check_invariants()
        done = eng.run()
        assert set(done) == {rids[1]}
        np.testing.assert_array_equal(done[rids[1]].output_ids, _refs(6)[1])
        assert eng.cancel(rids[1])       # finished record forgotten
        assert not eng.cancel(rids[1])   # already gone
        assert not eng.cancel(10**6)     # unknown rid
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages

    def test_adopt_validation(self):
        eng = _mk()
        with pytest.raises(ValueError, match="complete"):
            eng.adopt(_PROMPTS[0], generated=[1, 2, 3, 4], max_new_tokens=4)
        with pytest.raises(ValueError, match="complete"):
            eng.adopt(_PROMPTS[0], generated=[1, 9, 2], max_new_tokens=8,
                      eos_token_id=9)


# ---------------------------------------------------------------------------
# PagePool / prefix-cache serialization edges (satellite)
# ---------------------------------------------------------------------------
class TestSerializationEdges:
    def test_cow_shared_pages_refcount_roundtrip(self):
        """Two in-flight requests sharing cached prefix pages (refcount >
        1) must round-trip with refcounts EXACTLY equal — shared stays
        shared (no page duplication, no leak)."""
        shared = rng.integers(1, 64, (8,)).astype(np.int32)
        p1 = np.concatenate([shared, rng.integers(1, 64, (3,))
                             .astype(np.int32)])
        p2 = np.concatenate([shared, rng.integers(1, 64, (5,))
                             .astype(np.int32)])
        eng = _mk()
        r0 = eng.submit(p1, max_new_tokens=8)
        done0 = eng.run()                      # park p1's blocks in cache
        r1 = eng.submit(p1, max_new_tokens=8)  # re-attaches its own blocks
        r2 = eng.submit(p2, max_new_tokens=8)
        for _ in range(2):
            eng.step()
        assert eng.cache_hits >= 1
        assert any(c > 1 for c in eng.pool._refs.values()), \
            "setup failed to produce a shared page"
        state = eng.snapshot(mode="full_kv")
        eng2 = _mk()
        assert eng2.restore(state) == "full_kv"
        assert eng2.pool._refs == eng.pool._refs
        assert eng2.pool._free == eng.pool._free
        done = eng2.run()
        ref1 = np.asarray(llama_generate(_params(), CFG, p1[None],
                                         max_new_tokens=8))[0]
        ref2 = np.asarray(llama_generate(_params(), CFG, p2[None],
                                         max_new_tokens=8))[0]
        np.testing.assert_array_equal(done0[r0].output_ids, ref1)
        np.testing.assert_array_equal(done[r1].output_ids, ref1)
        np.testing.assert_array_equal(done[r2].output_ids, ref2)
        eng2.check_invariants()

    def test_cache_only_blocks_survive_and_still_hit(self):
        """Cache-referenced-but-unattached pages (a retired request's
        parked blocks, no live slot) must survive the round trip and be
        HIT by a later same-prefix admission on the restored engine."""
        p = rng.integers(1, 64, (11,)).astype(np.int32)
        eng = _mk()
        eng.submit(p, max_new_tokens=6)
        eng.run()
        assert len(eng.cache) > 0
        assert eng.num_active == 0
        state = eng.snapshot(mode="full_kv")
        eng2 = _mk()
        eng2.restore(state)
        assert len(eng2.cache) == len(eng.cache)
        assert eng2.pool._refs == eng.pool._refs
        rid = eng2.submit(p, max_new_tokens=6)
        done = eng2.run()
        assert done[rid].cached_prefix_tokens > 0   # the parked blocks hit
        ref = np.asarray(llama_generate(_params(), CFG, p[None],
                                        max_new_tokens=6))[0]
        np.testing.assert_array_equal(done[rid].output_ids, ref)
        eng2.check_invariants()

    def test_compact_restore_starts_cache_cold(self):
        p = rng.integers(1, 64, (9,)).astype(np.int32)
        eng = _mk()
        eng.submit(p, max_new_tokens=6)
        eng.run()
        state = eng.snapshot(mode="compact")
        eng2 = _mk()
        assert eng2.restore(state) == "reprefill"
        # token prefixes only: no pages, no cache content rode along
        assert len(eng2.cache) == 0
        assert eng2.pool.num_free == eng2.pool.num_pages
        eng2.check_invariants()


# ---------------------------------------------------------------------------
# EngineSnapshotManager: durable snapshots through the commit protocol
# ---------------------------------------------------------------------------
class TestEngineSnapshotManager:
    def _partway(self, **kw):
        eng = _mk(**kw)
        rids = [eng.submit(p, max_new_tokens=8) for p in _PROMPTS]
        for _ in range(3):
            eng.step()
        return eng, rids

    def test_disk_roundtrip_both_modes(self, tmp_path):
        eng, rids = self._partway()
        for mode in ("full_kv", "compact"):
            mgr = EngineSnapshotManager(str(tmp_path / mode))
            path = mgr.save_engine(eng, mode=mode)
            assert mgr.find_latest_complete() == path
            eng2 = _mk()
            got = mgr.restore_engine(eng2)
            assert got is not None and got[0] == path
            assert got[1] == ("full_kv" if mode == "full_kv"
                              else "reprefill")
            done = eng2.run()
            for rid, ref in zip(rids, _refs(8)):
                np.testing.assert_array_equal(done[rid].output_ids, ref)

    def test_rotation_keeps_last_n(self, tmp_path):
        eng, _ = self._partway()
        mgr = EngineSnapshotManager(str(tmp_path), keep_last=2)
        for _ in range(4):
            mgr.save_engine(eng, mode="compact")
        kept = sorted(d for d in os.listdir(tmp_path)
                      if d.startswith("step_"))
        assert kept == ["step_00000002", "step_00000003"]

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_crash_mid_write_never_commits(self, tmp_path, monkeypatch):
        """The writer's own ckpt.write faults fire on the engine-snapshot
        path too: a snapshot killed mid-write leaves only torn staging —
        discovery lands on the previous intact snapshot."""
        import sys
        mod = sys.modules["paddle_tpu.distributed.checkpoint."
                          "save_state_dict"]
        monkeypatch.setattr(mod, "WRITE_CHUNK", 64)
        eng, rids = self._partway()
        mgr = EngineSnapshotManager(str(tmp_path))
        first = mgr.save_engine(eng, mode="full_kv")
        eng.step()
        with inject({"ckpt.write": dict(match={"file": "rank0.data"},
                                        at=2)}):
            with pytest.raises(InjectedFault):
                mgr.save_engine(eng, mode="full_kv")
        assert mgr.find_latest_complete() == first
        eng2 = _mk()
        assert mgr.restore_engine(eng2)[0] == first
        done = eng2.run()
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)

    def test_dirsync_crash_never_commits_previous_stays_latest(
            self, tmp_path):
        """Pre-rename parent-entry durability (ISSUE 17 satellite): the
        ``ckpt.dirsync`` fault point sits between the staging-tree fsync
        and the atomic rename — the window where the snapshot CONTENTS
        are durable but the parent directory entry that will NAME the
        committed snapshot is not.  A crash there must leave the commit
        unhappened: discovery falls back to the previous intact snapshot
        and restore replays it bit-exactly."""
        eng, rids = self._partway()
        mgr = EngineSnapshotManager(str(tmp_path))
        first = mgr.save_engine(eng, mode="full_kv")
        eng.step()
        with inject({"ckpt.dirsync": dict(at=0)}) as plan:
            with pytest.raises(InjectedFault):
                mgr.save_engine(eng, mode="full_kv")
        assert plan.fired("ckpt.dirsync") == 1
        assert mgr.find_latest_complete() == first
        eng2 = _mk()
        assert mgr.restore_engine(eng2)[0] == first
        done = eng2.run()
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)

    def test_serve_snapshot_torn_rejected_via_manifest(self, tmp_path):
        """serve.snapshot action="trigger" tears the COMMITTED snapshot:
        verification must reject it and discovery must fall back to the
        previous intact one."""
        from paddle_tpu.distributed.checkpoint import (
            CheckpointCorruptError, verify_checkpoint)
        eng, rids = self._partway()
        mgr = EngineSnapshotManager(str(tmp_path))
        first = mgr.save_engine(eng, mode="full_kv")
        eng.step()
        with inject({"serve.snapshot": dict(action="trigger", at=0)}):
            torn = mgr.save_engine(eng, mode="full_kv")
        with pytest.raises(CheckpointCorruptError):
            verify_checkpoint(torn)
        assert mgr.find_latest_complete() == first
        eng2 = _mk()
        assert mgr.restore_engine(eng2)[0] == first
        done = eng2.run()
        for rid, ref in zip(rids, _refs(8)):
            np.testing.assert_array_equal(done[rid].output_ids, ref)


# ---------------------------------------------------------------------------
# ReplicaFleet: routing, failover, migration
# ---------------------------------------------------------------------------
def _factory(**kw):
    def make():
        return _mk(**kw)
    return make


def _check_fleet(fleet, rids, refs):
    done = fleet.run()
    assert len(done) == len(rids), "lost requests"
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].output_ids, ref)
    return done


class TestReplicaFleet:
    def test_routing_completes_bit_exact(self):
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
        _check_fleet(fleet, rids, _refs(8))
        st = fleet.stats()
        assert st["failovers"] == 0
        assert st["requests_resolved"] == len(rids)

    @pytest.mark.parametrize("phase", [
        "record",
        pytest.param("sched", marks=pytest.mark.slow),  # tier-1 budget
    ])
    def test_crash_migrates_bit_exact(self, phase):
        """The tier-1 deterministic failover drill: kill replica r0
        mid-step (post-admission or post-record), requests migrate to r1
        by re-prefill of prompt + streamed tokens, zero lost, outputs
        bit-equal the uninterrupted engine.  Replicas run telemetry-ON so
        the drill also pins the ISSUE 12 fleet observability plane: the
        merged failover dump (router routing decisions + the dying
        replica's flight ring in ONE artifact), the bucket-wise
        FleetTelemetry aggregation, and the stitched cross-component
        trace (the crashed request reads as one timeline)."""
        fleet = ReplicaFleet(_factory(telemetry=True), num_replicas=2)
        with inject({"serve.crash": dict(match={"engine": "r0",
                                                "phase": phase},
                                         at=2)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        assert plan.fired("serve.crash") == 1
        st = fleet.stats()
        assert st["failovers"] == 1
        assert st["migrations"] >= 1
        ev = fleet.flight.events()
        fo = [e for e in ev if e["event"] == "failover"]
        assert fo and fo[0]["kind"] == "crash" \
            and fo[0]["fault_plan"] is not None
        assert any(e["event"] == "migrate"
                   and e["fault_plan"] is not None for e in ev)
        assert fleet.stats()["recovery"]["count"] == 1
        # --- ISSUE 12 satellite: the MERGED failover dump — routing
        # decisions + dying replica's ring, diagnosable from one artifact
        dump = fleet.flight.last_dump()
        assert dump is not None and dump["reason"] == "failover"
        extra = dump["extra"]
        routing = extra["routing_decisions"]
        assert routing and all(e["event"] in ("route", "migrate")
                               for e in routing)
        assert any(e["event"] == "route" and e["replica"] == "r0"
                   for e in routing)
        assert extra["replica_ring"], \
            "the dying replica's flight ring must ride the fleet dump"
        assert any(e["event"] == "step" for e in extra["replica_ring"])
        # --- ISSUE 12 tentpole: fleet aggregation (bucket-wise merge)
        snap = fleet.stats_snapshot(ttft_deadline_s=60.0)
        per_rep = snap["per_replica_telemetry"]
        merged = snap["merged"]
        live = [k for k in per_rep if k.startswith("r")
                and k != "router"]
        assert len(live) == 2
        assert merged["serve.ttft_s"]["count"] == sum(
            1 for _ in rids), "merged TTFT histogram must count every " \
            "first token exactly once across replicas"
        assert all("mem.pool_occupancy_frac" in per_rep[k] for k in live)
        assert snap["fleet_slo"]["goodput_fraction"] == 1.0
        # --- ISSUE 12 tentpole: trace stitching — the crashed request is
        # ONE timeline across router -> dead r0 track -> surviving track
        summ = fleet.stitcher().summary()
        assert "router" in summ["components"] \
            and any("crashed" in c for c in summ["components"])
        assert summ["flow_events"] > 0
        assert len(summ["max_chain"]) >= 3, summ
        assert summ["max_chain"][0] == "router"
        assert any("crashed" in c for c in summ["max_chain"])
        # every fleet request carries a trace_id end to end
        assert all(fr.trace_id is not None
                   for fr in fleet._requests.values())
        # --- ISSUE 13: stitched critical-path attribution — EVERY end-to-
        # end request (the crashed/migrated ones included) decomposes into
        # exact disjoint segments summing to its traced e2e, and the
        # failover gap itself is attributed (migration / snapshot_restore)
        attr = fleet.attribution_report()
        assert attr["requests"] == len(rids)
        assert attr["exact_requests"] == attr["requests"], attr
        assert "migration" in attr["segments"] \
            or "snapshot_restore" in attr["segments"], attr["segments"]
        # fleet tail forensics: slowest requests captured across replicas
        slow = fleet.slow_requests()
        assert slow and slow[0]["attribution"]["exact"] is True
        # the alerts aggregation rides the stats snapshot (sentinel-less
        # replicas -> empty components, status ok)
        assert snap["alerts"]["status"] == "ok"

    def test_rejected_submit_leaves_no_tracer_ghost(self):
        """A submit that raises at placement (can-never-fit prompt) or at
        the fleet-queue reject rung must terminate its router trace
        record — Tracer._live is unbounded and a ghost would pollute
        every stitched trace."""
        fleet = ReplicaFleet(_factory(max_queue=2), num_replicas=1,
                             max_queue=0)
        with pytest.raises(ValueError):           # can never fit
            fleet.submit(np.ones(400, np.int32), max_new_tokens=8)
        assert fleet.tracer._live == {}
        # fill the replica's bounded admission queue, then overflow the
        # (zero-length) fleet queue: the reject rung must also terminate
        # the trace record
        rids = [fleet.submit(_PROMPTS[i], max_new_tokens=8)
                for i in range(2)]
        with pytest.raises(AdmissionRejected):
            fleet.submit(_PROMPTS[2], max_new_tokens=8)
        assert set(fleet.tracer._live) <= set(fleet._requests)
        _check_fleet(fleet, rids, _refs(8)[:2])

    def test_request_state_roundtrips_trace_id(self):
        """Snapshot serialization carries trace_id (and tolerates
        pre-ISSUE-12 snapshots without one)."""
        req = Request(rid=3, prompt=np.arange(4, dtype=np.int32),
                      trace_id=123)
        eng_like = object.__new__(ServingEngine)   # _req_state reads only r
        d = ServingEngine._req_state(eng_like, req)
        assert d["trace_id"] == 123
        assert ServingEngine._req_from_state(d).trace_id == 123
        d.pop("trace_id")
        assert ServingEngine._req_from_state(d).trace_id is None

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_crash_mid_speculation_migrates_bit_exact(self):
        fleet = ReplicaFleet(_factory(speculative=4), num_replicas=2)
        with inject({"serve.crash": dict(match={"engine": "r0"},
                                         at=6)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        assert plan.fired("serve.crash") == 1
        assert fleet.stats()["failovers"] == 1

    @pytest.mark.slow
    def test_crash_cache_off_migrates_bit_exact(self):
        # cache-off is covered tier-1 on the snapshot path; the crash
        # drill re-runs it in the slow lane (budget)
        fleet = ReplicaFleet(_factory(prefix_cache=False), num_replicas=2)
        with inject({"serve.crash": dict(match={"engine": "r0"}, at=3)}):
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        assert fleet.stats()["failovers"] == 1

    def test_snapshot_restore_failover(self, tmp_path):
        fleet = ReplicaFleet(_factory(), num_replicas=2,
                             snapshot_root=str(tmp_path), snapshot_every=2)
        with inject({"serve.crash": dict(match={"engine": "r0"}, at=8)}):
            rids = [fleet.submit(p, max_new_tokens=12) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(12))
        ev = [e["event"] for e in fleet.flight.events()]
        assert "restore" in ev     # revived from the snapshot, not blank
        assert fleet.stats()["failovers"] == 1

    def test_torn_snapshot_rejected_falls_back_to_intact(self, tmp_path):
        """serve.snapshot tears r0's NEWEST snapshot; on the later crash,
        discovery must reject it (manifest), flight-record the rejection
        with fault-plan context, and restore from the previous intact
        one — outputs still bit-equal."""
        fleet = ReplicaFleet(_factory(), num_replicas=2,
                             snapshot_root=str(tmp_path), snapshot_every=2,
                             snapshot_keep_last=3)
        with inject({"serve.snapshot": dict(action="trigger",
                                            match={"engine": "r0"}, at=2),
                     "serve.crash": dict(match={"engine": "r0"},
                                         at=12)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=16) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(16))
        assert plan.fired("serve.snapshot") == 1
        assert plan.fired("serve.crash") == 1
        st = fleet.stats()
        assert st["torn_snapshots"] >= 1
        torn = [e for e in fleet.flight.events()
                if e["event"] == "torn_snapshot"]
        rest = [e for e in fleet.flight.events() if e["event"] == "restore"]
        assert torn and torn[0]["fault_plan"] is not None
        assert rest and rest[0]["path"] < torn[0]["path"]  # older intact

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_crash_mid_snapshot_previous_stays_latest(self, tmp_path):
        """serve.snapshot action="raise": the replica dies mid-snapshot;
        the failover restores from the previous intact snapshot."""
        fleet = ReplicaFleet(_factory(), num_replicas=2,
                             snapshot_root=str(tmp_path), snapshot_every=2)
        with inject({"serve.snapshot": dict(match={"engine": "r1"},
                                            at=1)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=12) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(12))
        assert plan.fired("serve.snapshot") == 1
        fo = [e for e in fleet.flight.events() if e["event"] == "failover"]
        assert fo and fo[0]["replica"] == "r1"

    def test_sampled_request_migrates_from_streamed_not_snapshot(
            self, tmp_path):
        """temperature>0 requests must NEVER resume from a stale snapshot
        (re-sampling past the snapshot point diverges from tokens already
        streamed) — they migrate by adopt() from the streamed record, so
        the final result always EXTENDS what the router streamed."""
        fleet = ReplicaFleet(_factory(), num_replicas=2,
                             snapshot_root=str(tmp_path), snapshot_every=2)
        with inject({"serve.crash": dict(match={"engine": "r0"}, at=8)}):
            frids = [fleet.submit(p, max_new_tokens=12, temperature=0.9,
                                  top_p=0.9) for p in _PROMPTS]
            done = fleet.run()
        assert len(done) == len(frids)      # zero lost
        for frid in frids:
            fr = fleet._requests[frid]
            # the stream the client saw is exactly the final result — no
            # stitched-together divergent sample streams
            assert fr.streamed == [int(t) for t in done[frid].generated]
        assert fleet.stats()["failovers"] == 1

    def test_wedge_watchdog_fails_over(self):
        fleet = ReplicaFleet(_factory(), num_replicas=2, stall_threshold=4)
        with inject({"serve.wedge": dict(action="trigger",
                                         match={"engine": "r1"},
                                         count=None)}):
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        fo = [e for e in fleet.flight.events() if e["event"] == "failover"]
        assert fo and fo[0]["kind"] == "wedge"

    def test_wedge_unroutable_happens_before_adopt(self):
        """Regression for the wedge race (ISSUE 17 satellite): a
        wedged-but-ALIVE replica can un-wedge after the watchdog condemns
        it — anything still stepping the corpse would keep decoding
        requests the fleet is about to migrate (double emission through
        engine-level hooks, pages pinned forever).  The fix quiesces the
        corpse — cancels its outstanding requests ON the condemned engine
        — strictly before any adopt.  The flight recorder proves the
        ordering, and the corpse ends the failover carrying nothing."""
        fleet = ReplicaFleet(_factory(), num_replicas=2, stall_threshold=4)
        corpse = next(r.engine for r in fleet._replicas if r.name == "r1")
        with inject({"serve.wedge": dict(action="trigger",
                                         match={"engine": "r1"},
                                         count=None)}):
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        ev = fleet.flight.events()
        q = [i for i, e in enumerate(ev) if e["event"] == "wedge_quiesce"]
        f = [i for i, e in enumerate(ev) if e["event"] == "failover"]
        m = [i for i, e in enumerate(ev) if e["event"] == "migrate"]
        assert q and ev[q[0]]["replica"] == "r1"
        assert ev[q[0]]["cancelled"] >= 1
        # quiesce happens-before the failover record and before EVERY
        # migration — no adopt can race the condemned replica
        assert f and q[0] < f[0]
        assert m and q[0] < min(m)
        # the corpse carries nothing: every cancelled request's pages
        # parked in its cache and drain to fully-free
        corpse.release_cache()
        assert corpse.pool.num_free == corpse.pool.num_pages

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_transient_wedge_tolerated(self):
        """A stall shorter than the watchdog threshold self-recovers: no
        failover, no migration, outputs untouched."""
        fleet = ReplicaFleet(_factory(), num_replicas=2, stall_threshold=8)
        with inject({"serve.wedge": dict(action="trigger",
                                         match={"engine": "r0"}, after=0,
                                         count=3)}):
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        assert fleet.stats()["failovers"] == 0

    def test_fleet_ladder_route_queue_reject(self):
        """Fleet-wide degradation ladder: replicas saturate (route),
        overflow waits in the bounded fleet queue (queue), queue overflow
        is typed backpressure (reject) — and every ACCEPTED request still
        completes bit-exactly."""
        fleet = ReplicaFleet(_factory(max_queue=1, num_slots=1),
                             num_replicas=2, max_queue=2)
        refs = _refs(8)
        rids = []
        rejected = 0
        for i, p in enumerate(_PROMPTS * 3):
            try:
                rids.append((i, fleet.submit(p, max_new_tokens=8)))
            except AdmissionRejected:
                rejected += 1
        assert rejected >= 1
        assert fleet.stats()["rejections"] == rejected
        assert any(e["event"] == "queue" for e in fleet.flight.events())
        done = fleet.run()
        assert len(done) == len(rids)
        for i, rid in rids:
            np.testing.assert_array_equal(done[rid].output_ids,
                                          refs[i % len(_PROMPTS)])

    @pytest.mark.slow   # tier-1 budget: covered by the tier-1 siblings
    def test_single_replica_crash_respawns_blank(self):
        """num_replicas=1, no snapshots: the failed replica respawns blank
        and every request migrates onto it by re-prefill."""
        fleet = ReplicaFleet(_factory(), num_replicas=1)
        with inject({"serve.crash": dict(at=4)}):
            rids = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS]
            _check_fleet(fleet, rids, _refs(8))
        st = fleet.stats()
        assert st["failovers"] == 1 and st["migrations"] >= 1

    def test_failover_budget_exhausted_raises(self):
        fleet = ReplicaFleet(_factory(), num_replicas=1,
                             max_failovers_per_replica=1)
        with inject({"serve.crash": dict(count=None)}):
            fleet.submit(_PROMPTS[0], max_new_tokens=8)
            with pytest.raises(FleetFailedError):
                fleet.run()

    @pytest.mark.slow
    def test_fleet_chaos_sweep(self, tmp_path):
        """Randomized crash/wedge/torn-snapshot schedules: zero lost
        requests and bit-exact greedy outputs for every seed."""
        refs = _refs(10)
        for seed in range(4):
            fleet = ReplicaFleet(_factory(), num_replicas=2,
                                 snapshot_root=str(tmp_path / f"s{seed}"),
                                 snapshot_every=3, stall_threshold=4)
            plan = {
                "serve.crash": dict(prob=0.02, count=2),
                "serve.wedge": dict(action="trigger", prob=0.05, count=6),
                "serve.snapshot": dict(action="trigger", prob=0.3,
                                       count=2),
            }
            with inject(plan, seed=seed):
                rids = [fleet.submit(p, max_new_tokens=10)
                        for p in _PROMPTS]
                done = fleet.run()
            assert len(done) == len(rids), f"seed {seed} lost requests"
            for rid, ref in zip(rids, refs):
                np.testing.assert_array_equal(done[rid].output_ids, ref,
                                              err_msg=f"seed {seed}")


# ---------------------------------------------------------------------------
# Fleet-level streaming (ISSUE 11 satellite): on_token through
# ReplicaFleet.submit, router log authoritative across failover
# ---------------------------------------------------------------------------
class TestFleetStreaming:
    def test_on_token_matches_final_record(self):
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        got: dict[int, list] = {}
        rids = [fleet.submit(p, max_new_tokens=8,
                             on_token=got.setdefault(i, []).append)
                for i, p in enumerate(_PROMPTS)]
        done = _check_fleet(fleet, rids, _refs(8))
        for i, rid in enumerate(rids):
            assert got[i] == list(done[rid].generated)

    def test_stream_survives_failover_without_double_emission(self):
        """Kill r0 mid-trace: the revived/migrated engines RE-decode
        tokens the router already streamed (greedy-identical), but the
        fleet hook — fired only as the authoritative router log extends —
        must emit every position exactly once, in order."""
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        got: dict[int, list] = {}
        with inject({"serve.crash": dict(match={"engine": "r0"},
                                         at=2)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=8,
                                 on_token=got.setdefault(i, []).append)
                    for i, p in enumerate(_PROMPTS)]
            done = _check_fleet(fleet, rids, _refs(8))
        assert plan.fired("serve.crash") == 1
        assert fleet.stats()["failovers"] == 1
        assert fleet.stats()["migrations"] >= 1
        for i, (rid, ref) in enumerate(zip(rids, _refs(8))):
            # exactly the final record — no duplicates, no gaps, in order
            assert got[i] == list(done[rid].generated)
            assert got[i] == list(ref[len(_PROMPTS[i]):])

    def test_stream_disconnect_during_failover_migration(self):
        """ISSUE 17 satellite: a consumer iterating ``Request.stream()``
        on a replica handle disconnects DURING a failover migration —
        after the crash condemned its home replica and the request was
        adopted elsewhere.  The early-exit close must be clean (the
        victim's pages free on the corpse, the stream is not
        resurrected), and the client-gone cancel propagated through the
        fleet must land on the ADOPTED replica: its engine observes the
        cancel, no orphaned request keeps decoding to nobody."""
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        emitted: list = []
        victim = fleet.submit(_PROMPTS[0], max_new_tokens=24,
                              on_token=emitted.append)
        others = [fleet.submit(p, max_new_tokens=8) for p in _PROMPTS[1:]]
        fr = fleet._requests[victim]
        for _ in range(60):
            fleet.step()
            if fr.handle is not None and len(fr.streamed) >= 2:
                break
        assert fr.handle is not None and len(fr.streamed) >= 2
        home = fr.replica
        corpse = next(r.engine for r in fleet._replicas if r.name == home)
        old_handle = fr.handle
        old_rid = old_handle.rid
        gen = fr.handle.stream()            # the consumer's token stream
        assert next(gen) == fr.streamed[0]  # buffered: no engine stepping
        # crash the victim's home replica: failover + adopt-migration
        with inject({"serve.crash": dict(match={"engine": home},
                                         at=0)}) as plan:
            for _ in range(30):
                fleet.step()
                if fleet.stats()["failovers"] == 1 \
                        and fr.handle is not None:
                    break
        assert plan.fired("serve.crash") == 1
        # migrated: a NEW engine-side request on a NEW engine (rids are
        # per-engine counters, so only object identity discriminates)
        assert fr.handle is not None and fr.handle is not old_handle, \
            "victim was not migrated"
        adopted_eng = next(r.engine for r in fleet._replicas
                           if r.name == fr.replica)
        assert adopted_eng is not corpse
        # the consumer disconnects mid-migration, mid-decode
        n_at_disconnect = len(emitted)
        free_before = corpse.pool.num_free
        gen.close()                 # early-exit cancel lands on the corpse
        assert corpse.lookup(old_rid) is None
        corpse.release_cache()
        assert corpse.pool.num_free > free_before, \
            "disconnect did not free the victim's pages on the corpse"
        corpse.check_invariants()
        # the disconnect propagates fleet-level onto the ADOPTED replica
        adopted_rid = fr.handle.rid
        assert fleet.cancel(victim) is True
        assert adopted_eng.lookup(adopted_rid) is None, \
            "adopted replica never observed the cancel"
        # survivors complete bit-exact; the orphan never streamed again
        done = fleet.run()
        assert victim not in done
        assert len(emitted) == n_at_disconnect, \
            "orphaned stream kept emitting after the disconnect"
        for f, ref in zip(others, _refs(8)[1:]):
            np.testing.assert_array_equal(done[f].output_ids, ref)
        for rep in fleet._replicas:
            rep.engine.release_cache()
            assert rep.engine.pool.num_free == rep.engine.pool.num_pages

    @pytest.mark.slow   # tier-1 budget: the crash-migration variant above
    # pins the no-double-emission contract; this re-runs it on the
    # snapshot-restore re-decode path
    def test_stream_survives_snapshot_restore_failover(self, tmp_path):
        """Same contract when the revived replica restores from a
        snapshot and re-decodes from an OLDER state than the router had
        streamed: the re-decoded overlap is suppressed by the log."""
        fleet = ReplicaFleet(_factory(), num_replicas=2,
                             snapshot_root=str(tmp_path),
                             snapshot_every=2)
        got: dict[int, list] = {}
        with inject({"serve.crash": dict(match={"engine": "r0"},
                                         at=5)}) as plan:
            rids = [fleet.submit(p, max_new_tokens=10,
                                 on_token=got.setdefault(i, []).append)
                    for i, p in enumerate(_PROMPTS)]
            done = _check_fleet(fleet, rids, _refs(10))
        assert plan.fired("serve.crash") == 1
        for i, rid in enumerate(rids):
            assert got[i] == list(done[rid].generated)

    def test_fleet_cancel(self):
        """cancel(frid) drops the request wherever it lives — replica
        slot, fleet queue — freeing engine pages (conftest leak guard
        re-checks every replica engine)."""
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        keep = fleet.submit(_PROMPTS[0], max_new_tokens=8)
        drop = fleet.submit(_PROMPTS[1], max_new_tokens=48)
        for _ in range(2):
            fleet.step()
        assert fleet.cancel(drop) is True
        assert fleet.cancel(drop) is False          # already gone
        assert fleet.cancel(99_999) is False        # unknown frid
        done = fleet.run()
        assert drop not in done and keep in done
        np.testing.assert_array_equal(done[keep].output_ids, _refs(8)[0])
        for rep in fleet._replicas:
            rep.engine.release_cache()
            assert rep.engine.pool.num_free == rep.engine.pool.num_pages

