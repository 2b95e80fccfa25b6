"""Elastic fleet autoscaling + zero-loss drain (ISSUE 14 tentpole).

Acceptance bar: the sentinel-driven loop scales up on sustained queue
growth and down on sustained idle — deterministically under the
injectable (round-virtual) clock; a drain retirement live-migrates every
in-flight request (mark-unroutable -> cancel/adopt re-prefill ->
destroy) with ZERO loss and greedy outputs bit-equal the uninterrupted
engine, including mid-speculation; a drain target crashing mid-migration
falls through to the failover path with the same guarantees; the
conftest leak guard covers retired-then-destroyed replicas (destroy
re-checks page accounting before dropping the engine)."""
import numpy as np
import pytest
import jax

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.models.llama import (build_functional_llama,
                                     llama_config_tiny, llama_generate)
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.serving import (AutoscaleDecision, AutoscalePolicy,
                                ElasticFleet, PrefixAffinityRouter,
                                ReplicaFleet, VirtualClock, make_scenario,
                                replay_fleet)

rng = np.random.default_rng(55)

CFG = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        ep, bp, hp, *_ = build_functional_llama(CFG,
                                                key=jax.random.PRNGKey(6))
        _PARAMS = (ep, bp, hp)
    return _PARAMS


def _factory(**ekw):
    def mk():
        base = dict(num_slots=2, page_size=4, num_pages=40,
                    max_pages_per_seq=16, attention_impl="ref",
                    prompt_bucket=8, decode_horizon=2)
        base.update(ekw)
        return ServingEngine(_params(), CFG, **base)
    return mk


_PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32)
            for t in (5, 7, 3, 6, 4, 7)]
_REFS = {}


def _refs(n_new=6):
    if n_new not in _REFS:
        _REFS[n_new] = [np.asarray(
            llama_generate(_params(), CFG, p[None], max_new_tokens=n_new))[0]
            for p in _PROMPTS]
    return _REFS[n_new]


def _assert_exact(fleet, frids, n_new=6):
    """Every frid resolved, each bit-equal its prompt's uninterrupted
    reference (frids submitted in _PROMPTS order, cycling)."""
    done = fleet.results()
    refs = _refs(n_new)
    missing = [f for f in frids if f not in done]
    assert not missing, f"lost requests {missing}"
    for i, frid in enumerate(frids):
        np.testing.assert_array_equal(np.asarray(done[frid].output_ids),
                                      refs[i % len(_PROMPTS)])


def _policy(**kw):
    base = dict(min_replicas=1, max_replicas=3, queue_growth=2.0,
                queue_min_depth=3.0, growth_window_s=2.0,
                growth_fire_frac=0.34, idle_per_replica=1.0,
                idle_window_s=2.5, min_samples=3, scale_cooldown_s=1.5,
                dt_per_round=0.5)
    base.update(kw)
    return AutoscalePolicy(**base)


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------
class _StubSentinel:
    def __init__(self, *names):
        self._names = names

    def active(self):
        class A:          # duck Alert
            def __init__(self, rule):
                self.rule = rule
        return [A(n) for n in self._names]


class _StubFleet:
    def __init__(self, routable):
        self._routable = routable

    def routable_replicas(self, role=None):
        return self._routable


class TestPolicy:
    def test_validation(self):
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=0)
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(TypeError):
            ElasticFleet(_factory(), num_replicas=2)

    def test_decide_grow_shrink_hold(self):
        pol = _policy()
        dec = pol.decide(_StubSentinel("queue_growth"), _StubFleet(1),
                         now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.GROW
        dec = pol.decide(_StubSentinel("fleet_idle"), _StubFleet(2),
                         now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.SHRINK
        dec = pol.decide(_StubSentinel(), _StubFleet(2),
                         now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.HOLD

    def test_cooldown_holds(self):
        pol = _policy(scale_cooldown_s=5.0)
        dec = pol.decide(_StubSentinel("queue_growth"), _StubFleet(1),
                         now=4.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.HOLD

    def test_pressure_never_shrinks_at_max(self):
        """Regression: at max capacity with BOTH queue_growth and
        fleet_idle active, the loop must HOLD — shrinking would open an
        at-max grow/shrink oscillator that thrashes a replica per
        cooldown."""
        pol = _policy(max_replicas=3)
        dec = pol.decide(_StubSentinel("queue_growth", "fleet_idle"),
                         _StubFleet(3), now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.HOLD
        # below max the same evidence GROWS (pressure wins)
        dec = pol.decide(_StubSentinel("queue_growth", "fleet_idle"),
                         _StubFleet(2), now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.GROW

    def test_min_floor(self):
        pol = _policy(min_replicas=2)
        dec = pol.decide(_StubSentinel("fleet_idle"), _StubFleet(2),
                         now=10.0, last_action_t=0.0)
        assert dec is AutoscaleDecision.HOLD


# ---------------------------------------------------------------------------
# drain (manual retire_replica) — the zero-loss protocol
# ---------------------------------------------------------------------------
class TestDrain:
    def test_drain_migrates_inflight_bit_exact(self):
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        frids = [fleet.submit(p, max_new_tokens=6) for p in _PROMPTS]
        for _ in range(2):
            fleet.step()
        assert any(fleet._requests[f].replica == "r0" for f in frids)
        assert fleet.retire_replica("r0")
        st = fleet.stats()
        assert st["drain_migrations"] >= 1
        assert st["scale_downs"] == 1 and st["replicas_retired"] == 1
        assert [rep.name for rep in fleet._replicas] == ["r1"]
        fleet.run()
        _assert_exact(fleet, frids)

    def test_drain_refuses_last_replica_and_unknown(self):
        fleet = ReplicaFleet(_factory(), num_replicas=1)
        assert not fleet.retire_replica("r0")    # never drain the last
        assert not fleet.retire_replica("zz")

    def test_drained_replica_unroutable_during_window(self):
        """mark-unroutable is observable: a draining replica never
        receives new placements (router candidates exclude it)."""
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        rep0 = fleet._replicas[0]
        rep0.routable = False
        frids = [fleet.submit(p, max_new_tokens=4) for p in _PROMPTS[:4]]
        assert all(fleet._requests[f].replica != "r0" for f in frids
                   if fleet._requests[f].replica is not None)
        rep0.routable = True
        fleet.run()

    @pytest.mark.slow
    def test_drain_mid_speculation(self):
        """Scale-down racing a mid-speculation request: the drain
        cancels (rewind-exact), migrates, and the continuation stays
        greedy-bit-exact."""
        fleet = ReplicaFleet(_factory(speculative=4), num_replicas=2)
        frids = [fleet.submit(p, max_new_tokens=6) for p in _PROMPTS]
        for _ in range(2):
            fleet.step()
        assert fleet.retire_replica("r0")
        fleet.run()
        _assert_exact(fleet, frids)

    @pytest.mark.slow
    def test_drain_target_crash_mid_migration(self):
        """The drain target dying mid-migration hands the replica to the
        FAILOVER path: every request still resolves, still bit-exact."""
        fleet = ReplicaFleet(_factory(), num_replicas=2)
        frids = [fleet.submit(p, max_new_tokens=6) for p in _PROMPTS]
        for _ in range(2):
            fleet.step()
        rep0 = fleet._replicas[0]
        assert rep0.name == "r0"

        def boom(rid):
            raise RuntimeError("drain target crashed mid-migration")
        rep0.engine.cancel = boom
        # handled (not raised) but NOT a retirement: the failover path
        # revived the replica, so no phantom scale-down is reported
        assert fleet.retire_replica("r0") is False
        st = fleet.stats()
        assert st["failovers"] == 1 and st["scale_downs"] == 0
        fleet.run()
        _assert_exact(fleet, frids)

    @pytest.mark.slow
    def test_retired_replica_keeps_tracer_and_counters(self):
        """Telemetry lifecycle: a retired replica's tracer joins the
        stitched components, its registry stays aggregatable, and its
        cache counters stay in the fleet-wide hit accounting."""
        fleet = ReplicaFleet(_factory(telemetry=True),  # one per engine
                             num_replicas=2)
        frids = [fleet.submit(p, max_new_tokens=4) for p in _PROMPTS[:4]]
        fleet.run()
        pre_hit = fleet.fleet_hit_rate()
        assert fleet.retire_replica("r0")
        names = [n for n, _t in fleet.trace_components()]
        assert any("r0 (retired)" in n for n in names)
        post_hit = fleet.fleet_hit_rate()
        assert post_hit["cached_prefix_tokens"] \
            == pre_hit["cached_prefix_tokens"]
        assert "r0" in post_hit["per_replica"]
        snap = fleet.stats_snapshot()
        assert "r0 (retired)" in snap["replica_names"]
        _assert_exact(fleet, frids, n_new=4)


# ---------------------------------------------------------------------------
# the closed loop under the virtual clock (deterministic)
# ---------------------------------------------------------------------------
def _flood_scenario(seed=3, n=14):
    return make_scenario("flood", seed=seed, n_requests=n, vocab=64,
                         arrival="poisson", mean_interarrival_s=0.2,
                         prompt_len=(3, 8), max_new=(6, 10))


class TestElasticLoop:
    def test_scale_up_on_queue_growth(self):
        vc = VirtualClock(0.5)
        fleet = ElasticFleet(_factory(), policy=_policy(), clock=vc)
        sc = _flood_scenario()
        res = replay_fleet(fleet, sc, slo_ttft_s=5.0, virtual_clock=vc)
        assert fleet.stats()["scale_ups"] >= 1
        assert all(r["tokens"] > 0 for r in res["records"])
        ev = [e["event"] for e in fleet.flight.events()]
        assert "scale_up" in ev

    def test_scale_down_after_idle_drain(self):
        """Pressure then calm: the loop grows, then drains back to
        min_replicas — zero loss, bit-exact, retired engines destroyed
        (leak guard re-checks them at destroy)."""
        vc = VirtualClock(0.5)
        fleet = ElasticFleet(_factory(), policy=_policy(), clock=vc)
        # ramp two submits per round: the TrendRule watches GROWTH, so
        # the queue must build ACROSS rounds, faster than one replica
        # (2 slots) drains it
        frids = []
        for i, p in enumerate(_PROMPTS * 2):
            frids.append(fleet.submit(p, max_new_tokens=6))
            if i % 2:
                fleet.step()
        fleet.run()
        grew = fleet.stats()["scale_ups"]
        # calm traffic: a single trickle request per window keeps rounds
        # coming so the idle window fills and the drain fires
        trickle = []
        for _ in range(14):
            r = fleet.submit(_PROMPTS[0][:4], max_new_tokens=2)
            trickle.append(r)
            fleet.run()
            if len(fleet._alive()) == 1:
                break
        st = fleet.stats()
        assert grew >= 1, "flood never scaled up"
        assert st["scale_downs"] >= 1, "calm never scaled down"
        assert st["replicas_alive"] == 1
        assert st["requests_resolved"] == len(frids) + len(trickle)
        _assert_exact(fleet, frids)

    @pytest.mark.slow
    def test_scale_up_during_preemption_storm(self):
        """Scale-up racing a preemption storm: injected pool pressure
        forces the degradation ladder (evict -> preempt) on the loaded
        replica WHILE the queue-growth trigger is scaling the fleet —
        every output stays exact, nothing wedges."""
        from paddle_tpu.resilience import inject
        vc = VirtualClock(0.5)
        # page_size=2: decode crosses a page boundary every 2 tokens, so
        # the pressure window is guaranteed to catch a growth allocation
        # (the same geometry as the resilience ladder drills)
        fleet = ElasticFleet(_factory(page_size=2), policy=_policy(),
                             clock=vc)
        # after=6: the window opens once the ramp has built a real
        # queue, so blocked admissions preempt instead of just stalling
        with inject({"serve.pool_pressure": dict(action="trigger",
                                                 after=6, count=8)}):
            frids = []
            for i, p in enumerate(_PROMPTS * 2):
                frids.append(fleet.submit(p, max_new_tokens=6))
                if i % 2:
                    fleet.step()
            fleet.run()
        st = fleet.stats()
        assert st["scale_ups"] >= 1
        preempts = sum((s or {}).get("preemptions", 0)
                       for s in st["per_replica"].values())
        retired = sum(s.get("preemptions", 0)
                      for _n, s in fleet._retired_stats)
        assert preempts + retired >= 1, "storm never actually preempted"
        _assert_exact(fleet, frids)

    @pytest.mark.slow
    def test_deterministic_timeline_and_economics(self):
        """Same seed, same virtual clock -> IDENTICAL scale-event
        timeline, goodput report, and replica-seconds (the property the
        elastic bench gate rests on)."""
        sc = _flood_scenario(seed=9, n=12)

        def run():
            vc = VirtualClock(0.5)
            fleet = ElasticFleet(_factory(), policy=_policy(),
                                 router=PrefixAffinityRouter(), clock=vc)
            res = replay_fleet(fleet, sc, slo_ttft_s=5.0,
                               virtual_clock=vc, collect_tokens=True)
            return (fleet.scale_events,
                    res["replica_seconds"],
                    res["report"],
                    [r["stream"] for r in res["records"]])
        a, b = run(), run()
        assert a[0] == b[0]
        assert a[1] == b[1]
        assert a[2] == b[2]
        assert a[3] == b[3]

    def test_elastic_stats_block(self):
        vc = VirtualClock(0.5)
        fleet = ElasticFleet(_factory(), policy=_policy(), clock=vc)
        st = fleet.stats()["autoscale"]
        assert st["min_replicas"] == 1 and st["max_replicas"] == 3
        assert set(st["rule_fires"]) == {"queue_growth", "fleet_idle"}

