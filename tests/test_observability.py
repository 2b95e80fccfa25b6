"""Observability subsystem tests (ISSUE 6 tentpole): metrics registry +
log-bucketed histogram quantiles, EngineStats snapshot/delta + stats()
monotonicity across a serving trace, request-lifecycle tracing with a
nested Chrome-trace export, the crash flight recorder (stall / injected
fault / preemption-storm dumps), Request timing fields, the telemetry-off
no-op guarantee, and the snapshot contract an operator's dashboard reads."""
import json

import numpy as np
import pytest
import jax

from paddle_tpu.models.llama import (llama_config_tiny,
                                     build_functional_llama, llama_generate)
from paddle_tpu.inference.paged import EngineStalledError, ServingEngine
from paddle_tpu.observability import (Counter, EngineStats, FlightRecorder,
                                      Gauge, GaugeSeries, Histogram,
                                      MetricsRegistry, Telemetry,
                                      TrainTelemetry, latency_percentiles,
                                      slo_report)
from paddle_tpu.resilience import inject

rng = np.random.default_rng(17)


def _llama(seed=1):
    cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    return cfg, (ep, bp, hp)


def _engine(cfg, params, telemetry=True, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("attention_impl", "ref")
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("decode_horizon", 4)
    return ServingEngine(params, cfg, telemetry=telemetry, **kw)


class _FakeClock:
    """Deterministic injectable clock: each call advances by `tick`."""

    def __init__(self, start=100.0, tick=0.5):
        self.t = start
        self.tick = tick

    def __call__(self):
        t = self.t
        self.t += self.tick
        return t


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_monotonic(self):
        c = Counter("x")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)
        assert c.value == 4

    def test_gauge_last_value(self):
        g = Gauge("g")
        g.set(3)
        g.set(1.5)
        assert g.to_value() == 1.5

    def test_histogram_quantiles_vs_numpy(self):
        """Log-bucketed quantiles must track np.percentile within the
        bucket's relative width (growth=1.1 → ~10% worst case; the
        interpolation usually does much better)."""
        h = Histogram("lat")
        vals = rng.lognormal(mean=-4.0, sigma=1.0, size=2000)
        for v in vals:
            h.observe(v)
        for q in (50, 95, 99):
            got = h.quantile(q / 100.0)
            want = float(np.percentile(vals, q))
            assert abs(got - want) / want < 0.11, (q, got, want)
        assert h.count == 2000
        assert h.min == vals.min() and h.max == vals.max()
        np.testing.assert_allclose(h.total, vals.sum(), rtol=1e-9)

    def test_histogram_single_sample_is_exact(self):
        h = Histogram("one")
        h.observe(0.0421)
        for q in (0.0, 0.5, 0.95, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(0.0421)
        d = h.to_value()
        assert d["count"] == 1 and d["p50"] == pytest.approx(0.0421)

    def test_histogram_empty_and_fraction_below(self):
        h = Histogram("e")
        assert h.quantile(0.5) == 0.0
        assert h.fraction_below(1.0) == 0.0
        for v in (0.001, 0.01, 0.1, 1.0):
            h.observe(v)
        assert h.fraction_below(10.0) == 1.0
        assert h.fraction_below(1e-6) == 0.0
        mid = h.fraction_below(0.02)
        assert 0.25 <= mid <= 0.75

    def test_registry_get_or_create_and_type_conflict(self):
        r = MetricsRegistry()
        c = r.counter("serve.x")
        assert r.counter("serve.x") is c
        with pytest.raises(TypeError, match="already registered"):
            r.gauge("serve.x")
        assert "serve.x" in r

    def test_registry_snapshot_with_injectable_clock(self):
        clk = _FakeClock(start=50.0, tick=1.0)
        r = MetricsRegistry(clock=clk)
        r.counter("c").inc(7)
        r.gauge("g").set(2.5)
        r.histogram("h").observe(0.25)
        snap = r.snapshot()
        assert snap["c"] == 7 and snap["g"] == 2.5
        assert snap["h"]["count"] == 1
        assert snap["at"] == 50.0           # first clock read, deterministic
        assert r.snapshot()["at"] == 51.0   # ticks advance


# ---------------------------------------------------------------------------
# EngineStats snapshot/delta + stats() monotonicity (ISSUE 6 satellite)
# ---------------------------------------------------------------------------
class TestEngineStats:
    def test_capture_flattens_nested(self):
        s = EngineStats.capture({"a": 1, "nested": {"x": 2, "y": 3},
                                 "rate": 0.5}, clock=lambda: 9.0)
        assert s["a"] == 1 and s["nested.x"] == 2 and s["rate"] == 0.5
        assert s.at == 9.0
        assert "rate" not in s.counters()     # ratios are not counters

    def test_delta_is_per_window_activity(self):
        cfg, params = _llama()
        eng = _engine(cfg, params, telemetry=None)
        p = rng.integers(1, 64, (6,)).astype(np.int32)
        eng.submit(p, max_new_tokens=5)
        eng.run()
        s1 = eng.stats_snapshot()
        eng.submit(p, max_new_tokens=7)
        eng.submit(p[:3], max_new_tokens=4)
        eng.run()
        s2 = eng.stats_snapshot()
        d = s2.delta(s1)
        assert d["tokens_generated"] == 7 + 4      # exactly this window
        assert d["window_s"] > 0
        assert all(v >= 0 for k, v in d.items() if k != "window_s")
        zero = s2.delta(s2)
        assert all(v == 0 for k, v in zero.items() if k != "window_s")

    def test_stats_monotonic_across_full_serving_trace(self):
        """Counters never decrease at ANY step boundary of a trace that
        exercises prefix cache, chunked prefill, and speculation."""
        cfg, params = _llama(seed=3)
        eng = _engine(cfg, params, telemetry=None, prefill_chunk=8,
                      speculative=2)
        for t, n in ((14, 6), (9, 4), (22, 8), (14, 5)):
            eng.submit(rng.integers(1, 64, (t,)).astype(np.int32),
                       max_new_tokens=n)
        prev = eng.stats_snapshot()
        while eng.num_active or eng._queue:
            eng.step()
            cur = eng.stats_snapshot()
            pc = prev.counters()
            for k, v in cur.counters().items():
                assert v >= pc.get(k, 0), f"counter {k} decreased"
            prev = cur


# ---------------------------------------------------------------------------
# Request timing fields (ISSUE 6 satellite)
# ---------------------------------------------------------------------------
class TestRequestTiming:
    def test_admit_retire_queue_tpot(self):
        cfg, params = _llama()
        eng = _engine(cfg, params, telemetry=None, num_slots=1)
        p = rng.integers(1, 64, (6,)).astype(np.int32)
        r1 = eng.submit(p, max_new_tokens=6)
        r2 = eng.submit(p[:4], max_new_tokens=4)     # waits for the slot
        done = eng.run()
        for r in (done[r1], done[r2]):
            assert 0 < r.submit_time <= r.admit_time
            assert r.admit_time <= r.first_token_time <= r.finish_time
            assert r.retire_time == r.finish_time
            assert r.queue_time == r.admit_time - r.submit_time
            assert r.ttft == pytest.approx(r.queue_time + r.prefill_time)
            n = len(r.generated) - 1
            assert r.tpot == pytest.approx(
                (r.finish_time - r.first_token_time) / n)
        # the second request queued behind a full slot set: its wait is
        # real, and TTFT now decomposes into queue wait vs prefill
        assert done[r2].queue_time > done[r1].queue_time

    def test_unadmitted_request_reports_zero(self):
        cfg, params = _llama()
        eng = _engine(cfg, params, telemetry=None)
        rid = eng.submit(rng.integers(1, 64, (4,)).astype(np.int32),
                         max_new_tokens=2)
        req = eng._queue[0]
        assert req.rid == rid
        assert req.queue_time == 0.0 and req.ttft == 0.0 and req.tpot == 0.0
        eng.run()


# ---------------------------------------------------------------------------
# request-lifecycle tracing
# ---------------------------------------------------------------------------
class TestLifecycleTrace:
    def test_event_order_dense_prefill(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        rid = eng.submit(rng.integers(1, 64, (6,)).astype(np.int32),
                         max_new_tokens=6)
        eng.run()
        names = tel.tracer.get(rid).names()
        core = [n for n in names if n in ("submitted", "queued", "admitted",
                                          "prefill_dense", "first_token",
                                          "retired")]
        assert core == ["submitted", "queued", "admitted", "prefill_dense",
                        "first_token", "retired"]
        assert "decode_dispatch" in names
        # timestamps are ordered
        ts = [t for _, t, _ in tel.tracer.get(rid).events]
        assert ts == sorted(ts)

    def test_chunked_prefill_and_cache_hit_events(self):
        cfg, params = _llama(seed=2)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel, prefill_chunk=4,
                      prompt_bucket=4)
        p = rng.integers(1, 64, (13,)).astype(np.int32)
        r1 = eng.submit(p, max_new_tokens=4)
        eng.run()
        names1 = tel.tracer.get(r1).names()
        chunks = [n for n in names1 if n == "prefill_chunk"]
        assert len(chunks) >= 3          # 13 tokens / 4-token chunks
        assert names1.index("admitted") < names1.index("prefill_chunk") \
            < names1.index("first_token")
        # same prompt again: the retired pages were parked in the prefix
        # cache, so the second admission records a cache_hit
        r2 = eng.submit(p, max_new_tokens=4)
        eng.run()
        names2 = tel.tracer.get(r2).names()
        assert "cache_hit" in names2

    @pytest.mark.parametrize("telemetry", [False, True],
                             ids=["telemetry-off", "telemetry-on"])
    def test_phase_annotations_fire_with_telemetry_off_and_on(
            self, monkeypatch, telemetry):
        """Every host phase of a step is a profiler annotation
        (`jax.profiler.TraceAnnotation("serve.<phase>")`) whether or not a
        Telemetry is attached — tracing is on when a profiler session is
        open, nothing else — and both ways give the same names in the same
        order."""
        entered = []

        class _Rec:
            def __init__(self, name, **attrs):
                self.name = name

            def __enter__(self):
                entered.append(self.name)
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **attrs):
                pass

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Rec)
        cfg, params = _llama()

        def names(tel):
            del entered[:]
            eng = _engine(cfg, params, telemetry=tel, prefill_chunk=4,
                          prompt_bucket=4)
            eng.submit(np.random.default_rng(7).integers(1, 64, (13,))
                       .astype(np.int32), max_new_tokens=4)
            eng.run()
            assert (eng.telemetry is not None) == bool(tel)
            return list(entered)

        got = names(telemetry)
        assert got[:2] == ["serve.step", "serve.sched"]
        assert {"serve.prefill_chunk", "serve.first_token_sync",
                "serve.provision", "serve.decode_dispatch",
                "serve.decode_sync", "serve.decode_record"} <= set(got)
        assert got == names(not telemetry)

    def test_preemption_events_recorded(self):
        cfg, params = _llama(seed=5)
        tel = Telemetry()
        eng = ServingEngine(params, cfg, num_slots=2, page_size=2,
                            num_pages=40, max_pages_per_seq=16,
                            attention_impl="ref", prompt_bucket=8,
                            decode_horizon=2, telemetry=tel)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 7, 3)]
        with inject({"serve.pool_pressure": dict(action="trigger", after=1,
                                                 count=3)}):
            rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
            done = eng.run()
        assert eng.preemptions >= 1
        assert len(done) == 3
        victim = next(r for r in done.values() if r.preemptions > 0)
        names = tel.tracer.get(victim.rid).names()
        i_pre = names.index("preempted")
        # re-admission follows the preemption in the same record
        assert "admitted" in names[i_pre:]
        assert names[-1] == "retired"


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------
class TestChromeTrace:
    def test_export_valid_json_with_nested_spans(self, tmp_path):
        cfg, params = _llama(seed=2)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel, prefill_chunk=4,
                      prompt_bucket=4)
        for t, n in ((13, 4), (6, 5)):
            eng.submit(rng.integers(1, 64, (t,)).astype(np.int32),
                       max_new_tokens=n)
        eng.run()
        out = tmp_path / "serve_trace.json"
        tel.tracer.export_chrome(str(out))
        data = json.loads(out.read_text())     # valid JSON, loadable shape
        evs = data["traceEvents"]
        assert data["displayTimeUnit"] == "ms"
        assert any(e.get("ph") == "M" and e.get("name") == "process_name"
                   for e in evs)
        # per-request track: one top-level request span, phases nested
        # inside it (chrome nesting == containment on one tid)
        by_tid = {}
        for e in evs:
            if e.get("ph") == "X":
                by_tid.setdefault(e["tid"], []).append(e)
        req_tids = [tid for tid, es in by_tid.items()
                    if any(e["name"].startswith("request") for e in es)]
        assert len(req_tids) == 2
        eps = 0.01                              # us; rounding slack
        for tid in req_tids:
            spans = by_tid[tid]
            parent = next(e for e in spans
                          if e["name"].startswith("request"))
            p0, p1 = parent["ts"], parent["ts"] + parent["dur"]
            children = [e for e in spans if e is not parent]
            assert children                     # phases exist
            for c in children:
                assert c["ts"] >= p0 - eps, (c["name"], c["ts"], p0)
                assert c["ts"] + c.get("dur", 0) <= p1 + eps, c["name"]
            phase_names = {c["name"] for c in children}
            assert "queued" in phase_names and "decode" in phase_names
        # engine track carries the step/dispatch phase spans
        engine_spans = {e["name"] for e in by_tid.get(0, [])}
        assert "step" in engine_spans and "decode_dispatch" in engine_spans
        # instant events are well-formed
        for e in evs:
            if e.get("ph") == "i":
                assert "ts" in e and e.get("s") == "t"

    def test_inflight_request_exports_cleanly(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        eng.submit(rng.integers(1, 64, (6,)).astype(np.int32),
                   max_new_tokens=8)
        eng.step()                              # mid-flight
        data = tel.tracer.to_chrome_trace()
        assert any(e["name"].startswith("request")
                   for e in data["traceEvents"] if e.get("ph") == "X")
        eng.run()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_ring_is_bounded_with_continuous_seq(self):
        clk = _FakeClock()
        fr = FlightRecorder(capacity=8, clock=clk)
        for i in range(20):
            fr.record("e", i=i)
        assert len(fr) == 8
        seqs = [e["seq"] for e in fr.events()]
        assert seqs == list(range(13, 21))      # the most recent window
        d = fr.dump("test", note="x")
        assert d["total_events"] == 20 and len(d["events"]) == 8
        assert "note" in d["extra"]
        assert "flight-recorder dump: test" in FlightRecorder.format_dump(d)

    def test_dump_history_bounded(self):
        fr = FlightRecorder(capacity=4, max_dumps=3)
        for i in range(6):
            fr.record("e")
            fr.dump(f"r{i}")
        assert len(fr.dumps) == 3
        assert fr.last_dump()["reason"] == "r5"

    def test_dump_fires_on_engine_stalled(self):
        """A never-clearing injected pool-pressure window stalls the
        engine; the EngineStalledError dump must carry the recent-event
        window showing the no-progress steps."""
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        with inject({"serve.pool_pressure": dict(action="trigger",
                                                 count=None)}):
            eng.submit(rng.integers(1, 64, (5,)).astype(np.int32),
                       max_new_tokens=4)
            with pytest.raises(EngineStalledError):
                eng.run(max_stall_steps=5)
        dump = tel.flight.last_dump()
        assert dump["reason"] == "engine_stalled"
        assert dump["extra"]["stalled_steps"] == 5
        steps = [e for e in dump["events"] if e["event"] == "step"]
        assert steps and all(not s["progressed"] for s in steps)
        # every pressured step also flagged the injected fault
        assert any(d["reason"] == "injected_fault" for d in tel.flight.dumps)
        # drain the queue so the refcount leak guard sees a clean pool
        eng.run()

    def test_dump_fires_on_preemption_storm(self):
        cfg, params = _llama(seed=5)
        tel = Telemetry(storm_threshold=2, storm_window=32)
        eng = ServingEngine(params, cfg, num_slots=2, page_size=2,
                            num_pages=40, max_pages_per_seq=16,
                            attention_impl="ref", prompt_bucket=8,
                            decode_horizon=2, telemetry=tel)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 7, 3)]
        with inject({"serve.pool_pressure": dict(action="trigger", after=1,
                                                 count=4)}):
            for p in prompts:
                eng.submit(p, max_new_tokens=8)
            eng.run()
        assert eng.preemptions >= 2
        storm = [d for d in tel.flight.dumps
                 if d["reason"] == "preemption_storm"]
        assert storm and storm[0]["extra"]["preemptions_in_window"] >= 2


# ---------------------------------------------------------------------------
# telemetry-off is a no-op; telemetry-on is bit-exact
# ---------------------------------------------------------------------------
class TestTelemetryNoop:
    def test_off_by_default_and_bit_exact_on_vs_off(self):
        cfg, params = _llama(seed=4)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 9, 3)]
        eng_off = _engine(cfg, params, telemetry=None)
        assert eng_off.telemetry is None           # off = no object at all
        assert _engine(cfg, params, telemetry=False).telemetry is None
        rids_off = [eng_off.submit(p, max_new_tokens=6) for p in prompts]
        done_off = eng_off.run()
        tel = Telemetry()
        eng_on = _engine(cfg, params, telemetry=tel)
        rids_on = [eng_on.submit(p, max_new_tokens=6) for p in prompts]
        done_on = eng_on.run()
        for a, b, p in zip(rids_off, rids_on, prompts):
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=6))[0]
            np.testing.assert_array_equal(done_off[a].output_ids, ref)
            np.testing.assert_array_equal(done_on[b].output_ids, ref)
        # and the on-engine actually recorded the trace
        assert len(tel.tracer.traces()) == len(prompts)
        assert tel.registry.snapshot()["serve.requests_retired"] == 3

    def test_telemetry_true_builds_default(self):
        cfg, params = _llama()
        eng = _engine(cfg, params, telemetry=True)
        assert isinstance(eng.telemetry, Telemetry)
        eng.submit(rng.integers(1, 64, (4,)).astype(np.int32),
                   max_new_tokens=2)
        eng.run()
        assert eng.telemetry.flight.event_names()[0] == "submit"


# ---------------------------------------------------------------------------
# SLO report + shared percentile helper
# ---------------------------------------------------------------------------
class TestSLO:
    def test_goodput_counts_only_on_time_requests(self):
        summaries = [
            {"rid": 0, "tokens": 10, "ttft_s": 0.05, "tpot_s": 0.01,
             "e2e_s": 0.2, "timed_out": False},
            {"rid": 1, "tokens": 20, "ttft_s": 0.50, "tpot_s": 0.01,
             "e2e_s": 0.8, "timed_out": False},    # missed the deadline
            {"rid": 2, "tokens": 5, "ttft_s": 0.01, "tpot_s": 0.02,
             "e2e_s": 0.1, "timed_out": True},     # overdue: never good
        ]
        rep = slo_report(summaries, ttft_deadline_s=0.1, window_s=2.0)
        assert rep["requests"] == 3
        assert rep["on_time_requests"] == 1
        assert rep["goodput_fraction"] == pytest.approx(1 / 3, abs=1e-4)
        assert rep["total_tokens"] == 35 and rep["goodput_tokens"] == 10
        assert rep["goodput_tokens_per_sec"] == pytest.approx(5.0)
        assert rep["ttft"]["count"] == 3
        for block in ("ttft", "tpot", "e2e"):
            for f in ("p50_ms", "p95_ms", "p99_ms"):
                assert f in rep[block]

    def test_latency_percentiles_helper(self):
        vals = [0.010, 0.020, 0.030, 0.040, 0.100]
        out = latency_percentiles(vals)
        assert set(out) == {"p50_ms", "p95_ms", "p99_ms"}
        assert 15.0 <= out["p50_ms"] <= 35.0
        assert out["p99_ms"] <= 100.0 + 1e-6

    def test_engine_slo_report_end_to_end(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        for t, n in ((6, 4), (9, 6)):
            eng.submit(rng.integers(1, 64, (t,)).astype(np.int32),
                       max_new_tokens=n)
        eng.run()
        rep = tel.slo_report(ttft_deadline_s=60.0, window_s=1.0)
        assert rep["requests"] == 2 and rep["goodput_fraction"] == 1.0
        assert rep["total_tokens"] == 10
        assert rep["step_latency"]["count"] >= 1


# ---------------------------------------------------------------------------
# the snapshot an operator scrapes (README §Observability)
# ---------------------------------------------------------------------------
def test_snapshot_carries_the_documented_histograms_and_counters():
    """After one request through a real telemetry engine the snapshot
    holds the five latency histograms with their seven fields and the
    four engine counters: what a dashboard built on the README reads."""
    cfg, params = _llama()
    eng = _engine(cfg, params, telemetry=True)
    eng.submit(rng.integers(1, 64, (5,)).astype(np.int32), max_new_tokens=3)
    eng.run()
    snap = eng.telemetry.snapshot(eng.stats())
    for name in ("serve.ttft_s", "serve.tpot_s", "serve.queue_s",
                 "serve.e2e_s", "engine.step_host_s"):
        hist = snap[name]
        for field in ("count", "sum", "min", "max", "p50", "p95", "p99"):
            assert field in hist, (name, field)
        assert hist["count"] >= 1, name
    for name in ("engine.tokens_generated", "engine.decode_steps",
                 "engine.prefill_tokens_executed",
                 "engine.fused_sample_steps"):
        assert name in snap, name
    assert snap["engine.tokens_generated"] == 3
    assert snap["engine.prefill_tokens_executed"] == 5


# ---------------------------------------------------------------------------
# gauge time series (ISSUE 7 memory observatory primitive)
# ---------------------------------------------------------------------------
class TestGaugeSeries:
    def test_sampling_monotonic_under_injectable_clock(self):
        clk = _FakeClock(start=10.0, tick=0.25)
        r = MetricsRegistry(clock=clk)
        s = r.series("mem.pool", capacity=8)
        assert r.series("mem.pool") is s          # get-or-create
        for i in range(20):
            s.sample(clk(), free=64 - i, occupancy_frac=i / 64)
        rows = s.rows()
        assert len(rows) == 8                     # bounded ring
        assert s.total_samples == 20
        seqs = [row["seq"] for row in rows]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert seqs == list(range(13, 21))        # the most recent window
        ts = [row["t"] for row in rows]
        assert ts == sorted(ts)                   # clock-monotonic
        # reset drops rows but seq keeps counting (global sample order)
        s.reset()
        assert len(s) == 0
        row = s.sample(clk(), free=1)
        assert row["seq"] == 21
        assert s.to_value()["count"] == 1

    def test_value_normalization_and_minmax(self):
        s = GaugeSeries("m")
        s.sample(1.0, free=np.int32(7), occ=np.float64(0.5), flag=True,
                 label="x", none=None)
        row = s.last
        assert row["free"] == 7 and type(row["free"]) is int
        assert row["occ"] == 0.5 and type(row["occ"]) is float
        assert row["flag"] is True and row["label"] == "x"
        assert row["none"] is None
        json.dumps(row)                           # flight-dump JSON-safe
        s.sample(2.0, free=3, occ=0.9)
        assert s.field_minmax("free") == (3, 7)
        assert s.field_minmax("occ") == (0.5, 0.9)
        assert s.field_minmax("label") is None    # non-numeric
        assert s.tail(1) == [s.last] and s.tail(0) == []

    def test_registry_type_conflict(self):
        r = MetricsRegistry()
        r.series("x")
        with pytest.raises(TypeError, match="already registered"):
            r.histogram("x")


# ---------------------------------------------------------------------------
# utilization: host/device step decomposition (ISSUE 7 tentpole a)
# ---------------------------------------------------------------------------
class TestUtilization:
    def test_decomposition_is_disjoint_and_complete(self):
        cfg, params = _llama(seed=3)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel, prefill_chunk=4,
                      prompt_bucket=4)
        # warm, then measure a window
        eng.submit(rng.integers(1, 64, (13,)).astype(np.int32),
                   max_new_tokens=4)
        eng.run()
        tel.reset_window()
        import time
        t0 = time.perf_counter()
        for t, n in ((13, 5), (6, 4), (9, 6)):
            eng.submit(rng.integers(1, 64, (t,)).astype(np.int32),
                       max_new_tokens=n)
        eng.run()
        dt = time.perf_counter() - t0
        u = tel.utilization_report(window_s=dt)
        assert u["steps"] >= 1
        # the three buckets + gap tile the window exactly (no phase is
        # counted twice — the sched span subtracts nested prefill
        # dispatches)
        total = (u["host_busy_s"] + u["dispatch_s"] + u["device_wait_s"]
                 + u["gap_s"])
        assert total == pytest.approx(dt, rel=0.02)
        fsum = (u["host_busy_frac"] + u["dispatch_frac"]
                + u["device_wait_frac"] + u["gap_frac"])
        assert fsum == pytest.approx(1.0, abs=0.01)
        # the phases that actually ran are in the per-phase table
        assert "sched" in u["per_phase"]
        assert "decode_dispatch" in u["per_phase"]
        assert "prefill_chunk" in u["per_phase"]
        assert u["per_phase"]["sched"]["count"] == u["steps"]
        # every accounted second is attributed to a listed phase
        phase_sum = sum(p["total_s"] for p in u["per_phase"].values())
        assert phase_sum == pytest.approx(
            u["host_busy_s"] + u["dispatch_s"] + u["device_wait_s"],
            abs=1e-4)

    def test_sched_subtracts_nested_prefill_dispatch(self):
        """An admission-heavy window must not count its prefill dispatch
        seconds twice (once in sched, once in prefill_*)."""
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        for _ in range(4):
            eng.submit(rng.integers(1, 64, (9,)).astype(np.int32),
                       max_new_tokens=2)
        eng.run()
        u = tel.utilization_report()
        sched = u["per_phase"]["sched"]["total_s"]
        dense = u["per_phase"]["prefill_dense"]["total_s"]
        # the dense prefills ran INSIDE admission; had sched kept them its
        # total would dominate dense — subtracted, it must be well below
        assert sched < dense

    def test_window_report_resets(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        eng.submit(rng.integers(1, 64, (5,)).astype(np.int32),
                   max_new_tokens=3)
        eng.run()
        assert tel.utilization_report()["steps"] >= 1
        tel.reset_window()
        u = tel.utilization_report(window_s=1.0)
        assert u["steps"] == 0 and u["host_busy_s"] == 0.0
        assert u["gap_frac"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# memory observatory (ISSUE 7 tentpole b)
# ---------------------------------------------------------------------------
class TestMemoryObservatory:
    def test_per_step_series_and_report(self):
        cfg, params = _llama(seed=2)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel, prefill_chunk=4,
                      prompt_bucket=4)
        p = rng.integers(1, 64, (13,)).astype(np.int32)
        eng.submit(p, max_new_tokens=4)
        eng.run()
        rows = tel.memory.rows()
        assert len(rows) == eng._step_seq         # one sample per step
        for row in rows:
            assert 0.0 <= row["occupancy_frac"] <= 1.0
            assert 0.0 <= row["fragmentation_frac"] <= 1.0
            assert row["free_pages"] + row["allocated_pages"] \
                == row["total_pages"]
            assert row["referenced"] >= row["allocated_pages"]
        # retire parked pages in the cache: the last sample shows them
        assert rows[-1]["cache_page_refs"] > 0
        assert rows[-1]["active"] == 0
        rep = tel.memory_report(eng.stats())
        assert rep["samples"] == len(rows)
        assert rep["last"] == rows[-1]
        assert rep["peak_occupancy_frac"] >= rows[-1]["occupancy_frac"]
        assert rep["min_free_pages"] <= rows[-1]["free_pages"]
        assert rep["prefix_cache"]["executed_tokens"] > 0
        # gauges carry the last values into the metrics snapshot
        snap = tel.registry.snapshot()
        assert snap["mem.pool_free_pages"] == rows[-1]["free_pages"]
        assert snap["mem.pool"]["count"] == len(rows)

    def test_pool_pressure_dump_includes_occupancy_ramp(self):
        """The acceptance drill: a pool-pressure flight dump must show the
        occupancy ramp that caused it, not just the moment of failure."""
        cfg, params = _llama(seed=5)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        eng.submit(rng.integers(1, 64, (9,)).astype(np.int32),
                   max_new_tokens=6)
        with inject({"serve.pool_pressure": dict(action="trigger",
                                                 count=1)}):
            eng.submit(rng.integers(1, 64, (5,)).astype(np.int32),
                       max_new_tokens=4)
            eng.run()
        dump = next(d for d in tel.flight.dumps
                    if d["reason"] == "injected_fault")
        ramp = dump["extra"]["memory_ramp"]
        assert ramp, "pressure dump carries no occupancy ramp"
        assert all("occupancy_frac" in row and "free_pages" in row
                   for row in ramp)
        seqs = [row["seq"] for row in ramp]
        assert seqs == sorted(seqs)
        json.dumps(dump)                          # JSONL-able postmortem

    def test_chrome_export_has_counter_tracks(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        eng.submit(rng.integers(1, 64, (6,)).astype(np.int32),
                   max_new_tokens=4)
        eng.run()
        data = tel.tracer.to_chrome_trace()
        cevs = [e for e in data["traceEvents"] if e.get("ph") == "C"]
        assert cevs, "no counter events exported"
        tracks = {e["name"] for e in cevs}
        assert "pagepool.pages" in tracks and "engine.load" in tracks
        pool = [e for e in cevs if e["name"] == "pagepool.pages"]
        assert len(pool) == eng._step_seq         # one sample per step
        for e in pool:
            assert set(e["args"]) == {"used", "free", "cached"}
            assert "ts" in e
        json.dumps(data)

    def test_reset_window_drops_series(self):
        cfg, params = _llama()
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        eng.submit(rng.integers(1, 64, (5,)).astype(np.int32),
                   max_new_tokens=3)
        eng.run()
        assert tel.memory_report()["samples"] > 0
        tel.reset_window()
        rep = tel.memory_report()
        assert rep["samples"] == 0 and rep["last"] is None
        assert rep["peak_occupancy_frac"] is None


# ---------------------------------------------------------------------------
# compile accounting (ISSUE 7 tentpole a: engine.compile_s)
# ---------------------------------------------------------------------------
class TestCompileAccounting:
    def test_compiles_recorded_then_steady_state_adds_none(self):
        cfg, params = _llama(seed=4)
        tel = Telemetry()
        eng = _engine(cfg, params, telemetry=tel)
        p = rng.integers(1, 64, (6,)).astype(np.int32)
        eng.submit(p, max_new_tokens=5)
        eng.run()
        rep = tel.compile_report()
        assert rep["total_compiles"] > 0
        assert rep["compile_s_total"] > 0.0
        assert "prefill" in rep["per_fn"] and "decode_step" in rep["per_fn"]
        for e in rep["per_fn"].values():
            assert e["count"] >= 1 and e["total_s"] > 0.0
        # the compile ledger agrees with the sanitizer's miss counters
        assert rep["total_compiles"] == sum(eng.jit_cache_misses.values())
        # flight record carries one compile event per miss
        compiles = [e for e in tel.flight.events()
                    if e["event"] == "compile"]
        assert len(compiles) == rep["total_compiles"]
        assert all(e["dur_s"] > 0 for e in compiles)
        # metrics snapshot: histogram + counter
        snap = tel.registry.snapshot()
        assert snap["engine.compiles"] == rep["total_compiles"]
        assert snap["engine.compile_s"]["count"] == rep["total_compiles"]
        # warmed steady state: identical traffic adds ZERO compiles
        before = rep["total_compiles"]
        eng.submit(p, max_new_tokens=5)
        eng.run()
        assert tel.compile_report()["total_compiles"] == before

    def test_off_engine_pays_nothing(self):
        cfg, params = _llama()
        eng = _engine(cfg, params, telemetry=None)
        eng.submit(rng.integers(1, 64, (5,)).astype(np.int32),
                   max_new_tokens=3)
        eng.run()                                 # on_miss hook is inert
        assert eng.jit_cache_misses               # misses still counted


# ---------------------------------------------------------------------------
# EngineStats.delta across a preemption + re-prefill window (satellite)
# ---------------------------------------------------------------------------
class TestEngineStatsPreemptionWindow:
    def test_delta_window_containing_preemption_and_reprefill(self):
        cfg, params = _llama(seed=5)
        eng = ServingEngine(params, cfg, num_slots=2, page_size=2,
                            num_pages=40, max_pages_per_seq=16,
                            attention_impl="ref", prompt_bucket=8,
                            decode_horizon=2, telemetry=None)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 7, 3)]
        s0 = eng.stats_snapshot()
        with inject({"serve.pool_pressure": dict(action="trigger", after=1,
                                                 count=3)}):
            for p in prompts:
                eng.submit(p, max_new_tokens=8)
            done = eng.run()
        s1 = eng.stats_snapshot()
        assert len(done) == 3
        assert any(r.preemptions > 0 for r in done.values())
        d = s1.delta(s0)
        # the window saw the preemption AND the victim's re-prefill: the
        # executed prefill tokens exceed the three prompts' fresh tokens
        assert d["preemptions"] >= 1
        assert d["preemptions"] == eng.preemptions
        fresh = sum(len(p) for p in prompts)
        assert d["prefill_tokens_executed"] + d["cached_prefix_tokens"] \
            > fresh
        assert d["tokens_generated"] == 8 * 3
        assert all(v >= 0 for k, v in d.items() if k != "window_s")
        # a second, quiet window diffs back to zero activity
        s2 = eng.stats_snapshot()
        z = s2.delta(s1)
        assert all(v == 0 for k, v in z.items() if k != "window_s")


# ---------------------------------------------------------------------------
# training telemetry (ISSUE 7 tentpole c)
# ---------------------------------------------------------------------------
import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu import nn, optimizer as optim                 # noqa: E402


class TestTrainTelemetry:
    def _ts(self, tel, guard=2, scaler=None):
        from paddle_tpu.parallel.train_step import compile_train_step
        paddle.seed(13)
        net = nn.Linear(8, 4)
        opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        ts = compile_train_step(net, opt, lambda m, x: m(x).mean(),
                                nonfinite_guard=guard, scaler=scaler,
                                telemetry=tel)
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(
            np.float32)
        return ts, x

    def test_step_timing_and_counters(self):
        tel = TrainTelemetry()
        ts, x = self._ts(tel)
        for _ in range(4):
            ts(x)
        rep = tel.report(window_s=2.0)
        assert rep["steps"] == 4
        assert rep["samples"] == 16               # 4 steps x batch 4
        assert rep["step_s"]["count"] == 4
        assert rep["step_s"]["p50_ms"] > 0
        assert rep["steps_per_sec"] == pytest.approx(2.0)
        assert rep["nonfinite_skips"] == 0

    def test_nonfinite_skip_records_flight_event_with_fault_plan(self):
        """Satellite: TrainStep resilience events reach the flight
        recorder WITH the active FaultPlan context (the existing
        train.nonfinite fault point drives the drill)."""
        tel = TrainTelemetry()
        ts, x = self._ts(tel, guard=3)
        with inject({"train.nonfinite": dict(action="trigger", at=1)},
                    seed=7):
            for _ in range(3):
                ts(x)
        assert ts.skipped_steps == 1
        skips = [e for e in tel.flight.events()
                 if e["event"] == "nonfinite_skip"]
        assert len(skips) == 1
        ev = skips[0]
        assert ev["step"] == 1 and ev["consecutive"] == 1
        fp = ev["fault_plan"]
        assert fp is not None
        assert fp["seed"] == 7 and fp["fired"] == 1
        assert "train.nonfinite:trigger" in fp["specs"]
        assert tel.registry.snapshot()["train.nonfinite_skips"] == 1
        # outside an inject scope the context is None, not invented
        from paddle_tpu.observability import fault_context
        assert fault_context() is None

    def test_nonfinite_raise_auto_dumps(self):
        tel = TrainTelemetry()
        ts, x = self._ts(tel, guard=2)
        with inject({"train.nonfinite": dict(action="trigger", after=0,
                                             count=None)}):
            with pytest.raises(FloatingPointError, match="2 consecutive"):
                for _ in range(5):
                    ts(x)
        d = tel.flight.last_dump()
        assert d["reason"] == "nonfinite_raise"
        assert d["extra"]["consecutive"] == 2
        names = [e["event"] for e in d["events"]]
        assert names.count("nonfinite_skip") == 2
        assert "nonfinite_raise" in names
        assert tel.registry.snapshot()["train.nonfinite_raises"] == 1

    def test_scaler_backoff_counted(self):
        scaler = paddle.amp.GradScaler(enable=True,
                                       init_loss_scaling=1024.0,
                                       decr_every_n_nan_or_inf=1)
        tel = TrainTelemetry()
        ts, x = self._ts(tel, scaler=scaler)
        with inject({"train.nonfinite": dict(action="trigger", at=1)}):
            for _ in range(3):
                ts(x)
        assert scaler._scale == 512.0
        assert tel.registry.snapshot()["train.scaler_backoffs"] == 1
        assert "scaler_backoff" in tel.flight.event_names()

    def test_telemetry_off_is_default_and_steps_match(self):
        ts_off, x = self._ts(None)
        assert ts_off.telemetry is None
        tel = TrainTelemetry()
        ts_on, _ = self._ts(tel)
        for _ in range(3):
            a = float(ts_off(x).numpy())
            b = float(ts_on(x).numpy())
            assert a == b                         # bit-exact on vs off


class TestModelFitTelemetry:
    def _fit(self, tel, save_dir=None):
        paddle.seed(7)
        net = nn.Linear(4, 2)
        from paddle_tpu.hapi import Model
        m = Model(net)
        m.prepare(optimizer=optim.SGD(learning_rate=0.1,
                                      parameters=net.parameters()),
                  loss=lambda out, y: ((out - y) ** 2).mean())
        g = np.random.default_rng(1)
        xs = g.standard_normal((8, 4)).astype(np.float32)
        ys = g.standard_normal((8, 2)).astype(np.float32)
        data = [(xs[i * 2:(i + 1) * 2], ys[i * 2:(i + 1) * 2])
                for i in range(4)]
        losses = []
        from paddle_tpu.hapi.callbacks import Callback

        class Rec(Callback):
            def on_batch_end(self, mode, step, logs=None):
                if mode == "train" and logs and "loss" in logs:
                    losses.append(logs["loss"])

        m.fit(data, epochs=2, verbose=0, callbacks=[Rec()],
              telemetry=tel, save_dir=save_dir)
        return losses

    def test_fit_bit_exact_and_step_quantiles(self, tmp_path):
        """Acceptance: a Model.fit run with telemetry on produces
        train.step_s quantiles and checkpoint spans, bit-exact vs off."""
        tel = TrainTelemetry()
        l_on = self._fit(tel, save_dir=str(tmp_path / "ck"))
        l_off = self._fit(None)
        assert l_on == l_off                      # bit-exact on vs off
        rep = tel.report(window_s=1.0)
        assert rep["steps"] == 8                  # 2 epochs x 4 batches
        assert rep["samples"] == 16
        snap = tel.snapshot()
        h = snap["train.step_s"]
        for f in ("count", "p50", "p95", "p99"):
            assert f in h
        assert h["count"] == 8
        # the data-wait vs compute split is recorded per step
        assert snap["train.data_s"]["count"] == 8
        assert snap["train.compute_s"]["count"] == 8
        assert 0.0 <= rep["data_wait_frac"] <= 1.0
        # save_dir checkpoints got ckpt.save spans (one per epoch)
        assert snap["ckpt.save_s"]["count"] == 2
        assert tel.registry.snapshot()["ckpt.saves"] == 2
        saves = [e for e in tel.flight.events()
                 if e["event"] == "ckpt.save"]
        assert len(saves) == 2 and all(e["ok"] for e in saves)


class TestCheckpointTelemetry:
    def _mgr(self, root, tel, keep_last=None):
        from paddle_tpu.resilience import CheckpointManager
        paddle.seed(3)
        net = nn.Linear(6, 3)
        opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        return CheckpointManager(str(root), model=net, optimizer=opt,
                                 keep_last=keep_last, telemetry=tel), net

    def test_save_restore_spans_and_phases(self, tmp_path):
        tel = TrainTelemetry()
        mgr, _ = self._mgr(tmp_path, tel)
        mgr.save(1)
        snap = tel.snapshot()
        # whole-save span + the writer's stage/commit sub-phases
        assert snap["ckpt.save_s"]["count"] == 1
        assert snap["ckpt.stage_s"]["count"] == 1
        assert snap["ckpt.commit_s"]["count"] == 1
        assert snap["ckpt.saves"] == 1
        names = tel.flight.event_names()
        assert names.index("ckpt.stage") < names.index("ckpt.commit") \
            < names.index("ckpt.save")
        assert mgr.restore() == 1
        snap = tel.snapshot()
        assert snap["ckpt.restore_s"]["count"] == 1
        assert snap["ckpt.restores"] == 1
        # the flight record says WHICH snapshot was loaded
        restored = [e for e in tel.flight.events()
                    if e["event"] == "ckpt.restored"]
        assert len(restored) == 1 and restored[0]["step"] == 1

    def test_torn_snapshot_rejection_records_flight_event(self, tmp_path):
        """Satellite: a snapshot that fails manifest verification during
        discovery leaves a torn_snapshot flight event (with fault
        context), and an injected ckpt.write crash closes the save span
        with ok=False."""
        from paddle_tpu.resilience import InjectedFault
        tel = TrainTelemetry()
        mgr, _ = self._mgr(tmp_path, tel)
        mgr.save(1)
        mgr.save(2)
        # bit-flip the newest snapshot's payload: committed but corrupt
        data = next((tmp_path / "step_00000002").glob("*.data"))
        with open(data, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        best = mgr.find_latest_complete()
        assert best.endswith("step_00000001")
        torn = [e for e in tel.flight.events()
                if e["event"] == "torn_snapshot"]
        assert len(torn) == 1
        assert "step_00000002" in torn[0]["path"]
        assert torn[0]["fault_plan"] is None      # no plan active here
        assert tel.registry.snapshot()["ckpt.torn_snapshots"] == 1
        # injected writer crash (the existing ckpt.write fault point):
        # the save span still closes, marked not-ok, and no save counts
        with inject({"ckpt.write": dict(action="raise")}):
            with pytest.raises(InjectedFault):
                mgr.save(3)
        bad = [e for e in tel.flight.events()
               if e["event"] == "ckpt.save" and not e["ok"]]
        assert len(bad) == 1 and bad[0]["step"] == 3
        assert tel.registry.snapshot()["ckpt.saves"] == 2   # unchanged
        # discovery with a fault plan active stamps it on the rejection
        with inject({"ckpt.commit": dict(action="raise", at=99)}, seed=11):
            mgr.find_latest_complete()
        torn2 = [e for e in tel.flight.events()
                 if e["event"] == "torn_snapshot"][-1]
        assert torn2["fault_plan"] is not None
        assert torn2["fault_plan"]["seed"] == 11


class TestReviewHardening:
    def test_batch_samples_handles_0d_and_unknowable(self):
        from paddle_tpu.observability.train import batch_samples
        assert batch_samples([np.zeros((4, 8))]) == 4
        assert batch_samples(np.zeros((3, 2))) == 3
        assert batch_samples([np.float32(1.0)]) == 0     # 0-d: no crash
        assert batch_samples([]) == 0
        assert batch_samples("notanarray") == 0
        # TrainStep telemetry-on must survive a 0-d batch arg exactly like
        # telemetry-off does (numerics/behavior untouched either way)
        tel = TrainTelemetry()
        from paddle_tpu.parallel.train_step import compile_train_step
        paddle.seed(13)
        net = nn.Linear(8, 4)
        opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        ts = compile_train_step(
            net, opt, lambda m, s, x: (m(x) * s).mean(), telemetry=tel)
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(
            np.float32)
        ts(np.float32(2.0), x)                           # 0-d first arg
        assert tel.report()["steps"] == 1

    def test_report_is_window_scoped_after_reset(self):
        """steps/samples/throughput must describe the window the
        histograms hold, not the cumulative counters (an 11x-wrong
        tokens/s otherwise); lifetime totals ride along separately."""
        tel = TrainTelemetry()
        for _ in range(100):
            tel.step(0.01, samples=4)
        tel.reset_window()
        for _ in range(10):
            tel.step(0.02, samples=4)
        rep = tel.report(window_s=1.0)
        assert rep["steps"] == 10 and rep["samples"] == 40
        assert rep["total_steps"] == 110 and rep["total_samples"] == 440
        assert rep["steps_per_sec"] == pytest.approx(10.0)
        assert rep["samples_per_sec"] == pytest.approx(40.0)
        assert rep["step_s"]["count"] == 10              # internally agrees

    def test_scaler_backoff_counts_decays_not_notifications(self):
        """decr_every_n_nan_or_inf=2: one bad step notifies the scaler but
        does NOT decay the scale — the backoff counter must stay 0."""
        from paddle_tpu.parallel.train_step import compile_train_step
        scaler = paddle.amp.GradScaler(enable=True,
                                       init_loss_scaling=1024.0,
                                       decr_every_n_nan_or_inf=2)
        tel = TrainTelemetry()
        paddle.seed(13)
        net = nn.Linear(8, 4)
        opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        ts = compile_train_step(net, opt, lambda m, x: m(x).mean(),
                                nonfinite_guard=5, scaler=scaler,
                                telemetry=tel)
        x = np.random.default_rng(0).standard_normal((4, 8)).astype(
            np.float32)
        with inject({"train.nonfinite": dict(action="trigger", at=1)}):
            for _ in range(3):
                ts(x)
        assert scaler._scale == 1024.0            # no decay happened
        assert tel.registry.snapshot()["train.scaler_backoffs"] == 0
        # two consecutive bad steps DO decay once -> one backoff counted
        with inject({"train.nonfinite": dict(action="trigger", after=0,
                                             count=2)}):
            for _ in range(2):
                ts(x)
        assert scaler._scale == 512.0
        assert tel.registry.snapshot()["train.scaler_backoffs"] == 1

    def test_async_save_failure_is_on_the_record(self, tmp_path):
        """An async writer that dies must not remain a 'clean save': the
        next wait() records ckpt.async_save_failed before re-raising."""
        from paddle_tpu.resilience import CheckpointManager, InjectedFault
        tel = TrainTelemetry()
        paddle.seed(3)
        net = nn.Linear(6, 3)
        mgr = CheckpointManager(str(tmp_path), model=net, telemetry=tel)
        with inject({"ckpt.write": dict(match={"file": "rank0.data"},
                                        at=0)}):
            mgr.save(1, async_save=True)    # launches; writer dies in bg
            with pytest.raises(InjectedFault):
                mgr.wait()
        names = tel.flight.event_names()
        assert "ckpt.async_save_failed" in names
        assert tel.registry.snapshot()["ckpt.async_save_failures"] == 1
        # the launching span closed ok=True by design (documented): the
        # failure record is the wait-time event, not a rewritten span
        launch = [e for e in tel.flight.events()
                  if e["event"] == "ckpt.save"]
        assert launch and launch[0]["async_save"] is True
