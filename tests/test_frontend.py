"""Async serving front end (ISSUE 11 tentpole).

Acceptance bar: greedy outputs served through AsyncFrontend — streaming
on, concurrent clients, mid-trace cancels — are BIT-EQUAL per request to
direct ``ServingEngine.submit()``; abandoned/cancelled requests leave
zero leaked pages (the conftest leak guard re-checks every engine);
backpressure stalls only the slow client's drain fan-out, never the
engine; SLO-aware admission rejects on PREDICTED TTFT with the typed
``SLORejected`` and tracks its own prediction error."""
import asyncio

import numpy as np
import pytest
import jax

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.inference.paged import AdmissionRejected, ServingEngine
from paddle_tpu.models.llama import (build_functional_llama,
                                     llama_config_tiny, llama_generate)
from paddle_tpu.observability import Telemetry
from paddle_tpu.serving import (AdmissionController, AsyncFrontend,
                                ReplicaFleet, SLORejected, admission_view,
                                make_scenario, replay_engine)

rng = np.random.default_rng(41)

CFG = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=128)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        ep, bp, hp, *_ = build_functional_llama(CFG,
                                                key=jax.random.PRNGKey(4))
        _PARAMS = (ep, bp, hp)
    return _PARAMS


# one prompt bucket (lengths <= prompt_bucket=8): one dense-prefill
# executable per engine — tier-1 is compile-dominated on CPU
_PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32)
            for t in (5, 7, 3, 6)]
_NEWS = [10, 7, 12, 9]
_REFS = None


def _mk(**kw):
    base = dict(num_slots=2, page_size=4, num_pages=200,
                max_pages_per_seq=16, attention_impl="ref",
                prompt_bucket=8, decode_horizon=3)
    base.update(kw)
    return ServingEngine(_params(), CFG, **base)


def _refs():
    global _REFS
    if _REFS is None:
        _REFS = [list(np.asarray(
            llama_generate(_params(), CFG, p[None], max_new_tokens=n)
        )[0][len(p):]) for p, n in zip(_PROMPTS, _NEWS)]
    return _REFS


def _leakfree(eng):
    eng.release_cache()
    assert eng.pool.num_free == eng.pool.num_pages, \
        f"leaked pages: {eng.pool.num_pages - eng.pool.num_free}"
    eng.check_invariants()


class TestAsyncTransport:
    @pytest.mark.parametrize("overlap", [False, True])
    def test_concurrent_streams_bit_equal(self, overlap):
        """N concurrent clients stream through the frontend; every token
        sequence equals the direct-submit reference bit-for-bit, and the
        streamed order equals the final Request record."""
        eng = _mk(overlap=overlap)

        async def main():
            async with AsyncFrontend(eng) as fe:
                async def client(i):
                    s = await fe.submit(_PROMPTS[i],
                                        max_new_tokens=_NEWS[i])
                    toks = [t async for t in s]
                    req = await s.result()
                    return toks, list(req.generated)
                outs = await asyncio.gather(
                    *[client(i) for i in range(len(_PROMPTS))])
                await fe.drain()
            return outs

        outs = asyncio.run(main())
        for i, (toks, gen) in enumerate(outs):
            assert toks == gen == _refs()[i]
        _leakfree(eng)

    def test_backpressure_stalls_fanout_not_engine(self):
        """A slow client with a 2-token buffer: the engine retires the
        request at full speed (its feed never blocks), the fan-out stalls
        on the bounded queue, and the client still sees every token in
        order."""
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng, stream_buffer=2) as fe:
                s = await fe.submit(_PROMPTS[2], max_new_tokens=_NEWS[2])
                # the engine finishes long before the client drains
                req = await s.result()
                assert req is not None and req.finish_time
                backlog = len(s._overflow) + s._q.qsize()
                assert backlog >= len(req.generated)  # buffered, not lost
                toks = []
                async for t in s:
                    await asyncio.sleep(0.002)        # slow consumer
                    toks.append(t)
                return toks, list(req.generated)

        toks, gen = asyncio.run(main())
        assert toks == gen == _refs()[2]
        _leakfree(eng)

    def test_disconnect_cancels_and_frees_pages(self):
        """Mid-decode disconnect (task cancellation inside the iterator)
        propagates to engine.cancel: the request vanishes and its pages
        free."""
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng) as fe:
                s = await fe.submit(_PROMPTS[0], max_new_tokens=48)
                started = asyncio.Event()

                async def consume():
                    async for _ in s:
                        started.set()

                task = asyncio.ensure_future(consume())
                await started.wait()             # first token consumed
                task.cancel()
                with pytest.raises(asyncio.CancelledError):
                    await task
                res = await s.result()
                await fe.drain()
                return res

        res = asyncio.run(main())
        # 48 tokens at horizon 3 cannot finish before the cancel lands
        assert res is None
        _leakfree(eng)

    def test_context_manager_exit_abandons(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng) as fe:
                async with await fe.submit(_PROMPTS[1],
                                           max_new_tokens=48) as s:
                    tok = await s.__anext__()     # stream started
                    assert isinstance(tok, int)
                # exiting the context abandoned the live request
                assert (await s.result()) is None
                await fe.drain()

        asyncio.run(main())
        _leakfree(eng)

    def test_mixed_cancels_leave_survivors_bit_exact(self):
        """Mid-trace cancels must not perturb concurrent survivors; what
        the abandoner did read is a prefix of its reference, and every
        request that retired still decomposes exactly (the abandoned one
        never retires and is left out)."""
        eng = _mk(num_slots=3, telemetry=Telemetry())

        async def main():
            async with AsyncFrontend(eng) as fe:
                async def survivor(i):
                    s = await fe.submit(_PROMPTS[i], max_new_tokens=_NEWS[i])
                    return [t async for t in s]

                async def abandoner():
                    s = await fe.submit(_PROMPTS[3], max_new_tokens=48)
                    got = []
                    async for t in s:
                        got.append(t)
                        if len(got) == 2:
                            s.abandon()
                            break
                    return got

                a, b, ab = await asyncio.gather(
                    survivor(0), survivor(1), abandoner())
                await fe.drain()
                return a, b, ab

        a, b, ab = asyncio.run(main())
        assert a == _refs()[0]
        assert b == _refs()[1]
        assert ab == _refs()[3][:2]
        attr = eng.telemetry.attribution_report()
        assert attr["requests"] >= 2
        assert attr["exact_requests"] == attr["requests"], attr
        _leakfree(eng)

    def test_gc_dropped_stream_cancels(self):
        """Fire-and-forget: a client that submits and silently drops the
        stream (no consumption, no abandon) must not pin a decode slot —
        every frontend-side reference is weak, so GC reaches the
        finalizer and the finalizer cancels the request."""
        import gc
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng) as fe:
                s = await fe.submit(_PROMPTS[0], max_new_tokens=48)
                rid = s.rid
                del s                          # client forgot the stream
                gc.collect()
                # the finalizer enqueued the cancel; give the worker a
                # few polls to process it
                for _ in range(200):
                    if eng.lookup(rid) is None:
                        break
                    await asyncio.sleep(0.005)
                return rid

        rid = asyncio.run(main())
        assert eng.lookup(rid) is None, "GC'd stream did not cancel"
        _leakfree(eng)

    def test_submit_before_start_raises(self):
        eng = _mk()
        fe = AsyncFrontend(eng)
        with pytest.raises(RuntimeError, match="not started"):
            asyncio.run(fe.submit(_PROMPTS[0]))

    def test_restart_after_aclose(self):
        """aclose() then start() yields a LIVE frontend again (regression:
        a stale _stop flag made the restarted worker exit immediately and
        every later submit hang)."""
        eng = _mk()

        async def main():
            fe = AsyncFrontend(eng)
            async with fe:
                s = await fe.submit(_PROMPTS[0], max_new_tokens=4)
                toks1 = [t async for t in s]
            async with fe:                       # restart
                s = await fe.submit(_PROMPTS[1], max_new_tokens=4)
                toks2 = [t async for t in s]
                await fe.drain()
            return toks1, toks2

        toks1, toks2 = asyncio.run(main())
        assert len(toks1) == 4 and len(toks2) == 4
        _leakfree(eng)

    def test_fleet_wrapped_frontend(self):
        """The same transport over a ReplicaFleet: tokens arrive through
        the router-authoritative stream, outputs bit-equal the
        single-engine reference."""
        fleet = ReplicaFleet(lambda: _mk(), num_replicas=2)

        async def main():
            async with AsyncFrontend(fleet) as fe:
                async def client(i):
                    s = await fe.submit(_PROMPTS[i],
                                        max_new_tokens=_NEWS[i])
                    toks = [t async for t in s]
                    req = await s.result()
                    return toks, list(req.generated)
                outs = await asyncio.gather(
                    *[client(i) for i in range(len(_PROMPTS))])
                await fe.drain()
                return outs

        outs = asyncio.run(main())
        for i, (toks, gen) in enumerate(outs):
            assert toks == gen == _refs()[i]

    def test_fleet_frontend_cancel(self):
        fleet = ReplicaFleet(lambda: _mk(), num_replicas=2)

        async def main():
            async with AsyncFrontend(fleet) as fe:
                s = await fe.submit(_PROMPTS[0], max_new_tokens=48)
                while s._q.qsize() == 0 and not s._done.is_set():
                    await asyncio.sleep(0.002)
                s.abandon()
                assert (await s.result()) is None
                await fe.drain()

        asyncio.run(main())
        for rep in fleet._replicas:
            _leakfree(rep.engine)
        assert fleet._requests == {}


class TestSLOAdmission:
    def test_slo_rejected_typed_and_counted(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng, admission="predictive",
                                     slo_ttft_s=1e-9) as fe:
                with pytest.raises(SLORejected):
                    await fe.submit(_PROMPTS[0], max_new_tokens=8)
                return fe.stats()

        rep = asyncio.run(main())
        assert rep["offered"] == 1 and rep["rejected_slo"] == 1
        assert rep["fraction_sum"] == pytest.approx(1.0, abs=1e-3)
        assert issubclass(SLORejected, AdmissionRejected)

    def test_per_request_slo_overrides_default(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng, admission="predictive",
                                     slo_ttft_s=1e-9) as fe:
                # generous per-request deadline overrides the impossible
                # frontend default
                s = await fe.submit(_PROMPTS[0], max_new_tokens=6,
                                    slo_ttft_s=30.0)
                toks = [t async for t in s]
                await fe.drain()
                return toks

        toks = asyncio.run(main())
        assert len(toks) == 6
        _leakfree(eng)

    def test_prediction_error_tracked_through_frontend(self):
        eng = _mk(telemetry=Telemetry())

        async def main():
            async with AsyncFrontend(eng, admission="predictive",
                                     slo_ttft_s=60.0) as fe:
                streams = [await fe.submit(p, max_new_tokens=n)
                           for p, n in zip(_PROMPTS, _NEWS)]
                for s in streams:
                    assert s.predicted_ttft_s is not None
                    assert s.predicted_ttft_s >= 0.0
                await fe.drain()
                return fe.stats()

        rep = asyncio.run(main())
        assert rep["ttft_pred_err_s"]["count"] == len(_PROMPTS)
        assert rep["admitted"] + rep["queued"] == len(_PROMPTS)
        _leakfree(eng)

    def test_admission_view_from_live_engine(self):
        eng = _mk(telemetry=Telemetry())
        eng.submit(_PROMPTS[0], max_new_tokens=8)
        eng.submit(_PROMPTS[1], max_new_tokens=8)
        eng.submit(_PROMPTS[2], max_new_tokens=8)   # 2 slots -> 1 queued
        eng.step()
        v = admission_view(eng)
        assert v.free_slots == 0
        assert len(v.active) == 2
        assert v.queue_depth == 1
        assert v.queued[0][0] == len(_PROMPTS[2])
        eng.run()
        _leakfree(eng)


class TestEngineReplay:
    def test_replay_bit_equal_and_goodput(self):
        """The traffic harness drives a real engine: greedy streams equal
        direct submit, abandons cancel mid-decode, the goodput report and
        admission fractions are complete."""
        sc = make_scenario(
            "bursty", seed=6, n_requests=8, vocab=64, arrival="bursty",
            mean_interarrival_s=0.3, burst_every_s=1.0, burst_size=3,
            prompt_len=(3, 8), max_new=(6, 12), abandon_frac=0.25,
            abandon_range=(2, 4))
        eng = _mk(telemetry=Telemetry())
        eng.submit(_PROMPTS[0], max_new_tokens=8)
        eng.run()                                  # warm
        out = replay_engine(eng, sc,
                            AdmissionController(policy="always"),
                            load_tps=150.0, slo_ttft_s=30.0,
                            collect_tokens=True)
        rep = out["report"]
        assert rep["offered_requests"] == 8
        assert rep["rejected_requests"] == 0
        adm = out["admission"]
        assert adm["fraction_sum"] == pytest.approx(1.0, abs=1e-3)
        # bit-equality for every non-abandoned greedy request
        for rec, sr in zip(out["records"], sc.requests):
            if rec["abandoned"] or sr.temperature > 0:
                continue
            ref = np.asarray(llama_generate(
                _params(), CFG, sr.prompt[None],
                max_new_tokens=sr.max_new_tokens))[0][len(sr.prompt):]
            assert rec["stream"] == list(ref)
        _leakfree(eng)

    def test_replay_depth_policy_rejects(self):
        sc = make_scenario(
            "burst", seed=9, n_requests=10, vocab=64, arrival="bursty",
            mean_interarrival_s=0.01, burst_every_s=0.05, burst_size=10,
            burst_spread_s=0.01, prompt_len=(3, 8), max_new=(6, 10))
        eng = _mk()
        ctrl = AdmissionController(policy="depth", max_queue_depth=2)
        out = replay_engine(eng, sc, ctrl, load_tps=2.0, slo_ttft_s=30.0)
        assert out["admission"]["rejected_depth"] > 0
        assert out["report"]["rejected_requests"] \
            == out["admission"]["rejected_depth"]
        _leakfree(eng)


# ---------------------------------------------------------------------------
# ISSUE 12: live exporter attach + end-to-end trace stitching
# ---------------------------------------------------------------------------
class TestFrontendObservabilityPlane:
    def test_exporter_stitching_and_freeze_through_frontend(self):
        """One engine-backed drill for the whole plane: AsyncFrontend
        mints a trace_id per submit (stitchable through the engine
        tracer), start_exporter() serves labeled live metrics over HTTP
        from a non-engine thread, and the component registries come back
        FROZEN (pre-registration makes the worker thread safe)."""
        import json
        import urllib.request
        from paddle_tpu.observability import TraceStitcher

        eng = _mk(telemetry=Telemetry())

        async def main():
            async with AsyncFrontend(eng) as fe:
                ex = fe.start_exporter()         # port=0: pick a free port
                streams = [await fe.submit(_PROMPTS[i],
                                           max_new_tokens=_NEWS[i])
                           for i in range(2)]
                outs = []
                for s in streams:
                    outs.append([t async for t in s])
                await fe.drain()
                # registries frozen by the exporter attach
                assert fe.controller.metrics.frozen
                assert eng.telemetry.registry.frozen
                body = urllib.request.urlopen(
                    f"{ex.url}/metrics").read().decode()
                js = json.loads(urllib.request.urlopen(
                    f"{ex.url}/metrics.json").read().decode())
                hz = json.loads(urllib.request.urlopen(
                    f"{ex.url}/healthz").read().decode())
                return streams, outs, body, js, hz

        streams, outs, body, js, hz = asyncio.run(main())
        for i, got in enumerate(outs):
            assert got == _refs()[i]
        # live scrape saw both components, labeled
        assert 'component="frontend"' in body \
            and 'component="engine"' in body
        assert "serve_ttft_s_bucket" in body
        assert js["frontend"]["frontend.offered"]["value"] == 2
        assert js["engine"]["serve.requests_submitted"]["value"] == 2
        assert hz["status"] == "ok" and hz["open_streams"] == 0
        # exporter is torn down with the frontend (aclose)
        # trace stitching: frontend span -> engine span per request
        tids = [s.trace_id for s in streams]
        assert all(isinstance(t, int) for t in tids) \
            and len(set(tids)) == 2
        st = (TraceStitcher().add("frontend", _frontend_tracer(streams))
              .add("engine", eng.telemetry.tracer))
        summ = st.summary()
        assert summ["requests_stitched"] == 2
        assert summ["max_chain"] == ["frontend", "engine"]
        chains = st.flow_chains()
        assert set(chains) == set(tids)
        _leakfree(eng)


def _frontend_tracer(streams):
    """The frontend tracer behind the streams' frontend instance."""
    return streams[0]._fe.tracer


# ---------------------------------------------------------------------------
# HTTP/SSE streaming endpoint (ISSUE 14 satellite: the real socket
# transport leftover from ROADMAP item 4)
# ---------------------------------------------------------------------------
class TestSSEGenerate:
    """``POST /generate`` on the exporter server -> SSE token stream over
    AsyncFrontend; a client disconnect mid-stream lands in the existing
    cancel path (pages freed, zero leaks — conftest re-checks)."""

    @staticmethod
    def _post(port, body, read_n=None, timeout=30):
        import http.client
        import json as _json
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        conn.request("POST", "/generate", _json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        events, toks = [], []
        if read_n is None:
            for raw in resp.fp:
                line = raw.decode().strip()
                if line.startswith("event: "):
                    events.append(line[7:])
                elif line.startswith("data: "):
                    d = _json.loads(line[6:])
                    if "token" in d:
                        toks.append(d["token"])
            conn.close()
        else:
            while len(toks) < read_n:
                line = resp.fp.readline().decode().strip()
                if line.startswith("data: "):
                    d = _json.loads(line[6:])
                    if "token" in d:
                        toks.append(d["token"])
            conn.close()               # disconnect mid-stream
        return resp.status, events, toks

    def test_loopback_stream_bit_equal(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng) as fe:
                ex = fe.start_exporter()
                status, events, toks = await asyncio.to_thread(
                    self._post, ex.port,
                    {"prompt": _PROMPTS[0].tolist(),
                     "max_new_tokens": _NEWS[0]})
                return status, events, toks

        status, events, toks = asyncio.run(main())
        assert status == 200
        assert events[0] == "start" and events[-1] == "done"
        assert toks == _refs()[0]

    def test_disconnect_triggers_cancel(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng) as fe:
                ex = fe.start_exporter()
                _status, _ev, toks = await asyncio.to_thread(
                    self._post, ex.port,
                    {"prompt": _PROMPTS[0].tolist(),
                     "max_new_tokens": _NEWS[0]}, 2)
                # the broken pipe surfaces at the NEXT write; give the
                # generator a beat to observe it and abandon the stream
                for _ in range(100):
                    await asyncio.sleep(0.02)
                    if not eng.num_active and not eng._queue \
                            and not eng.inflight_depth:
                        break
                await fe.drain()
                return toks

        toks = asyncio.run(main())
        assert toks == _refs()[0][:2]       # a prefix, then disconnect
        assert eng.num_active == 0 and not eng._queue
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages   # zero leaks
        eng.check_invariants()

    def test_rejection_and_bad_request(self):
        eng = _mk()

        async def main():
            async with AsyncFrontend(eng, admission="predictive",
                                     slo_ttft_s=1e-9) as fe:
                ex = fe.start_exporter()
                # impossible SLO -> typed SSE rejection event
                s1, ev1, toks1 = await asyncio.to_thread(
                    self._post, ex.port,
                    {"prompt": _PROMPTS[0].tolist(),
                     "max_new_tokens": 4})
                # malformed body -> error event, engine untouched
                s2, ev2, _ = await asyncio.to_thread(
                    self._post, ex.port, {"max_new_tokens": 4})
                return (s1, ev1, toks1), (s2, ev2)

        (s1, ev1, toks1), (s2, ev2) = asyncio.run(main())
        assert s1 == 200 and ev1 == ["rejected"] and toks1 == []
        assert s2 == 200 and ev2 == ["error"]
        assert eng.num_active == 0 and not eng._queue

    def test_post_without_generate_fn_404(self):
        from paddle_tpu.observability import MetricsExporter
        ex = MetricsExporter(lambda: {"at": 0.0}).start()
        try:
            status, _ev, _toks = self._post(ex.port, {"prompt": [1]})
            assert status == 404
        finally:
            ex.stop()
