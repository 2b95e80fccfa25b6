"""Cross-process fleet drills (ISSUE 17 tentpole).

Real worker processes under real signals: SIGKILL mid-decode (crash),
SIGSTOP (wedge — heartbeat timeouts, then supervisor SIGKILL), SIGTERM
(zero-loss drain ladder), plus the retire ladder and the cross-process
leak guard.  Acceptance: zero requests lost, greedy outputs bit-equal
the uninterrupted single engine built from the same spec, and every
spawned worker generation files a passing invariants report.

Everything here spawns interpreters (jit warmup per process) — slow
lane; `make proc-smoke` carries the CI drill.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax compat shims
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.serving.procfleet import ProcessFleet
from paddle_tpu.serving.worker import build_from_spec

pytestmark = pytest.mark.slow   # every test spawns worker processes

SPEC = {
    "seed": 2024,
    "model": {"config": dict(vocab_size=64, hidden_size=32,
                             intermediate_size=96, num_hidden_layers=2,
                             num_attention_heads=4, num_key_value_heads=4,
                             max_position_embeddings=64),
              "prng_key": 1, "n_micro": 1},
    "engine": dict(num_slots=2, page_size=4, num_pages=40,
                   max_pages_per_seq=16, attention_impl="ref",
                   prompt_bucket=8, decode_horizon=2),
}
N_NEW = 12
rng = np.random.default_rng(7)
PROMPTS = [rng.integers(1, 64, (t,)).astype(np.int32)
           for t in (5, 7, 3, 6, 4, 6)]
_REF = None


def _refs():
    """Uninterrupted single-engine outputs from the same spec — the
    bit-equality bar for every drill."""
    global _REF
    if _REF is None:
        params, cfg, ekw = build_from_spec(SPEC)
        eng = ServingEngine(params, cfg, **ekw)
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=N_NEW)
        _REF = {i: list(r.generated)
                for i, r in sorted(eng.run().items())}
        eng.release_cache()
    return _REF


def _fleet(tmp_path, **kw):
    kw.setdefault("num_workers", 2)
    kw.setdefault("heartbeat_timeout", 2.0)
    kw.setdefault("snapshot_every", 3)
    return ProcessFleet(SPEC, workdir=str(tmp_path / "fleet"), **kw)


def _check_bitexact(frids, results):
    """`frids` in PROMPTS order (cycling past its end)."""
    ref = _refs()
    assert len(results) == len(frids), "request lost"
    for i, f in enumerate(frids):
        assert list(results[f].generated) == ref[i % len(PROMPTS)], \
            f"request {i} diverged"


def _sigkill_busy_worker(fl, submit):
    """The crash of the SIGKILL drills: let 8 tokens stream, place two
    more requests through `submit(prompt) -> frid` and SIGKILL a worker
    that holds one of them, before the supervisor's next round.  What the
    victim holds is then unfinished by the supervisor's own record,
    whatever the worker's pace: a worker can finish a whole wave inside
    one supervisor round (the round waits on the worker's lock through
    its compiles and snapshots), and a kill that finds nothing assigned
    fails nothing over.  Returns (the two frids, the victim)."""
    while fl.tokens_streamed < 8:
        fl.step()
    late = [submit(p) for p in PROMPTS[:2]]
    victim = next(w for w in fl._workers if fl._assigned[w.name])
    os.kill(victim.pid, signal.SIGKILL)           # real crash mid-decode
    return late, victim


class TestRoundTrip:
    def test_bitexact_and_clean_teardown(self, tmp_path):
        fl = _fleet(tmp_path)
        frids = [fl.submit(p, max_new_tokens=N_NEW) for p in PROMPTS]
        res = fl.run()
        _check_bitexact(frids, res)
        st = fl.stats()
        assert st["workers_alive"] == 2 and st["failovers"] == 0
        assert st["rpc"]["calls"] > 0
        assert st["spawns"] == 2
        fl.shutdown()
        fl.assert_worker_invariants()
        # both generations filed direct teardown reports
        assert set(fl.final_reports) == {"w0#0", "w1#0"}
        assert all(r["invariants_ok"] for r in fl.final_reports.values())

    def test_leak_guard_requires_shutdown(self, tmp_path):
        fl = _fleet(tmp_path, num_workers=1)
        with pytest.raises(AssertionError, match="never shut down"):
            fl.assert_worker_invariants()
        fl.shutdown()
        fl.assert_worker_invariants()


class TestSigkillFailover:
    def test_zero_loss_bitexact_and_stream_once(self, tmp_path):
        fl = _fleet(tmp_path)
        streams: dict[int, list] = {}

        def submit(p):
            acc: list = []
            frid = fl.submit(p, max_new_tokens=N_NEW, on_token=acc.append)
            streams[frid] = acc
            return frid

        frids = [submit(p) for p in PROMPTS]
        late, victim = _sigkill_busy_worker(fl, submit)
        dead_key = victim.key()
        frids += late
        res = fl.run()
        _check_bitexact(frids, res)
        st = fl.stats()
        assert st["failovers"] == 1
        assert st["worker_restarts"][victim.name] == 1
        assert st["recovery"]["count"] == 1
        assert st["recovery"]["p50_ms"] > 0.0     # wall-clock, not virtual
        # the fleet-level hook fired exactly once per position even though
        # the replacement re-decoded tokens the router already streamed
        for i, f in enumerate(frids):
            assert streams[f] == _refs()[i % len(PROMPTS)], \
                "double-streamed token"
        fl.shutdown()
        fl.assert_worker_invariants()
        # the killed generation is vouched for by its replacement
        assert fl.final_reports[dead_key]["via"] == "replacement_restore"
        assert fl.final_reports[dead_key]["invariants_ok"] is True

    def test_stitched_trace_crosses_process_boundary(self, tmp_path):
        fl = _fleet(tmp_path, trace_every=2)
        frids = [fl.submit(p, max_new_tokens=N_NEW) for p in PROMPTS]
        late, _ = _sigkill_busy_worker(
            fl, lambda p: fl.submit(p, max_new_tokens=N_NEW))
        frids += late
        res = fl.run()
        _check_bitexact(frids, res)
        summary = fl.stitcher().summary()
        # supervisor track + at least one worker-process track in a
        # single flow chain: the trace_id crossed the wire
        assert len(summary["max_chain"]) >= 2, summary
        comps = [n for n, _ in fl.trace_components()]
        assert "supervisor" in comps and len(comps) >= 2
        fl.shutdown()
        fl.assert_worker_invariants()


class TestSigstopWedge:
    def test_wedged_worker_is_killed_and_failed_over(self, tmp_path):
        fl = _fleet(tmp_path, heartbeat_timeout=0.5, wedge_heartbeats=2)
        frids = [fl.submit(p, max_new_tokens=N_NEW) for p in PROMPTS]
        while fl.tokens_streamed < 8:
            fl.step()
        victim = fl._workers[1]
        os.kill(victim.pid, signal.SIGSTOP)       # wedged, not dead
        res = fl.run()
        _check_bitexact(frids, res)
        kinds = [e["kind"] for e in fl.flight.events()
                 if e["event"] == "failover"]
        assert kinds == ["wedge"]
        assert fl.stats()["worker_restarts"]["w1"] == 1
        fl.shutdown()
        fl.assert_worker_invariants()


class TestDrainLadders:
    def test_retire_worker_migrates_streams(self, tmp_path):
        fl = _fleet(tmp_path)
        frids = [fl.submit(p, max_new_tokens=N_NEW) for p in PROMPTS]
        while fl.tokens_streamed < 4:
            fl.step()
        fl.retire_worker("w0")
        assert fl.final_reports["w0#0"]["kind"] == "retired"
        assert fl.final_reports["w0#0"]["invariants_ok"] is True
        res = fl.run()
        _check_bitexact(frids, res)
        assert fl.stats()["workers_alive"] == 1
        fl.shutdown()
        fl.assert_worker_invariants()

    def test_sigterm_drains_then_stops(self, tmp_path):
        fl = _fleet(tmp_path)
        frids = [fl.submit(p, max_new_tokens=N_NEW) for p in PROMPTS[:4]]
        threading.Timer(
            0.3, lambda: os.kill(os.getpid(), signal.SIGTERM)).start()
        fl.run()
        deadline = time.monotonic() + 60
        while not fl.closed and time.monotonic() < deadline:
            fl.run()
            time.sleep(0.05)
        assert fl.closed, "SIGTERM did not drain-shutdown the fleet"
        _check_bitexact(frids, fl.results())
        fl.assert_worker_invariants()
