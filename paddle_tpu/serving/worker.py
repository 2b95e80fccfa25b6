"""Worker process entrypoint for the cross-process fleet (ISSUE 17).

``python -m paddle_tpu.serving.worker --name r0 --spec spec.json
--portfile /tmp/r0.port [--snapshot-root DIR --snapshot-every N]`` hosts
one full :class:`~paddle_tpu.inference.paged.ServingEngine` behind the
length-prefixed loopback RPC of :mod:`paddle_tpu.serving.rpc` and speaks
the fleet wire protocol:

=============  ============================================================
``hello``      identity + boot-restore report: pid, restored snapshot
               path/mode, the live rids the restore reinstated, and the
               post-restore ``check_invariants()`` verdict (the supervisor
               relays this into the conftest cross-process leak guard for
               workers that died mid-drill and can no longer answer)
``submit``     queue one request -> rid
``adopt``      queue with already-emitted tokens (migration re-prefill
               path; also the supervisor's unified placement primitive)
``poll``       incremental token stream: ``{rid: have_n}`` -> new tokens
               past ``have_n`` per rid + finished/timed-out flags — the
               supervisor's record only ever EXTENDS, so a retried poll
               (idempotency cache) can never double-stream a token
``cancel``     drop a request wherever it lives (KV parks in prefix cache)
``health``     heartbeat seq + step count + load + engine ``stats()`` +
               live invariants verdict (PagePool refcounts / page tables /
               cache accounting), every call — the leak guard's wire
``snapshot``   force one crash-consistent EngineSnapshotManager snapshot
``drain``      stop admitting, cancel all live work (zero-loss ladder:
               the supervisor has already adopted the streams elsewhere)
``trace``      the engine Tracer in wire form (stitched cross-process
               spans; worker telemetry runs on ``time.time`` so the
               supervisor's clock domain matches)
``stats``      engine ``stats()``
``stop``       final teardown report (release_cache + check_invariants),
               then process exit 0
=============  ============================================================

Determinism: the spec carries the model config + a PRNG key integer, and
the worker rebuilds params via ``build_functional_llama(cfg,
key=PRNGKey(k))`` — bit-identical to a supervisor-side reference build,
which is what makes the SIGKILL failover drill's bit-equality bar
meaningful.  A crash inside ``engine.step()`` exits the process non-zero:
the supervisor observes a real death, not an exception.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
import traceback

__all__ = ["build_from_spec", "main", "WORKER_CRASH_EXIT"]

WORKER_CRASH_EXIT = 13      # engine.step raised: distinguishable from OOM-kill


def build_from_spec(spec: dict):
    """(params, cfg, engine_kwargs) from a fleet worker spec — shared by
    worker processes and supervisor-side reference builds so both sides
    hold bit-identical weights."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from ..models.llama import LlamaConfig, build_functional_llama

    model = spec["model"]
    paddle.seed(int(spec.get("seed", 2024)))
    cfg = LlamaConfig(**model["config"])
    dtype = None if not model.get("dtype") else jnp.dtype(model["dtype"])
    ep, bp, hp, *_ = build_functional_llama(
        cfg, key=jax.random.PRNGKey(int(model.get("prng_key", 0))),
        dtype=dtype, n_micro=int(model.get("n_micro", 1)))
    return (ep, bp, hp), cfg, dict(spec.get("engine", {}))


class _WorkerHost:
    """The handler + serve loop around one engine."""

    def __init__(self, name: str, engine, snapshots=None,
                 snapshot_every: int = 0, snapshot_mode: str = "full_kv"):
        self.name = name
        self.engine = engine
        self.snapshots = snapshots
        self.snapshot_every = int(snapshot_every)
        self.snapshot_mode = snapshot_mode
        self.lock = threading.RLock()
        self.stop_event = threading.Event()
        self.draining = False
        self.hb = 0
        self.steps = 0
        self.restored = None          # (path, mode) | None
        self.restored_rids: list[int] = []
        self.restore_invariants_ok = True
        self.restore_error = ""
        self.final_report: dict | None = None

    # -- engine helpers ----------------------------------------------------
    def _live_rids(self) -> list[int]:
        eng = self.engine
        rids = [r.rid for r in eng._queue]
        rids += [sl.req.rid for sl in eng._slots if sl is not None]
        rids += list(eng._finished)
        return sorted(int(r) for r in set(rids))

    def _invariants(self) -> tuple[bool, str]:
        try:
            self.engine.check_invariants()
            return True, ""
        except AssertionError as e:
            return False, str(e)

    def boot_restore(self):
        if self.snapshots is None:
            return
        with self.lock:
            got = self.snapshots.restore_engine(self.engine)
            if got is not None:
                self.restored = (str(got[0]), got[1])
                self.restored_rids = self._live_rids()
            ok, err = self._invariants()
            self.restore_invariants_ok = ok
            self.restore_error = err

    def _maybe_snapshot(self):
        if self.snapshots is None or self.snapshot_every <= 0:
            return
        if self.steps % self.snapshot_every:
            return
        try:
            self.snapshots.save_engine(self.engine, mode=self.snapshot_mode)
        except Exception:
            # durability is best-effort from inside the worker; a failed
            # snapshot must not take down live decode
            traceback.print_exc()

    # the serve thread owns the engine and the hb counter behind self.lock;
    # the RPC handler threads only touch them under the same lock
    def serve_loop(self):  # graftlint: owner=worker
        eng = self.engine
        while not self.stop_event.is_set():
            did = False
            with self.lock:
                self.hb += 1
                if eng._queue or eng.num_active or eng._inflight is not None:
                    try:
                        eng.step()
                    except BaseException:
                        traceback.print_exc()
                        os._exit(WORKER_CRASH_EXIT)
                    self.steps += 1
                    did = True
                    self._maybe_snapshot()
            if not did:
                self.stop_event.wait(0.002)

    # -- RPC handler -------------------------------------------------------
    def handle(self, method: str, p: dict):
        import numpy as np
        eng = self.engine
        if method == "hello":
            import jax
            return {"name": self.name, "pid": os.getpid(),
                    "platform": jax.default_backend(),
                    "restored": self.restored is not None,
                    "restored_path": None if self.restored is None
                    else self.restored[0],
                    "restored_mode": None if self.restored is None
                    else self.restored[1],
                    "restored_rids": self.restored_rids,
                    "restore_invariants_ok": self.restore_invariants_ok,
                    "restore_error": self.restore_error}
        if method == "submit":
            if self.draining:
                raise RuntimeError("worker draining: admission closed")
            with self.lock:
                return int(eng.submit(
                    np.asarray(p["prompt"], np.int32),
                    max_new_tokens=int(p.get("max_new_tokens", 32)),
                    temperature=float(p.get("temperature", 0.0)),
                    top_p=float(p.get("top_p", 1.0)),
                    eos_token_id=p.get("eos_token_id"),
                    timeout=p.get("timeout"),
                    trace_id=p.get("trace_id")))
        if method == "adopt":
            if self.draining:
                raise RuntimeError("worker draining: admission closed")
            with self.lock:
                return int(eng.adopt(
                    np.asarray(p["prompt"], np.int32),
                    generated=tuple(int(t) for t in p.get("generated", ())),
                    max_new_tokens=int(p.get("max_new_tokens", 32)),
                    temperature=float(p.get("temperature", 0.0)),
                    top_p=float(p.get("top_p", 1.0)),
                    eos_token_id=p.get("eos_token_id"),
                    deadline=p.get("deadline"),
                    trace_id=p.get("trace_id")))
        if method == "poll":
            out = {}
            with self.lock:
                for rid_s, have in (p.get("have") or {}).items():
                    r = eng.lookup(int(rid_s))
                    if r is None:
                        out[rid_s] = None
                        continue
                    gen = r.generated
                    out[rid_s] = {
                        "new": [int(t) for t in gen[int(have):]],
                        "done": r.finish_time > 0.0,
                        "timed_out": bool(r.timed_out),
                        "n": len(gen)}
                load = {"active": int(eng.num_active),
                        "queued": len(eng._queue)}
            return {"rids": out, "hb": self.hb, "load": load}
        if method == "cancel":
            with self.lock:
                return bool(eng.cancel(int(p["rid"])))
        if method == "health":
            with self.lock:
                ok, err = self._invariants()
                return {"hb": self.hb, "steps": self.steps,
                        "pid": os.getpid(),
                        "load": {"active": int(eng.num_active),
                                 "queued": len(eng._queue)},
                        "draining": self.draining,
                        "invariants_ok": ok, "invariants_error": err,
                        "stats": {k: (float(v) if isinstance(v, float)
                                      else int(v))
                                  for k, v in eng.stats().items()
                                  if isinstance(v, (int, float))}}
        if method == "snapshot":
            if self.snapshots is None:
                raise RuntimeError("worker has no snapshot root")
            with self.lock:
                path = self.snapshots.save_engine(
                    eng, mode=p.get("mode") or self.snapshot_mode)
            return {"path": str(path)}
        if method == "drain":
            with self.lock:
                self.draining = True
                live = [r for r in self._live_rids()
                        if r not in eng._finished]
                for rid in live:
                    eng.cancel(rid)
                ok, err = self._invariants()
            return {"cancelled": live, "invariants_ok": ok,
                    "invariants_error": err}
        if method == "trace":
            from ..observability.tracing import tracer_to_wire
            with self.lock:
                if eng.telemetry is None:
                    return {"requests": [], "engine": [], "counters": []}
                return tracer_to_wire(eng.telemetry.tracer)
        if method == "stats":
            with self.lock:
                return {k: (v if isinstance(v, (int, float, str, bool))
                            else str(v)) for k, v in eng.stats().items()}
        if method == "stop":
            with self.lock:
                self.draining = True
                try:
                    eng.release_cache()
                except Exception as e:   # release must not mask the report
                    return self._finalize(False, f"release_cache: {e}")
                ok, err = self._invariants()
            return self._finalize(ok, err)
        raise RuntimeError(f"unknown rpc method {method!r}")

    def _finalize(self, ok: bool, err: str) -> dict:
        self.final_report = {"invariants_ok": bool(ok),
                             "invariants_error": err, "name": self.name}
        self.stop_event.set()
        return self.final_report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="paddle_tpu.serving.worker")
    ap.add_argument("--name", required=True)
    ap.add_argument("--spec", required=True,
                    help="JSON file: {model, engine, seed, snapshot}")
    ap.add_argument("--portfile", required=True,
                    help="written atomically with the bound port")
    ap.add_argument("--port", type=int, default=0,
                    help="bind this port (0 = ephemeral); the supervisor "
                         "pre-assigns via the elastic-launch _free_port")
    ap.add_argument("--snapshot-root", default=None)
    ap.add_argument("--snapshot-every", type=int, default=0)
    ap.add_argument("--snapshot-mode", default="full_kv")
    args = ap.parse_args(argv)

    with open(args.spec) as f:
        spec = json.load(f)

    # Heavy imports AFTER argparse so --help stays fast.
    import time as _time  # noqa: F401 — clock domain note below

    from ..core.device import setup_compile_cache
    from ..inference.paged import ServingEngine
    from ..observability.telemetry import Telemetry
    from .rpc import RpcServer
    from .snapshot import EngineSnapshotManager

    setup_compile_cache()
    params, cfg, engine_kw = build_from_spec(spec)
    # One clock domain fleet-wide: the supervisor stitches worker spans
    # with its own, so both must stamp wall-clock time.time.
    telemetry = Telemetry(clock=time.time)
    engine = ServingEngine(params, cfg, telemetry=telemetry, **engine_kw)

    snaps = None
    if args.snapshot_root:
        os.makedirs(args.snapshot_root, exist_ok=True)
        snaps = EngineSnapshotManager(
            args.snapshot_root,
            keep_last=int(spec.get("snapshot", {}).get("keep_last", 2)))
    host = _WorkerHost(args.name, engine, snapshots=snaps,
                       snapshot_every=args.snapshot_every,
                       snapshot_mode=args.snapshot_mode)
    host.boot_restore()

    server = RpcServer(host.handle, port=args.port).start()
    tmp = args.portfile + ".tmp"
    with open(tmp, "w") as f:
        f.write(f"{server.port}\n")
    os.replace(tmp, args.portfile)

    signal.signal(signal.SIGTERM, lambda *_: host.stop_event.set())

    loop = threading.Thread(target=host.serve_loop, name="serve-loop",
                            daemon=True)
    loop.start()
    host.stop_event.wait()
    # Grace so the in-flight `stop` reply flushes before the listener dies.
    time.sleep(0.2)
    server.stop()
    loop.join(timeout=2.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
