"""Serving plane (ROADMAP item 4): durable engine snapshots, the
replica-fleet router, and the async front end + traffic harness.

* :class:`EngineSnapshotManager` — crash-consistent
  ``ServingEngine.snapshot()`` persistence through the checkpoint commit
  protocol (staged tmp + fsync + SHA-256 manifest + atomic rename), with
  keep-last-N rotation and torn-snapshot-skipping discovery.
* :class:`ReplicaFleet` — N engine replicas behind one ``submit()``:
  least-loaded routing, health watchdog (crash + wedge detection),
  snapshot-restore / re-prefill failover with zero request loss and
  greedy-bit-exact outputs, fleet-wide degradation ladder
  (route -> queue -> reject), router-authoritative token streaming
  (``submit(on_token=...)`` survives failover without double emission).
* :class:`AsyncFrontend` — the asyncio transport (ISSUE 11): ``await
  submit()`` returns a bounded async token stream with per-client
  backpressure; client disconnect cancels the request mid-decode; the
  engine steps on one worker thread.  :class:`AdmissionController` /
  :class:`TTFTPredictor` add SLO-aware admission — reject on PREDICTED
  TTFT (typed :class:`SLORejected`) instead of raw queue depth, with the
  prediction error itself tracked (``frontend.ttft_pred_err_s``).
* :mod:`.traffic` — seeded, replayable scenario generators (Poisson
  bursty + diurnal arrivals, shared-prefix user fleets, mixed
  greedy/sampled/long-context, streaming-abandon clients) plus engine,
  fleet, and virtual-clock replays reporting goodput-under-SLO.
* :mod:`.quant` — the quantized serving plane (ROADMAP item 2):
  the one int8/fp8 KV codec (per-page, per-head, per-token-row absmax
  scales — write-order independent, so the quantized engine keeps every
  self-exactness invariant), per-channel int8 serving weights, page-byte
  accounting for the memory observatory, and :func:`parity_report` —
  greedy exact-match + teacher-forced logit drift vs the f32 engine on
  the standard parity scenarios (tests/test_quant.py gates it).
* :mod:`.routing` + :mod:`.autoscale` — the elastic control plane
  (ROADMAP item 5): pluggable placement strategies
  (:class:`LeastLoadedRouter`, :class:`PrefixAffinityRouter` — route
  shared-prefix users to the replica already holding their KV via the
  cache's own chained block-hash, under a bounded-imbalance guard) and
  :class:`ElasticFleet` — sentinel-driven replica autoscaling
  (:class:`AutoscalePolicy` GROW on sustained queue growth / SLO burn,
  SHRINK on sustained idle) with zero-loss, greedy-bit-exact drain
  through the live-migration path.
* Disaggregated prefill/decode (ISSUE 19): ``ReplicaFleet(roles=
  ["prefill", "decode", ...])`` splits the fleet into prefill replicas
  (dense/chunked prefill + first token on their own TP submesh) and
  decode replicas that receive the head-sharded KV pages via
  ``ServingEngine.export_kv``/``import_kv`` — rank-local at equal ``mp``
  degree, scale planes included, with re-prefill fallback on any
  geometry mismatch (:class:`~paddle_tpu.inference.paged.KVHandoffError`)
  and the transfer itself visible as the ``kv_transfer`` attribution
  segment plus fleet counters/histograms.  ``ElasticFleet(role_policies=
  {"prefill": ..., "decode": ...})`` scales each role independently.
* :mod:`.rpc` + :mod:`.worker` + :mod:`.procfleet` — the cross-process
  fleet (ISSUE 17): replicas as real worker processes behind a
  length-prefixed loopback wire (deadline-per-call timeouts,
  exponential backoff with jitter, idempotent retry keys), with
  :class:`ProcessFleet` supervising spawn/reap/failover under real
  ``SIGKILL``/``SIGSTOP`` — same zero-loss, greedy-bit-exact recovery
  bar, now across an actual process boundary.
"""
from ..inference.paged import KVHandoffError
from .autoscale import AutoscaleDecision, AutoscalePolicy, ElasticFleet
from .quant import (dequantize_kv, kv_spec, page_bytes, parity_report,
                    parity_scenarios, quantize_kv, quantize_params)
from .fleet import FleetFailedError, ReplicaFleet
from .frontend import (AdmissionController, AdmissionView, AsyncFrontend,
                       AsyncStream, SLORejected, TTFTPredictor,
                       admission_view)
from .routing import (LeastLoadedRouter, PrefixAffinityRouter, Router,
                      RoutingDecision)
from .procfleet import ProcessFleet, WorkerDiedError
from .rpc import RpcClient, RpcError, RpcRemoteError, RpcServer, RpcTimeout
from .snapshot import EngineSnapshotManager, load_engine_snapshot
from .traffic import (ClientRequest, Scenario, VirtualClock,
                      goodput_report, make_scenario, replay_engine,
                      replay_fleet, replay_sim)

__all__ = ["ReplicaFleet", "FleetFailedError", "EngineSnapshotManager",
           "load_engine_snapshot", "AsyncFrontend", "AsyncStream",
           "SLORejected", "AdmissionController", "AdmissionView",
           "TTFTPredictor", "admission_view", "ClientRequest", "Scenario",
           "make_scenario", "replay_engine", "replay_fleet", "replay_sim",
           "goodput_report", "VirtualClock", "Router", "RoutingDecision",
           "LeastLoadedRouter", "PrefixAffinityRouter", "AutoscalePolicy",
           "AutoscaleDecision", "ElasticFleet", "quantize_kv",
           "dequantize_kv", "kv_spec", "page_bytes", "quantize_params",
           "parity_report", "parity_scenarios", "ProcessFleet",
           "WorkerDiedError", "RpcClient", "RpcServer", "RpcError",
           "RpcTimeout", "RpcRemoteError", "KVHandoffError"]
