#!/usr/bin/env python3
"""One traced run of a serve cell, read through the host spans and the
dispatch-time counters as well — what ``run.py --trace 1`` cannot print yet,
because the harness' own files would have to change for it (PERF.md
section 7 says which):

    python3 benchmark/tools/span_probe.py --workload serve_chat_c16 \
        [--seconds 51] [--seed 1]

Runs the cell exactly as ``run.py --trace 1`` does (its JSON line is printed
as always) and hooks three seams WITHOUT touching the timed path: the trace
loader (to read the ``/host:CPU`` plane before the trace directory is
deleted), ``measure`` (to keep the three ``stats()`` snapshots it takes
anyway) and the driver's ``run`` (to keep the run's context).  Then the LAST
line of stdout is ``{"span_probe": {...}}``:

  ``metrics``      the six per-layer metrics that need a new reader term or
                   new facts (None where the program has no spans/counters)
  ``idle_by_host_span``   device-idle seconds per innermost host span, plus
                   ``holes:serve.step`` and ``outside:serve.step``
  ``idle_s``       their sum against the trace's own idle seconds
  ``clock``        ``host_spans.clock_margins`` of the horizon runs
  ``out_tok_s``    of this (traced) window: the cost of tracing ON
and ``chiprun_out/span_probe.<cell>.seed<seed>.json`` holds the same plus the
first ``serve.*`` events with their stats beside the device's first modules.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import host_spans, run, trace_reduce          # noqa: E402
from benchmark.drivers import serve                          # noqa: E402

HORIZON = r"^jit_decode_horizon\("
PREFILL = r"^jit_(prefill_chunk|_lambda)\("
DECODE_KERNEL = (r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention",'
                 r'\s*"role":"decode"')


def kv_bytes_per_token(model, dtype_bytes):
    """Bytes of K and V one token holds over all layers (belongs in
    ``flops.py``): 2 x KV heads x head dim x bytes x layers."""
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    return (2 * model["num_key_value_heads"] * head_dim * dtype_bytes
            * model["num_hidden_layers"])


def ratio(n, d, scale=1.0):
    return None if n is None or not d else scale * n / d


def report(facts, tr, conf, snaps, host_lines, hbm_bytes_per_s):
    """The part of this tool a ``benchmark`` PR would move into the
    harness: from a run's facts, its ``trace_reduce.Trace``, the three
    ``stats()`` snapshots of ``measure`` (window start, trace start, end)
    and the host lines, the six metrics and the tables.  Pure."""
    s0, st, s1 = snaps
    diff = lambda a, b, k: (a[k] - b[k]) if k in a and k in b else None
    plane = tr.planes[sorted(tr.planes)[0]]
    ops = plane.get(trace_reduce.OPS, [])
    modules = plane.get(trace_reduce.MODULES, [])
    spans = host_spans.engine_line(host_lines)
    lo = min((s[1] for s in spans), default=None)
    hi = max((s[1] + s[2] for s in spans), default=None)
    by = host_spans.idle_by_span(host_spans.idle_intervals(ops, lo, hi),
                                 spans)
    under = host_spans.idle_under(by, host_spans.ROOT)
    steps = facts.get("traced.decode_steps")
    kv_tokens = diff(s1, st, "decode_kv_tokens_attended")
    dispatched = diff(s1, st, "prefill_tokens_dispatched")
    padded = diff(s1, s0, "prefill_tokens_padded")      # the whole window
    kv_bytes = kv_bytes_per_token(conf, 2 if conf["torch_dtype"] in
                                  ("bfloat16", "float16") else 4)
    seconds = lambda line, rx: tr.matching_s(line, rx) or None
    decode_kernel_s = seconds(trace_reduce.OPS, DECODE_KERNEL)
    prefill_s = seconds(trace_reduce.MODULES, PREFILL)
    metrics = {
        "sched.exposed_host_ms_per_dispatch": ratio(under, steps, 1e3),
        "entry.exposed_client_ms_per_dispatch": ratio(
            host_spans.idle_under(by, "outside:serve.step"), steps, 1e3),
        "sched.idle_unattributed_pct": ratio(
            host_spans.idle_under(by, "holes:serve.step"), under, 100),
        # memory-bound side of the roofline: the KV bytes the live decode
        # steps had to read over the decode-role kernel's device seconds
        "kernel.ragged_attn_roofline_pct": ratio(
            None if kv_tokens is None
            else kv_tokens * kv_bytes / hbm_bytes_per_s,
            decode_kernel_s, 100),
        "model.prefill_exec_tok_s": ratio(dispatched, prefill_s),
        "model.prefill_pad_pct": None if not padded else 100 * (
            1 - diff(s1, s0, "prefill_tokens_dispatched") / padded),
    }
    return {
        "out_tok_s": facts.get("out_tok_s"),
        "metrics": metrics,
        "facts": {"traced.decode_steps": steps,
                  "traced.decode_kv_tokens_attended": kv_tokens,
                  "traced.prefill_tokens_dispatched": dispatched,
                  "traced.prefill_tokens": facts.get("traced.prefill_tokens"),
                  "kv_bytes_per_token": kv_bytes,
                  "hbm_bytes_per_s": hbm_bytes_per_s,
                  "decode_kernel_s": decode_kernel_s,
                  "horizon_s": seconds(trace_reduce.MODULES, HORIZON),
                  "prefill_s": prefill_s},
        "idle_by_host_span": host_spans.top(by, 20),
        "idle_s": {"laid_over_spans": sum(by.values()) if by else None,
                   "between_ops": sum(b - a for a, b in
                                      host_spans.idle_intervals(ops)) / 1e9,
                   "trace_idle_s": tr.idle_s(), "window_s": tr.window_s},
        "clock": host_spans.clock_margins(modules, spans, HORIZON),
        "host_lines": {k: len(v) for k, v in host_lines.items()},
        "span_counts": {n: sum(1 for s in spans if s[0] == n)
                        for n in sorted({s[0] for s in spans})},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--seed", default="1")
    args = ap.parse_args()
    got = {"snaps": []}

    load = trace_reduce.load

    def load_with_host(logdir):
        got["host"] = host_spans.load(logdir)
        got["trace_bytes"] = sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(logdir) for f in fs)
        return load(logdir)

    measure = serve.measure

    def measure_keeping_stats(ld, seconds, trace_seconds=0.0):
        stats = ld.eng.stats
        ld.eng.stats = lambda: got["snaps"].append(stats()) \
            or got["snaps"][-1]
        try:
            return measure(ld, seconds, trace_seconds)
        finally:
            del ld.eng.stats

    drive = serve.run

    def run_keeping_context(conf, *a, **kw):
        got["conf"] = conf
        got["out"] = drive(conf, *a, **kw)
        return got["out"]

    trace_reduce.load = load_with_host
    serve.measure = measure_keeping_stats
    serve.run = run_keeping_context
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"])
    if rc:
        return rc

    import jax
    from benchmark import peaks
    out = got["out"]
    rep = {"workload": args.workload, "seed": int(args.seed),
           "trace_bytes": got["trace_bytes"],
           **report(out["facts"], out["trace"], got["conf"], got["snaps"],
                    got["host"], peaks.lookup(
                        jax.devices()[0].device_kind)["hbm_bytes_per_s"])}
    plane = out["trace"].planes[sorted(out["trace"].planes)[0]]
    modules = sorted(plane.get(trace_reduce.MODULES, []), key=lambda e: e[1])
    spans = sorted(host_spans.engine_line(got["host"]), key=lambda s: s[1])
    first = modules[0][1] if modules else 0
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(
            dest, f"span_probe.{args.workload}.seed{args.seed}.json"),
            "w") as f:
        json.dump({**rep,
                   "first_spans": [[n, s - first, d, a]
                                   for n, s, d, a in spans[:80]],
                   "first_modules": [[n, s - first, d]
                                     for n, s, d in modules[:40]],
                   "kernel_event_names": sorted(
                       {e[0][:1500] for e in plane.get(trace_reduce.OPS, [])
                        if "kernel_metadata" in e[0]})[:8]},
                  f, indent=1, default=str)
    print(json.dumps({"span_probe": rep}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
