#!/usr/bin/env python3
"""What the prefill executables of a serve cell spend their device time on
(ISSUE 32 step 0; PERF.md section 5's prefill paragraph).

    chiprun -- python3 perf/prefill_probe.py [--root DIR] [--tag NAME]
        [--workload serve_chat_c16] [--seconds 51] [--seed 1]

One traced run of the cell exactly as ``benchmark/run.py --trace 1`` makes it
(its JSON line is printed as always), of the checkout ``--root`` (this one,
or the parent's unpacked by `git archive`).  Two seams are hooked WITHOUT
touching the timed path: the trace loader keeps the device planes, and
``measure`` keeps the ``stats()`` snapshots it takes anyway.  Then the LAST
line of stdout is ``{"prefill_probe": {...}}``:

  ``prefill_s`` / ``busy_s``   seconds of the `jit_prefill_chunk(` and
                   `jit__lambda(` (dense prefill) module runs, and of the
                   device's busy union, in the traced part of the window
  ``pool_write_s``   self seconds, inside those modules, of the operations
                   whose RESULT has as many elements as one side of the KV
                   page pool whatever shape a bitcast gave it (the K/V
                   writes: the row scatter's `bf16[4202496,128]` fusions on
                   PR 31's tree, the page scatter's since), and their share
                   of ``prefill_s``
  ``top_ops``      the operations inside those modules by self seconds
  ``rows_written`` / ``pages_written``   `prefill_kv_rows_written` and
                   `prefill_kv_pages_written` over the WHOLE window (None
                   where the engine has no such counters: the parent)
and ``chiprun_out/prefill_probe.<tag>.json`` holds the same plus ``all_ops``,
every operation inside those modules.
"""
import argparse
import bisect
import json
import math
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PREFILL = r"^jit_(prefill_chunk|_lambda)\("
_RESULT = re.compile(r"^%\S+ = \(?\w+\[([\d,]*)\]")


def inside(ops, modules):
    """The events of ``ops`` that start inside one of ``modules``."""
    spans = sorted((s, s + d) for _, s, d in modules)
    starts = [a for a, _ in spans]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < spans[i][1]:
            out.append(e)
    return out


def result_elements(label):
    m = _RESULT.match(label)
    return math.prod(int(n) for n in m[1].split(",") if n) if m else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--workload", default="serve_chat_c16")
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--seed", default="1")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmark import run, trace_reduce
    from benchmark.drivers import serve

    got = {"snaps": []}
    load = trace_reduce.load

    def load_keeping_planes(logdir):
        got["planes"] = load(logdir)
        return got["planes"]

    measure = serve.measure

    def measure_keeping_stats(ld, seconds, trace_seconds=0.0):
        stats = ld.eng.stats
        pool = ld.eng._pages_k
        got["pool_elements"] = math.prod(
            (pool["q"] if isinstance(pool, dict) else pool).shape)
        ld.eng.stats = lambda: got["snaps"].append(stats()) \
            or got["snaps"][-1]
        try:
            return measure(ld, seconds, trace_seconds)
        finally:
            del ld.eng.stats

    trace_reduce.load = load_keeping_planes
    serve.measure = measure_keeping_stats
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"], root=root)
    if rc:
        return rc

    planes = got["planes"]
    lines = planes[sorted(planes)[0]]
    ops = lines.get(trace_reduce.OPS, [])
    modules = [e for e in lines.get(trace_reduce.MODULES, [])
               if re.search(PREFILL, e[0])]
    own = trace_reduce.self_ns(inside(ops, modules))
    writes = {label: ns for label, ns in own.items()
              if result_elements(label) == got["pool_elements"]}
    prefill_s = trace_reduce.union_ns(modules) / 1e9
    pool_write_s = sum(writes.values()) / 1e9
    short = {}
    for label, ns in own.items():
        key = trace_reduce.short(label)
        short[key] = short.get(key, 0) + ns
    s0, s1 = got["snaps"][0], got["snaps"][-1]
    diff = lambda k: (s1[k] - s0[k]) if k in s0 and k in s1 else None
    rep = {"root": root, "tag": args.tag, "workload": args.workload,
           "seed": int(args.seed),
           "prefill_s": prefill_s,
           "prefill_runs": len(modules),
           "busy_s": trace_reduce.union_ns(ops) / 1e9,
           "pool_write_s": pool_write_s,
           "pool_write_share_of_prefill_pct":
               100 * pool_write_s / prefill_s if prefill_s else None,
           "pool_write_ops": sorted({trace_reduce.short(label)
                                     for label in writes}),
           "top_ops": [[k, v / 1e9] for k, v in
                       sorted(short.items(), key=lambda kv: -kv[1])[:24]],
           "rows_written": diff("prefill_kv_rows_written"),
           "pages_written": diff("prefill_kv_pages_written"),
           "prefill_tokens_dispatched": diff("prefill_tokens_dispatched"),
           "prefill_tokens_padded": diff("prefill_tokens_padded")}
    dest = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"prefill_probe.{args.tag}.json"), "w") as f:
        json.dump({**rep, "all_ops": sorted(
            ([k, v / 1e9] for k, v in short.items()),
            key=lambda kv: -kv[1])}, f, indent=1)
    print(json.dumps({"prefill_probe": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
