#!/usr/bin/env python3
"""How many executables and uploads a model call of a serve cell costs
(ISSUE 38; PERF.md section 6, PR 38).

    chiprun -- python3 perf/step_launch_probe.py [--root DIR] [--tag NAME]
        [--workload serve_chat_c16] [--seconds 51] [--seed 1] [--trace 0]

One run of the cell exactly as ``benchmark/run.py`` makes it (its JSON line
is printed as always), of the checkout ``--root`` (this one, or the parent's
unpacked by `git archive`).  One seam is hooked WITHOUT touching the timed
path: ``measure`` keeps the ``stats()`` snapshots it takes anyway (and
``result_line`` the line it makes).  Then the
LAST line of stdout is ``{"step_launch_probe": {...}}`` over the window:

  ``model_calls``  dense prefills + prefill chunks + decode horizons
                   (``prefill_calls`` + ``decode_steps`` + ``verify_steps``)
  ``step_launches`` / ``step_uploads``   what `step()` launched and uploaded
  ``launches_per_model_call`` / ``uploads_per_model_call``   1 and 1 on a
                   greedy cell with no prefix hit (a copy-on-write copy, in
                   ``cow_copies``, is a launch and an upload of its own)
(``None`` where the engine has no such counters: the parent), beside the
line's ``out_tok_s`` / ``tpot_p90_ms`` / ``ttft_p90_ms`` / ``setup_s`` and,
with ``--trace 1``, ``device.idle_pct.serve`` and
``model.horizon_ms_per_step``; ``chiprun_out/step_launch_probe.<tag>.json``
holds the same.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTERS = ("step_launches", "step_uploads", "prefill_calls", "decode_steps",
            "verify_steps", "cow_copies", "tokens_generated")
LINE = ("out_tok_s", "tpot_p90_ms", "ttft_p90_ms", "train_tok_s", "setup_s",
        "device.idle_pct.serve", "model.horizon_ms_per_step", "correct")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--workload", default="serve_chat_c16")
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--seed", default="1")
    ap.add_argument("--trace", default="0")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmark import run
    from benchmark.drivers import serve

    snaps, lines = [], []
    measure, result_line = serve.measure, run.result_line

    def measure_keeping_stats(ld, seconds, trace_seconds=0.0):
        stats = ld.eng.stats
        ld.eng.stats = lambda: snaps.append(stats()) or snaps[-1]
        try:
            return measure(ld, seconds, trace_seconds)
        finally:
            del ld.eng.stats

    serve.measure = measure_keeping_stats
    run.result_line = lambda *a: lines.append(result_line(*a)) or lines[-1]
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", args.trace],
                  root=root)
    if rc:
        return rc
    line = lines[-1]
    flat = {"correct": line["correct"],
            **{k: v["value"] for k, v in line["metrics"].items()}}
    s0, s1 = (snaps[0], snaps[-1]) if snaps else ({}, {})   # a train cell
    rep = {"root": root, "tag": args.tag, "workload": args.workload,
           "seed": int(args.seed), "trace": int(args.trace)}
    rep.update({k: (s1[k] - s0[k]) if k in s0 else None for k in COUNTERS})
    calls = rep["prefill_calls"]
    if calls is not None:
        calls += rep["decode_steps"] + rep["verify_steps"]
        rep["model_calls"] = calls
        rep["launches_per_model_call"] = rep["step_launches"] / calls
        rep["uploads_per_model_call"] = rep["step_uploads"] / calls
    rep.update({k: flat.get(k) for k in LINE})
    dest = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"step_launch_probe.{args.tag}.json"),
              "w") as f:
        json.dump(rep, f, indent=1)
    print(json.dumps({"step_launch_probe": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
