#!/usr/bin/env python3
"""Where a cell's device time goes, by the names the program gave the work.

    python3 benchmark/tools/region_probe.py --workload <cell> --seed <n> [--seconds 51]

One traced run through the cell's own driver, exactly as ``run.py --trace
1`` makes it (its result line is printed too, before the probe's).  Before
the trace is reduced and deleted the ``XLA Ops`` of the first device plane
are split by label (`benchmark/regions.py`: ``pt_region`` of
`paddle_tpu.profiler.device_span`, else a kernel's ``kernel_metadata``, else
``ragged_dot_tiling=``): the table of seconds and shares of busy self time,
the share under any label, the ten longest unlabelled operations and the
twenty longest of all with their labels.  The table goes to stderr, the same
as one JSON object ``{"region_probe": ...}`` on the LAST line of stdout and
to ``chiprun_out/region_probe.<cell>.json``, which also holds EVERY operation
(``ops``: short name, label, seconds) and one event's text a label
(``sample_event``).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import regions, run, trace_reduce             # noqa: E402


def table(ops, per=None):
    """The probe's numbers from one device line's events; ``per``: the
    steps (train) the traced part held, to give milliseconds a step.
    ``ops`` in the result is EVERY operation ([short name, label, s])."""
    seconds, rows = regions.by_region(ops), regions.top_ops(ops, None)
    busy = sum(seconds.values())
    bare = seconds.get(regions.UNLABELLED, 0.0)
    out = {"busy_self_s": busy,
           "labelled_share_pct": 100 * (1 - bare / busy) if busy else 0.0,
           "regions": {k: {"s": v, "pct": 100 * v / busy if busy else 0.0}
                       for k, v in seconds.items()},
           "top_unlabelled": [[name, s] for name, label, s in rows
                              if label == regions.UNLABELLED][:10],
           "top_ops": rows[:20], "ops": rows}
    if per:
        for row in out["regions"].values():
            row["ms_per_step"] = 1000 * row["s"] / per
    return out


def samples(ops):
    """{label: one event's name, cut to its first 240 characters and the
    160 from ``frontend_attributes=``}: the text a regex is written
    against."""
    out = {}
    for name, _, _ in ops:
        label = regions.label_of(name)
        if label not in out:
            at = name.find("frontend_attributes=")
            out[label] = name[:240] + (" ... " + name[at:at + 160]
                                       if at > 240 else "")
    return out


def show(cell, t, file=sys.stderr):
    print(f"region_probe {cell}: busy self time {t['busy_self_s']:.4f} s, "
          f"{t['labelled_share_pct']:.1f} % under a label", file=file)
    for name, row in t["regions"].items():
        step = f"  {row['ms_per_step']:9.3f} ms/step" \
            if "ms_per_step" in row else ""
        print(f"  {name:<40} {row['s']:9.4f} s  {row['pct']:6.2f} %{step}",
              file=file)
    print("  longest unlabelled operations:", file=file)
    for name, s in t["top_unlabelled"]:
        print(f"    {s:9.4f} s  {100 * s / t['busy_self_s']:5.2f} %  {name}",
              file=file)
    print("  longest operations:", file=file)
    for name, label, s in t["top_ops"]:
        print(f"    {s:9.4f} s  {label:<24} {name}", file=file)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--seed", default="1")
    args = ap.parse_args(argv)
    load, seen = trace_reduce.load, {}

    def load_and_keep(logdir):
        planes = load(logdir)
        seen["ops"] = planes[sorted(planes)[0]].get(trace_reduce.OPS, [])
        return planes

    result_line, steps = run.result_line, {}

    def line_and_steps(out, *a, **kw):
        steps["n"] = out["facts"].get("traced.steps")
        return result_line(out, *a, **kw)

    trace_reduce.load, run.result_line = load_and_keep, line_and_steps
    try:
        rc = run.main(["--workload", args.workload, "--seed", args.seed,
                       "--seconds", args.seconds, "--trace", "1"])
    finally:
        trace_reduce.load, run.result_line = load, result_line
    if rc or "ops" not in seen:
        return rc or 1
    t = table(seen["ops"], steps.get("n"))
    show(args.workload, t)
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"region_probe.{args.workload}.json"),
              "w") as f:
        json.dump({**t, "sample_event": samples(seen["ops"])}, f, indent=1)
    print(json.dumps({"region_probe": {
        "workload": args.workload, "seed": int(args.seed),
        "busy_self_s": t["busy_self_s"],
        "labelled_share_pct": t["labelled_share_pct"],
        "regions": {k: round(v["s"], 6) for k, v in t["regions"].items()},
        "top_unlabelled": t["top_unlabelled"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
