#!/usr/bin/env python3
"""Look at one trace by hand before writing a regular expression against it.

    python3 benchmark/tools/trace_probe.py --workload <cell> [--seconds 20]

Runs the cell once with ``--trace 1`` exactly as ``run.py`` does and, before
the trace is reduced and deleted, writes what the profiler recorded to
``chiprun_out/trace_probe.<cell>.json``: every plane and line with its event
count, and per device line the labels that took most time (count, seconds,
one event's stats) plus the first events as ``trace_reduce`` labels them.
"""
import argparse
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run, trace_reduce                      # noqa: E402


def describe(logdir, top=60, head=400):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = {"file_bytes": os.path.getsize(path), "planes": []}
    for plane in ProfileData.from_file(path).planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            events = list(line.events)
            row = {"name": line.name, "events": len(events)}
            if trace_reduce.DEVICE_PLANE.match(plane.name):
                by = {}
                for e in events:
                    t = by.setdefault(e.name, [0, 0.0, None])
                    t[0] += 1
                    t[1] += e.duration_ns / 1e9
                    if t[2] is None:
                        t[2] = {k: str(v)[:300] for k, v in e.stats}
                row["top"] = sorted(([n] + t for n, t in by.items()),
                                    key=lambda r: -r[2])[:top]
                row["head"] = [list(trace_reduce._event(e))
                               for e in events[:head]]
                first = next((i for i, e in enumerate(events)
                              if "tpu_custom_call" in e.name), None)
                if first is not None:       # the first kernel's neighbours
                    row["around_first_kernel"] = [
                        list(trace_reduce._event(e))
                        for e in events[max(first - 150, 0):first + 150]]
            p["lines"].append(row)
        out["planes"].append(p)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="20")
    ap.add_argument("--seed", default="1")
    args = ap.parse_args()
    load = trace_reduce.load
    dest = os.path.join(ROOT, "chiprun_out")
    os.makedirs(dest, exist_ok=True)

    def load_and_describe(logdir):
        with open(os.path.join(dest, f"trace_probe.{args.workload}.json"),
                  "w") as f:
            json.dump(describe(logdir), f, indent=1)
        return load(logdir)

    trace_reduce.load = load_and_describe
    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
