#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` (its configuration, its
traffic mix, the chips it needs, the metrics it reports) and everything that
belongs to it is a data file found by that name under ``benchmark/``:
``configs/<config>.json``, ``traffic/<traffic>.json`` and, per per-layer
metric, ``layer_metrics/<metric>.json`` (a reader of ``readers.py`` and its
arguments).  No cell, mix, configuration or metric is listed in code.

Set-up (counted in ``setup_s``): import, the weights on the device from
``--seed``, compilation or compile-cache loads, a warm-up of the shapes this
cell's traffic uses, the check against ``reference.py``, the ramp.  Then the
window of ``--seconds``.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` records the last seconds of the same window with the profiler
and prints the cell's per-layer metrics and a breakdown.  The LAST line of
stdout is the one JSON object; everything else goes to stderr.

There is no CPU branch: without a TPU, with a device kind missing from
``peaks.py`` or with fewer chips than the cell asks for, it exits non-zero
before anything is measured.
"""
import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import importlib                                              # noqa: E402
import json                                                   # noqa: E402
import os                                                     # noqa: E402
import sys                                                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(root, name):
    """(cell, configuration, traffic, e2e names, [(per-layer name, file)])
    of the cell ``name`` of the benchmark rooted at ``root``."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    conf = load_json(root, files[cell["config"]])
    bench = os.path.join(root, os.path.dirname(os.path.dirname(
        files[cell["config"]])))
    mix = load_json(bench, "traffic", cell["traffic"] + ".json")
    mine = lambda m: name in m.get("workloads", [name])
    e2e = [m["name"] for m in manifest["end_to_end"] if mine(m)]
    layer = [(m["name"], load_json(bench, "layer_metrics",
                                   m["name"] + ".json"))
             for m in manifest["per_layer"] if mine(m)]
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    return cell, conf, mix, e2e, layer, units


def result_line(out, names, layer, units, trace, device):
    """The one JSON object: end-to-end metrics, or with ``trace`` the
    per-layer ones read from the run's counters, requests and trace."""
    from benchmark import readers
    line = {k: out[k] for k in ("correct", "attempted", "failed")}
    if not trace:
        values = {n: out["end_to_end"].get(n) for n in names}
    else:
        values = {n: getattr(readers, spec["reader"])(out, **spec["args"])
                  for n, spec in layer}
    line["metrics"] = {n: {"value": v, "unit": units[n]}
                       for n, v in values.items() if v is not None}
    line["device"] = device
    tr = out.get("trace")
    if trace and tr is not None:
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        line["breakdown"] = tr.breakdown()
    return line


def main(argv=None, root=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(HERE)
    if root not in sys.path:
        sys.path.insert(0, root)
    cell, conf, mix, names, layer, units = load_cell(root, args.workload)

    import jax
    from benchmark import peaks
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    peak = peaks.lookup(devices[0].device_kind)
    if len(devices) < cell["chips"]:
        print(f"benchmark: the cell needs {cell['chips']} chips, JAX found "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 2
    found, devices = len(devices), devices[:cell["chips"]]
    from paddle_tpu.core.device import setup_compile_cache
    print(f"compile cache: {setup_compile_cache()}", file=sys.stderr)

    driver = importlib.import_module("benchmark.drivers." + conf["driver"])
    out = driver.run(conf, mix, args.seed, args.seconds, bool(args.trace),
                     T_START, devices, peak)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": found,
              "memory_peak_bytes": max(
                  int(d.memory_stats()["peak_bytes_in_use"])
                  for d in devices)}
    print(json.dumps(result_line(out, names, layer, units, args.trace,
                                 device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
