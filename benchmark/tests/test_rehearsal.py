"""CPU rehearsal of the benchmark (on-chip-measurement guide section 2,
rehearsal 1): every cell's path at a tiny configuration kept beside this
file, the drivers' functions called with the Pallas kernels interpreted.
ALL steering lives here: ``run.py`` has no CPU switch, and run plainly with
no TPU it exits non-zero before anything is measured.  No number from these
runs is a device number; they check control flow and the shape of the output.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import json
import os
import re
import shutil
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

import jax                                                   # noqa: E402
from benchmark import peaks, reference, run                  # noqa: E402
from benchmark.drivers import serve, train                   # noqa: E402

PEAK = peaks.lookup("TPU v5 lite")
INTERPRET = dict(interpret=True, attention_impl="pallas")
SEED = 2 ** 31 + 12345              # the driver's seeds pass 32 signed bits


def tiny(name):
    return run.load_json(HERE, name + ".json")


def serve_run(mix, **kw):
    return serve.run(tiny("tiny-serve"), tiny(mix), SEED, 1.5, False,
                     time.perf_counter(), jax.devices(), PEAK,
                     **INTERPRET, **kw)


def line_of(out, names, layer, units, trace=0):
    device = {"platform": "cpu", "kind": "cpu", "count": 1,
              "memory_peak_bytes": 0}
    line = run.result_line(out, names, layer, units, trace, device)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    return json.loads(json.dumps(line))


@pytest.mark.parametrize("mix", ["tiny_closed", "tiny_open"])
def test_serve_cell_path_closed_and_open_loop(mix):
    out = serve_run(mix)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 5
    assert out["facts"]["compiled_in_window"] == 0
    assert out["check"]["check_positions"] == 32
    names = ["out_tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    units = dict.fromkeys(names + ["sched.decode_fill_pct"], "x")
    line = line_of(out, names, [], units)
    assert set(line["metrics"]) == set(names)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # a per-layer metric read from counters; one that needs a trace is
    # left out when there is none
    layer = [(n, run.load_json(ROOT, "benchmark", "layer_metrics",
                               n + ".json"))
             for n in ("sched.decode_fill_pct", "device.idle_pct.serve")]
    units["device.idle_pct.serve"] = "%"
    line = line_of(out, names, layer, units, trace=1)
    assert set(line["metrics"]) == {"sched.decode_fill_pct"}
    assert 0 < line["metrics"]["sched.decode_fill_pct"]["value"] <= 100


def test_serve_check_fails_a_model_with_two_layers_swapped(monkeypatch):
    # toy logits are tiny (|logit| < 0.2): hold the toy to a toy's delta
    monkeypatch.setattr(reference, "SERVE_LOGIT_DELTA", 1e-3)
    right = serve_run("tiny_closed")
    wrong = serve_run("tiny_closed", layer_order=[1, 0, 2])
    assert right["correct"] and not wrong["correct"]
    assert wrong["check"]["worst_logit_gap"] > 1e-3 \
        >= right["check"]["worst_logit_gap"]


def test_train_cell_path_and_wrong_model(monkeypatch):
    args = (tiny("tiny-train"), tiny("tiny_batches"), SEED, 1.0, False,
            time.perf_counter(), jax.devices(), PEAK)
    right = train.run(*args, check_kernels=False)
    assert right["correct"] and right["failed"] == 0
    assert right["attempted"] == right["facts"]["steps"] > 2
    units = dict.fromkeys(["train_tok_s", "setup_s", "train.mfu_pct"], "x")
    line = line_of(right, ["train_tok_s", "setup_s"], [], units)
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    mfu = [("train.mfu_pct", run.load_json(ROOT, "benchmark", "layer_metrics",
                                           "train.mfu_pct.json"))]
    assert "train.mfu_pct" in line_of(right, [], mfu, units, 1)["metrics"]
    wrong = train.run(*args, check_kernels=False, layer_order=[2, 1, 0])
    assert wrong["check"]["relative_diff"] \
        > 3 * right["check"]["relative_diff"]
    monkeypatch.setattr(reference, "TRAIN_LOSS_RTOL",
                        2 * right["check"]["relative_diff"])
    assert not train.run(*args, check_kernels=False,
                         layer_order=[2, 1, 0])["correct"]


def test_without_a_tpu_nothing_runs_and_nothing_is_printed(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert run.main(["--workload", "serve_chat_c16", "--seed", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


def test_files_alone_add_a_config_a_mix_a_cell_and_a_metric(tmp_path):
    """A later PR adds files and BENCHMARK.json entries and edits no code."""
    root = str(tmp_path)
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "benchmark", d),
                        os.path.join(root, "benchmark", d))
    m = run.load_json(ROOT, "BENCHMARK.json")
    shutil.copy(os.path.join(HERE, "tiny-serve.json"),
                os.path.join(root, "benchmark", "configs", "new-model.json"))
    shutil.copy(os.path.join(HERE, "tiny_open.json"),
                os.path.join(root, "benchmark", "traffic", "new_mix.json"))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "sched.tokens_per_dispatch.json"), "w") as f:
        json.dump({"reader": "quotient", "args": {
            "num": {"facts": ["decode_tokens"]},
            "den": {"facts": ["decode_steps"]}}}, f)
    m["configs"].append({"name": "new-model", "source": "a paper",
                         "file": "benchmark/configs/new-model.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "new_cell", "config": "new-model",
                           "traffic": "new_mix", "chips": 1, "why": "test"})
    for metric in m["end_to_end"]:
        if "workloads" in metric and metric["name"] != "train_tok_s":
            metric["workloads"].append("new_cell")
    m["per_layer"].append({
        "name": "sched.tokens_per_dispatch", "unit": "tokens",
        "better": "higher", "source": "program_counter",
        "layer": "scheduler / host loop", "moves": "out_tok_s",
        "workloads": ["new_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    cell, conf, mix, names, layer, units = run.load_cell(root, "new_cell")
    assert conf["driver"] == "serve" and mix["rate_rps"] == 20
    assert names == ["out_tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"]
    assert [n for n, _ in layer] == ["sched.tokens_per_dispatch"]
    out = serve.run(conf, mix, SEED, 1.0, False, time.perf_counter(),
                    jax.devices(), PEAK, **INTERPRET)
    line = line_of(out, names, layer, units, trace=1)
    assert line["metrics"]["sched.tokens_per_dispatch"]["unit"] == "tokens"
    assert line["metrics"]["sched.tokens_per_dispatch"]["value"] > 0


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_names_files_and_arrows():
    m = run.load_json(ROOT, "BENCHMARK.json")
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        cell, conf, mix, names, layer, units = run.load_cell(ROOT, w["name"])
        assert "setup_s" in names and len(names) >= 2 and layer
        assert conf["driver"] in ("serve", "train")
        for n, spec in layer:
            assert spec["reader"] in ("quotient", "request_percentile")
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        conf = run.load_json(ROOT, c["file"])
        assert c["file"].startswith("benchmark/")
        assert set(c["reduced"]) == set(conf["reduced"])
        assert c["source"] == conf["source"]
    layers = set()
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and x["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", x["unit"])
        assert set(x.get("workloads", cells)) <= set(cells)
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        layers.add(x["layer"])
        where = set(x.get("workloads", cells))
        assert where <= set(e2e[x["moves"]].get("workloads", cells)), x
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for name in layers:
        assert f"| {name} |" in perf, f"PERF.md section 3 lacks {name!r}"
    assert len(json.dumps(m)) < 64 * 1024
