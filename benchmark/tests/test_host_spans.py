"""Rehearsal of what PR 26 adds to the benchmark, on hand-made contexts (CPU,
by hand; not tier-1 — the pure functions of ``host_spans.py`` are tested in
``tests/test_serve_spans.py``, which tier-1 runs):

- every ``layer_metrics/*.json`` names a reader that exists and resolves on
  a hand-made context to a value or to None, never an exception — also on a
  program WITHOUT the names (the parent commit), where the two metrics new in
  this PR are left out of the line;
- ``tools/span_probe.report``, the part a ``benchmark`` PR would move into
  the harness, gives the six further metrics with host spans and counters,
  and leaves the span metrics OUT (None), not 0, without host spans.
"""
import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import readers, run, trace_reduce             # noqa: E402
from benchmark.tools import span_probe                       # noqa: E402

MS = 1_000_000
KERNEL = ('%closed_call.16 = bf16[16,8,8,128]{3,2,1,0} custom-call(s32[16,32]'
          '{1,0} %p, s32[16]{0} %q), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={\n"kernel":'
          '"ragged_paged_attention",\n"role":"ROLE"\n}}, backend_config={}')
OLD_KERNEL = ('%closed_call.16 = bf16[16,8,8,128]{3,2,1,0} custom-call('
              's32[16,32]{1,0} %p), custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata={}}, backend_config={}')


def trace(horizon="jit_decode_horizon(1)",
          kernel=KERNEL.replace("ROLE", "decode")):
    """Two engine steps: a dense prefill then a horizon, twice; the device
    is idle 10 ms before each executable."""
    mods, ops, t = [], [], 0
    for _ in range(2):
        mods.append(("jit__lambda(7)", t + 10 * MS, 40 * MS))
        ops.append(("%fusion.1 = bf16[1,512]{1,0} fusion(%a)",
                    t + 10 * MS, 40 * MS))
        mods.append((horizon, t + 60 * MS, 340 * MS))
        ops.append((kernel, t + 60 * MS, 85 * MS))
        ops.append(("%copy.83 = bf16[16,8]{1,0} copy(%b)",
                    t + 145 * MS, 255 * MS))
        t += 400 * MS
    planes = {"/device:TPU:0": {trace_reduce.MODULES: mods,
                                trace_reduce.OPS: ops}}
    return trace_reduce.Trace(planes, 0.8)


def spans():
    out, t = [], 0
    for i in range(2):
        out += [("serve.step", t + 2 * MS, 396 * MS, {"step": i}),
                ("serve.sched", t + 2 * MS, 52 * MS, {}),
                ("serve.prefill_dense", t + 8 * MS, 4 * MS, {"rid": i}),
                ("serve.first_token_sync", t + 12 * MS, 39 * MS, {"rid": i}),
                ("serve.provision", t + 55 * MS, 1 * MS, {}),
                ("serve.decode_dispatch", t + 56 * MS, 6 * MS, {}),
                ("serve.decode_sync", t + 62 * MS, 339 * MS - MS, {}),
                ("serve.decode_record", t + 400 * MS - 2 * MS, MS, {})]
        t += 400 * MS
    return out


FACTS = {"traced.decode_steps": 2, "decode_horizon": 8,
         "traced.prefill_tokens": 900, "decode_steps": 10, "num_slots": 16,
         "decode_tokens": 1000, "out_tok_s": 270.0, "peak_flops": 197e12}
CONF = {"hidden_size": 4096, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_hidden_layers": 16,
        "torch_dtype": "bfloat16"}


def snaps(counters=True):
    s = [{"prefill_tokens_executed": n} for n in (0, 500, 1400)]
    if counters:
        for d, (disp, pad, kv) in zip(s, [(0, 0, 0), (500, 640, 10 ** 6),
                                          (1400, 1792, 3 * 10 ** 6)]):
            d.update(prefill_tokens_dispatched=disp,
                     prefill_tokens_padded=pad,
                     decode_kv_tokens_attended=kv)
    return s


def resolve(ctx, path):
    spec = run.load_json(path)
    return getattr(readers, spec["reader"])(ctx, **spec["args"])


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    ROOT, "benchmark", "layer_metrics", "*.json"))), ids=os.path.basename)
def test_every_layer_metric_names_a_reader_and_resolves(path):
    spec = run.load_json(path)
    assert callable(getattr(readers, spec["reader"])) and spec["what"]
    for ctx in ({"facts": FACTS, "requests": [], "trace": trace()},
                {"facts": FACTS, "requests": [], "trace": None},
                {"facts": {}, "requests": []}):
        value = resolve(ctx, path)
        assert value is None or value > 0
    json.dumps(spec)


def test_named_twins_agree_with_the_shape_matched_metrics():
    ctx = {"facts": FACTS, "requests": [], "trace": trace()}
    lm = lambda n: os.path.join(ROOT, "benchmark", "layer_metrics",
                                n + ".json")
    assert resolve(ctx, lm("model.horizon_ms_per_step")) \
        == pytest.approx(resolve(ctx, lm("model.decode_ms_per_step"))) \
        == pytest.approx(2 * 340 / 16)
    assert resolve(ctx, lm("kernel.ragged_attn_named_share_pct")) \
        == pytest.approx(resolve(ctx, lm("kernel.ragged_attn_share_pct"))) \
        == pytest.approx(100 * 170 / 760)


def test_a_program_without_the_names_leaves_the_new_metrics_out():
    """The parent commit: the horizon is `jit__lambda`, the kernel's
    metadata is empty.  The line then has the old metrics and not the new."""
    old = trace(horizon="jit__lambda(3)", kernel=OLD_KERNEL)
    m = run.load_json(ROOT, "BENCHMARK.json")
    layer = [(p["name"], run.load_json(ROOT, "benchmark", "layer_metrics",
                                       p["name"] + ".json"))
             for p in m["per_layer"] if "serve_chat_c16" in p["workloads"]]
    units = {p["name"]: p["unit"] for p in m["per_layer"]}
    out = {"correct": True, "attempted": 1, "failed": 0, "facts": FACTS,
           "requests": [], "trace": old}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    got = set(run.result_line(out, [], layer, units, 1, device)["metrics"])
    assert {"model.decode_ms_per_step", "kernel.ragged_attn_share_pct",
            "model.prefill_dev_tok_s"} <= got
    assert not {"model.horizon_ms_per_step",
                "kernel.ragged_attn_named_share_pct"} & got
    out["trace"] = trace()
    got = set(run.result_line(out, [], layer, units, 1, device)["metrics"])
    assert {"model.horizon_ms_per_step",
            "kernel.ragged_attn_named_share_pct"} <= got


def test_span_probe_report_with_spans_and_counters():
    rep = span_probe.report(FACTS, trace(), CONF, snaps(),
                            {"main/1": spans()}, 819e9)
    m = rep["metrics"]
    # idle: 10 ms before each prefill and each horizon, counted from the
    # first span's start; 2 ms between the two steps are the caller's
    by = dict(rep["idle_by_host_span"])
    assert by["serve.sched"] == pytest.approx(2 * 0.006 + 2 * 0.003)
    assert by["outside:serve.step"] == pytest.approx(0.002)
    assert m["sched.exposed_host_ms_per_dispatch"] == pytest.approx(
        1e3 * sum(v for k, v in by.items() if k != "outside:serve.step") / 2)
    assert m["entry.exposed_client_ms_per_dispatch"] == pytest.approx(1.0)
    assert 0 <= m["sched.idle_unattributed_pct"] < 100
    assert rep["idle_s"]["laid_over_spans"] == pytest.approx(sum(by.values()))
    # 2e6 KV tokens x 64 KiB over 0.17 s of decode-role kernel at 819 GB/s
    assert m["kernel.ragged_attn_roofline_pct"] == pytest.approx(
        100 * 2e6 * 65536 / 819e9 / 0.17)
    assert m["model.prefill_exec_tok_s"] == pytest.approx(900 / 0.08)
    assert m["model.prefill_pad_pct"] == pytest.approx(
        100 * (1 - 1400 / 1792))
    assert rep["clock"] == {"runs": 2, "min_start_margin_ns": 4 * MS,
                            "min_end_margin_ns": 0}
    json.dumps(rep)


@pytest.mark.parametrize("host,counters", [({}, True), ({}, False),
                                           ({"main/1": spans()}, False)],
                         ids=["no-spans", "parent", "no-counters"])
def test_span_probe_report_leaves_out_what_it_cannot_read(host, counters):
    rep = span_probe.report(FACTS, trace(), CONF, snaps(counters), host,
                            819e9)
    m = rep["metrics"]
    if not host:
        assert m["sched.exposed_host_ms_per_dispatch"] is None
        assert m["entry.exposed_client_ms_per_dispatch"] is None
        assert m["sched.idle_unattributed_pct"] is None
        assert rep["idle_by_host_span"] == [] and rep["clock"] is None
    if not counters:
        assert m["kernel.ragged_attn_roofline_pct"] is None
        assert m["model.prefill_exec_tok_s"] is None
        assert m["model.prefill_pad_pct"] is None
    json.dumps(rep)
