"""trace_reduce.py on a hand-made event list (no chip, no profiler)."""
import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import readers, trace_reduce as tr            # noqa: E402

MS = 1_000_000
# labels as the TPU runtime writes them: the whole HLO instruction
WHILE = "%while.51 = (s32[]{:T(128)}, s32[16]{0:T(128)S(1)}) while(%tuple.1), condition=%cond, body=%body"
RAGGED = ('%closed_call.16 = bf16[16,8,8,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
          'custom-call(s32[16,32]{1,0:T(8,128)} %get-tuple-element.1347, '
          's32[16]{0:T(128)} %get-tuple-element.1348, bf16[16,8,8,128]{3,2,1,0} '
          '%pad.52), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
NORM = ('%closed_call.3 = bf16[16,4096]{1,0} custom-call(bf16[16,4096]{1,0} '
        '%p.1, bf16[4096]{0} %p.2), custom_call_target="tpu_custom_call"')
DOT = "%fusion.139 = bf16[16,14336]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[16,4096]{1,0} %p.3), kind=kOutput"
PDOT = "%fusion.7 = bf16[512,4096]{1,0:T(8,128)(2,1)} fusion(bf16[512,4096]{1,0} %p.4), kind=kOutput"
COPY = "%copy.3 = bf16[8,513,64,128]{3,2,1,0:T(8,128)(2,1)} copy(bf16[8,513,64,128]{3,0,2,1} %fusion.13)"
# one device line: a 10 ms `while` that encloses two kernel calls and a
# fusion, then (after a 5 ms gap) a 4 ms fusion overlapping a 4 ms copy by 2
OPS = [(WHILE, 0, 10 * MS), (RAGGED, 1 * MS, 3 * MS), (DOT, 4 * MS, 2 * MS),
       (RAGGED, 6 * MS, 3 * MS), (PDOT, 15 * MS, 4 * MS),
       (COPY, 17 * MS, 4 * MS)]
MODULES = [("jit__lambda(123)", 0, 10 * MS), ("jit__lambda(9)", 15 * MS, 6 * MS)]
PLANES = {"/device:TPU:0": {tr.OPS: OPS, tr.MODULES: MODULES}}
LAYER = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "layer_metrics")


def args_of(metric):
    with open(os.path.join(LAYER, metric + ".json")) as f:
        return json.load(f)["args"]


def test_busy_union_and_idle_share():
    assert tr.union_ns(OPS) == 16 * MS          # 10 + (4 + 4 - 2)
    t = tr.Trace(PLANES, window_s=0.025)
    assert t.busy_s() == pytest.approx(0.016)
    assert t.idle_s() == pytest.approx(0.009)
    assert readers.quotient({"trace": t, "facts": {}},
                            **args_of("device.idle_pct.serve")) \
        == pytest.approx(36.0)


def test_self_time_per_name_does_not_count_a_loop_body_twice():
    own = tr.self_ns(OPS)
    assert own[WHILE] == 2 * MS                              # 10 - 3 - 2 - 3
    assert own[RAGGED] == 6 * MS
    assert own[PDOT] == 2 * MS                               # 4 - overlap
    assert sum(own.values()) == tr.union_ns(OPS)
    top = tr.Trace(PLANES, 0.025).breakdown(top=2)
    assert top["device_ops"][0] == [
        "closed_call.16 custom-call[tpu_custom_call] bf16[16,8,8,128]", 0.006]
    assert top["device_ops"][1][0] == "copy.3 copy bf16[8,513,64,128]"
    assert top["idle_gaps"] == [
        ["before fusion.7 fusion bf16[512,4096]", 0.005]]
    assert tr.short(WHILE) == "while.51 while s32[]"
    assert tr.short("%x.1 = cut off mid-instr") == "x.1"


def test_the_metric_files_expressions_on_the_hand_made_trace():
    t = tr.Trace(PLANES, 0.025)
    ctx = {"trace": t, "facts": {"traced.decode_steps": 1, "decode_horizon": 8,
                                 "traced.prefill_tokens": 600}}
    # the ragged kernel is the custom call whose first operand is the page
    # table; another Pallas kernel (NORM) would not match
    ragged = args_of("kernel.ragged_attn_share_pct")
    assert re.search(ragged["num"]["trace"]["match"], RAGGED)
    assert not re.search(ragged["num"]["trace"]["match"], NORM)
    assert readers.quotient(ctx, **ragged) == pytest.approx(100 * 6 / 16)
    # two modules of one name: the decode horizon is the one in which the
    # kernel ran at its decode shape, prefill the one in which it did not
    assert readers.quotient(ctx, **args_of("model.decode_ms_per_step")) \
        == pytest.approx(10 / 8)
    assert readers.quotient(ctx, **args_of("model.prefill_dev_tok_s")) \
        == pytest.approx(600 / 0.006)
    assert t.matching_s(tr.MODULES, "^jit__lambda") == pytest.approx(0.016)
    # nothing to read -> None, and the harness leaves the metric out
    assert readers.quotient(ctx, {"trace": {"line": tr.OPS, "match": "nope"}},
                            {"trace": "busy_s"}) is None
    assert readers.quotient({"trace": None, "facts": {}},
                            **args_of("device.idle_pct.serve")) is None


def test_the_flash_attention_expression_tells_attention_from_rms_norm():
    # signatures of the train step's kernels (sandbox compile for a described
    # v5e, PR 25), written the way the runtime labels an event
    rx = args_of("kernel.flash_attn_roofline_pct")["den"]["trace"]["match"]
    tail = ' %p.1), custom_call_target="tpu_custom_call", frontend_attributes={}'
    lay3, lay2 = "{2,1,0:T(8,128)(2,1)}", "{1,0:T(8,128)(2,1)S(1)}"
    flash = [f"%jvp__.3 = (bf16[64,2048,128]{lay3}, f32[64,2048,1]{lay3}) "
             f"custom-call(bf16[64,2048,128]{lay3}" + tail,
             f"%pallas_call.91 = f32[64,16,128]{lay3} custom-call("
             f"f32[64,2048,1]{lay3}" + tail,
             f"%t.1 = (bf16[16,2048,128]{lay3}, bf16[16,2048,128]{lay3}) "
             f"custom-call(bf16[64,2048,128]{lay3}" + tail,
             f"%t.2 = bf16[64,2048,128]{lay3} custom-call(bf16[64,2048,128]"
             f"{lay3}" + tail]
    other = [f"%jvp__.17 = (bf16[4096,4096]{lay2}, f32[4096,1]{lay2}) "
             f"custom-call(bf16[4096,4096]{lay2}" + tail,
             f"%fusion.9 = bf16[64,2048,128]{lay3} fusion(bf16[64,2048,128]"
             f"{lay3} %p.1), kind=kLoop", RAGGED.replace("16,8,8,128", "1,8,2048,128")]
    assert all(re.search(rx, x) for x in flash)
    assert not any(re.search(rx, x) for x in other)
    events = [(flash[0], 0, 2 * MS), (other[0], 2 * MS, 1 * MS),
              (flash[3], 3 * MS, 2 * MS)]
    t = tr.Trace({"/device:TPU:0": {tr.OPS: events, tr.MODULES: []}}, 0.01)
    ctx = {"trace": t, "facts": {"traced.steps": 1, "peak_flops": 100e12,
                                 "flash_flops_per_step": 0.2e12}}
    assert readers.quotient(
        ctx, **args_of("kernel.flash_attn_roofline_pct")) \
        == pytest.approx(100 * 0.2e12 / (0.004 * 100e12))


def test_host_planes_are_ignored_and_two_devices_average():
    both = dict(PLANES)
    both["/device:TPU:1"] = {tr.OPS: [(DOT, 0, 8 * MS)],
                             tr.MODULES: []}
    assert tr.Trace(both, 0.025).busy_s() == pytest.approx(0.012)
    assert not tr.DEVICE_PLANE.match("/host:CPU")
    assert not tr.DEVICE_PLANE.match("/device:TPU:0 SparseCore")
    with pytest.raises(ValueError):
        tr.Trace({}, 1.0)


def test_request_percentile():
    reqs = [{"queue_s": x / 1000} for x in range(1, 102)]
    assert readers.request_percentile({"requests": reqs}, "queue_s", 50,
                                      1000) == pytest.approx(51.0)
    assert readers.request_percentile({"requests": reqs[:1]}, "queue_s",
                                      50) is None


def test_on_the_head_of_a_real_trace():
    """The first 400 device operations of serve_chat_c16's traced run on a
    TPU v5 lite (PR 25): a dense prefill, before the first horizon.  The
    numbers are what the reducer gave when the fixture was cut; the labels
    are the runtime's own, so the metric files' expressions meet real text."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "real_trace_head.json")) as f:
        fx = json.load(f)
    lines = {k: [tuple(e) for e in fx[k]] for k in (tr.OPS, tr.MODULES)}
    t = tr.Trace({"/device:TPU:0": lines}, 0.029689349)
    assert t.busy_s() == pytest.approx(0.027801046)
    assert sum(tr.self_ns(lines[tr.OPS]).values()) == tr.union_ns(lines[tr.OPS])
    ctx = {"trace": t, "facts": {"traced.prefill_tokens": 128,
                                 "traced.decode_steps": 1,
                                 "decode_horizon": 8}}
    # the one big module is a dense prefill (jit__lambda, no ragged kernel)
    assert readers.quotient(ctx, **args_of("model.prefill_dev_tok_s")) \
        == pytest.approx(128 / 0.034945276)
    assert readers.quotient(ctx, **args_of("model.decode_ms_per_step")) is None
    assert readers.quotient(ctx, **args_of("kernel.ragged_attn_share_pct")) \
        is None
    top = t.breakdown(3)["device_ops"]
    assert top[1][0] == "constant_dynamic-slice_fusion.13 fusion " \
                        "bf16[1,8,513,64,128]"
    assert all(len(name) <= 120 for name, _ in top)
