"""`benchmark/regions.py` on hand-made event lists and on the head of a real
trace: device time by the names the program wrote into its instructions
(`pt_region` of `paddle_tpu.profiler.device_span`, a kernel's
`kernel_metadata`, XLA's `ragged_dot_tiling=`).  No chip, no model; joined to
tier-1 by `tests/test_benchmark_reduction.py`.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers, regions, trace_reduce as tr    # noqa: E402

sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
import region_probe                                           # noqa: E402

US = 1000        # the events below are in microseconds


def op(name, shape, opcode, attrs="", rest=""):
    """An instruction's text as the runtime names a device event."""
    fa = f", frontend_attributes={{{attrs}}}" if attrs else ""
    return f"%{name} = {shape}{{1,0}} {opcode}({rest}){fa}, metadata={{}}"


OPTIMIZER = op("subtract_convert_fusion.3", "bf16[4096,32768]", "fusion",
               'pt_region="optimizer"', "bf16[4096,32768] %p")
HEAD = op("fusion.7", "f32[4096]", "fusion", 'pt_region="head_loss"')
# a kernel call inside a region: jax writes the JSON with newlines, and the
# region follows it inside the same braces
FLASH = op("jvp_block.attn_.13", "bf16[64,2048,128]", "custom-call",
           'kernel_metadata={\n"kernel":"flash_attention",\n"pass":"fwd"\n}'
           ',pt_region="block.attn"', "bf16[64,2048,128] %q")
# a kernel call outside any region (an earlier program, or the ragged
# kernel's own file run alone)
RAGGED = op("closed_call.16", "bf16[16,8,8,128]", "custom-call",
            'kernel_metadata={\n"kernel":"ragged_paged_attention",\n'
            '"role":"decode"\n}', "s32[16,32] %t")
XLA_GMM = op("ragged-dot-fusion.1", "bf16[8192,1024]", "fusion",
             'ragged_dot_tiling="512,512,512"')
COPY = op("copy.16", "bf16[16,4096,4096]", "copy")
WHILE = op("while.3", "(s32[], f32[128,64,128])", "while",
           'pt_region="ssm.chunked_scan"')
BODY = op("multiply_add_fusion.2", "f32[128,64,128]", "fusion",
          'pt_region="ssm.chunked_scan"')
COND = op("conditional.1", "bf16[704,1024]", "conditional",
          'pt_region="moe.experts"')
GLUE = op("gather_fusion.4", "bf16[704,1024]", "fusion",
          'pt_region="moe.dispatch"')


def test_label_of_takes_the_region_first_then_the_kernel_then_the_tiling():
    assert regions.label_of(OPTIMIZER) == "optimizer"
    assert regions.label_of(FLASH) == "block.attn"          # both: region
    assert regions.label_of(RAGGED) == "kernel:ragged_paged_attention/decode"
    assert regions.label_of(XLA_GMM) == "ragged_dot"
    assert regions.label_of(COPY) == regions.UNLABELLED
    bare = op("k.1", "f32[8]", "custom-call",
              'kernel_metadata={"kernel": "grouped_matmul"}')
    assert regions.label_of(bare) == "kernel:grouped_matmul"


def test_by_region_is_self_time_and_adds_up_to_the_busy_union():
    # a `while` that encloses labelled children keeps only its own part;
    # a `conditional` labelled moe.experts encloses a moe.dispatch child
    ops = [(OPTIMIZER, 0, 40 * US), (HEAD, 40 * US, 10 * US),
           (WHILE, 60 * US, 30 * US), (BODY, 62 * US, 10 * US),
           (BODY, 75 * US, 10 * US),
           (COND, 100 * US, 20 * US), (GLUE, 102 * US, 6 * US),
           (COPY, 130 * US, 5 * US), (RAGGED, 140 * US, 2 * US),
           (XLA_GMM, 150 * US, 8 * US), (FLASH, 160 * US, 12 * US)]
    got = regions.by_region(ops)
    us = lambda s: round(s * 1e6)
    assert {k: us(v) for k, v in got.items()} == {
        "optimizer": 40, "ssm.chunked_scan": 30, "moe.experts": 14,
        "block.attn": 12, "head_loss": 10, "ragged_dot": 8,
        "moe.dispatch": 6, "unlabelled": 5,
        "kernel:ragged_paged_attention/decode": 2}
    assert list(got)[0] == "optimizer"                   # largest first
    assert sum(got.values()) == pytest.approx(tr.union_ns(ops) / 1e9)
    assert region_probe.table(ops)["labelled_share_pct"] == pytest.approx(
        100 * (1 - 5 / 127))
    assert regions.top_unlabelled(ops, 3) == [
        ["copy.16 copy bf16[16,4096,4096]", pytest.approx(5e-6)]]
    top = regions.top_ops(ops, 2)
    assert [r[:2] for r in top] == [
        ["subtract_convert_fusion.3 fusion bf16[4096,32768]", "optimizer"],
        ["multiply_add_fusion.2 fusion f32[128,64,128]", "ssm.chunked_scan"]]
    assert top[1][2] == pytest.approx(20e-6)     # both runs of the body
    assert regions.top_ops(ops, 5, only="moe.dispatch")[0][2] \
        == pytest.approx(6e-6)


def test_nothing_labelled_and_nothing_at_all():
    assert regions.by_region([]) == {}
    assert region_probe.table([])["labelled_share_pct"] == 0.0
    assert regions.top_unlabelled([]) == []
    ops = [(COPY, 0, 5 * US)]
    assert regions.by_region(ops) == {"unlabelled": pytest.approx(5e-6)}
    assert region_probe.table(ops)["labelled_share_pct"] == 0.0


def test_the_metric_files_read_the_regions_and_nothing_where_there_is_none():
    """The six name-matched metrics of ISSUE 37 on a hand-made trace: each
    finds the events of its region; on a trace without labels (an earlier
    program) each returns None, so the line leaves it out."""
    spec = lambda name: json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json")))["args"]
    update = op("multiply_reduce_fusion.10", "(bf16[64,128,64], "
                "f32[64,128,64,128])", "fusion",
                'pt_region="ssm.decode_update"')
    layer = op("fusion.9", "bf16[64,4096]", "fusion", 'pt_region="moe.layer"')
    shared = op("fusion.11", "bf16[64,4096]", "fusion",
                'pt_region="moe.shared"')
    ops = [(OPTIMIZER, 0, 30 * US), (HEAD, 30 * US, 10 * US),
           (update, 50 * US, 20 * US),
           (WHILE, 80 * US, 40 * US), (BODY, 85 * US, 10 * US),
           (COND, 130 * US, 30 * US), (GLUE, 132 * US, 5 * US),
           (layer, 170 * US, 5 * US), (shared, 180 * US, 20 * US)]
    trace = tr.Trace({"/device:TPU:0": {tr.OPS: ops, tr.MODULES: []}},
                     250e-6)
    facts = {"traced.steps": 2, "traced.state_bytes": 8192.0,
             "traced.ssd_flops": 1e6, "peak_hbm_bytes_per_s": 819e6,
             "peak_flops": 1e12}
    ctx = {"trace": trace, "facts": facts}
    q = lambda name: readers.quotient(ctx, **spec(name))
    assert q("train.optimizer_ms_per_step") == pytest.approx(0.015)
    assert q("train.head_loss_ms_per_step") == pytest.approx(0.005)
    # every moe.* label, the conditional's interval whole: 30 + 5 + 20
    assert q("train.moe_layer_ms_per_step") == pytest.approx(0.0275)
    assert q("kernel.ssm_update_named_roofline_pct") == pytest.approx(
        100 * 8192 / (20e-6 * 819e6))
    assert q("kernel.ssd_scan_named_roofline_pct") == pytest.approx(
        100 * 1e6 / (40e-6 * 1e12))
    # the glue: dispatch 5 + layer 5 of 155 busy; the conditional carries
    # moe.experts and the shared expert moe.shared, so neither is in it
    assert q("model.moe_glue_share_pct") == pytest.approx(100 * 10 / 155)
    bare = tr.Trace({"/device:TPU:0": {tr.OPS: [(COPY, 0, 9 * US)],
                                       tr.MODULES: []}}, 1e-5)
    for name in ("train.optimizer_ms_per_step",
                 "train.head_loss_ms_per_step",
                 "train.moe_layer_ms_per_step",
                 "kernel.ssm_update_named_roofline_pct",
                 "kernel.ssd_scan_named_roofline_pct",
                 "model.moe_glue_share_pct"):
        assert readers.quotient({"trace": bare, "facts": facts},
                                **spec(name)) is None, name


def test_on_the_head_of_a_real_trace_nothing_is_labelled_yet():
    """The first 400 device operations of serve_chat_c16's traced run of PR
    25: a program from before the labels.  Everything is `unlabelled`, the
    seconds are the busy union's, and the probe's table says so."""
    with open(os.path.join(HERE, "real_trace_head.json")) as f:
        ops = [tuple(e) for e in json.load(f)[tr.OPS]]
    got = regions.by_region(ops)
    assert list(got) == [regions.UNLABELLED]
    assert got[regions.UNLABELLED] == pytest.approx(tr.union_ns(ops) / 1e9)
    t = region_probe.table(ops, per=2)
    assert t["labelled_share_pct"] == 0.0
    assert t["regions"]["unlabelled"]["pct"] == pytest.approx(100.0)
    assert t["regions"]["unlabelled"]["ms_per_step"] == pytest.approx(
        500 * t["busy_self_s"])
    assert len(t["top_unlabelled"]) == 10 and len(t["top_ops"]) == 20
    assert "constant_dynamic-slice_fusion.13 fusion bf16[1,8,513,64,128]" \
        in [name for name, _ in t["top_unlabelled"]]


def test_the_probe_prints_its_table(capsys):
    ops = [(OPTIMIZER, 0, 30 * US), (COPY, 40 * US, 10 * US)]
    long = op("k.2", "bf16[8,8]", "custom-call", 'pt_region="head"',
              "x" * 400)
    kept = region_probe.samples(ops + [(long, 60 * US, US)])
    assert kept["optimizer"] == OPTIMIZER and kept["unlabelled"] == COPY
    assert len(kept["head"]) < 420 and 'pt_region="head"' in kept["head"]
    region_probe.show("a_cell", region_probe.table(ops), file=sys.stdout)
    out = capsys.readouterr().out
    assert "75.0 % under a label" in out
    assert "optimizer" in out and "copy.16 copy" in out
