"""The engine's host phases in the profiler's own trace, the dispatch-time
counters, and the reduction that lays host spans over device idle time
(ISSUE 26).

`ServingEngine._span` is unconditional: a `jax.profiler.TraceAnnotation`
named `serve.<phase>` whether or not a `Telemetry` is attached, visible
only while a profiler session is open.  The first test opens a real one on
the CPU backend (the host tracer records annotations there too) and reads
the `.xplane.pb` back with the benchmark's loader.
"""
import os
import sys

import numpy as np
import pytest
import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import host_spans                               # noqa: E402
from paddle_tpu.inference.paged import ServingEngine           # noqa: E402
from paddle_tpu.models.llama import (build_functional_llama,   # noqa: E402
                                     llama_config_tiny)
from paddle_tpu.observability import Telemetry                 # noqa: E402
from paddle_tpu.observability.telemetry import ENGINE_PHASES   # noqa: E402

SPAN_ONLY = ("step", "first_token_sync", "provision")
VOCABULARY = {"serve." + n for n in ENGINE_PHASES + SPAN_ONLY}


@pytest.fixture(scope="module")
def llama():
    cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(1))
    return cfg, (ep, bp, hp)


def _echo(params):
    """Echo-biased weights (tests/test_spec_decode.py): greedy decode
    settles into repetition, so the n-gram drafter has drafts to verify."""
    ep, bp, hp = params
    bp = {k: (v * 0.05 if k.startswith("w") else v) for k, v in bp.items()}
    return ep, bp, dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))


def _engine(llama, **kw):
    cfg, params = llama
    if kw.get("speculative"):
        params = _echo(params)
    kw = {"num_slots": 2, "page_size": 8, "num_pages": 64,
          "attention_impl": "ref", "prompt_bucket": 8, "decode_horizon": 4,
          **kw}
    return ServingEngine(params, cfg, **kw)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, (n,)).astype(np.int32) for n in lens]


def _serve(eng, lens, max_new=6, seed=0):
    rids = [eng.submit(p, max_new_tokens=max_new)
            for p in _prompts(lens, seed)]
    done = eng.run()
    return [list(done[r].generated) for r in rids]


# ---------------------------------------------------------------------------
# the real profiler
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [
    dict(prefill_chunk=8),
    dict(prefill_chunk=8, overlap=True),
    dict(speculative=2),
], ids=["sync", "overlap", "speculative"])
def test_real_profiler_trace_holds_tiled_steps(llama, tmp_path, kw):
    eng = _engine(llama, **kw)
    lens = [13, 5, 21]
    _serve(eng, lens)                    # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0         # as benchmark's Recording
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        # a repeating prompt gives the n-gram drafter something to propose
        rids = [eng.submit(np.tile(p[:4], 4), max_new_tokens=6)
                for p in _prompts(lens, seed=3)]
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert eng.telemetry is None         # spans need no Telemetry

    lines = host_spans.load(str(tmp_path))
    spans = host_spans.engine_line(lines)
    names = {s[0] for s in spans}
    steps = [s for s in spans if s[0] == host_spans.ROOT]
    assert steps and names <= VOCABULARY, names - VOCABULARY
    assert {"serve.sched", "serve.provision"} <= names
    pre = "verify" if kw.get("speculative") else \
        "overlap" if kw.get("overlap") else "decode"
    assert {f"serve.{pre}_dispatch", f"serve.{pre}_sync",
            f"serve.{pre}_record"} <= names
    # step numbers count up; one request's spans carry its rid
    seq = [s[3]["step"] for s in sorted(steps, key=lambda s: s[1])]
    assert seq == list(range(seq[0], seq[0] + len(seq)))
    per_request = [s for s in spans if s[0].startswith("serve.prefill_")
                   or s[0] == "serve.first_token_sync"]
    assert per_request and all(s[3]["rid"] in rids for s in per_request)
    for s in spans:
        if s[0].startswith("serve.prefill_"):
            assert 0 < s[3]["tokens"] <= s[3]["padded"]
            # the pages the call's K/V rows lie on (PR 32): one more than
            # the rows fill when the run starts inside a page
            full = -(-s[3]["tokens"] // eng.page_size)
            assert full <= s[3]["pages"] <= full + 1
        if s[0].endswith("_record"):
            assert s[3]["tokens"] >= 0
        if s[0].endswith("_dispatch"):
            assert s[3]["slots"] >= 1 and s[3]["k"] >= 1
    # every other span lies inside a step, and the children tile the steps:
    # what a step does under no child is a hole, and holes stay small
    for name, start, dur, _ in spans:
        if name != host_spans.ROOT:
            assert any(a <= start and start + dur <= a + d
                       for _, a, d, _ in steps), name
    tl = host_spans.timeline(spans)
    assert all(a[1] <= b[0] for a, b in zip(tl, tl[1:]))      # disjoint
    total = sum(d for _, _, d, _ in steps)
    assert sum(t1 - t0 for t0, t1, _ in tl) == total          # covers them
    holes = sum(t1 - t0 for t0, t1, label in tl
                if label == "holes:serve.step")
    assert holes < 0.1 * total, (holes, total)


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation."""
    entered, left = [], []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        _Recorder.entered.append(self)
        return self

    def __exit__(self, *exc):
        _Recorder.left.append(self.name)
        return False

    def set_metadata(self, **attrs):
        self.attrs.update(attrs)


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.entered, _Recorder.left = [], []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    return _Recorder


def test_span_closes_on_error_and_skips_the_telemetry_phase(llama, recorder):
    tel = Telemetry()
    eng = _engine(llama, telemetry=tel)
    with pytest.raises(RuntimeError):
        with eng._span("decode_sync"):
            raise RuntimeError("device lost")
    assert recorder.left == ["serve.decode_sync"]
    assert tel.utilization_report()["per_phase"] == {}
    with eng._span("decode_record") as late:
        late["tokens"] = 3
    assert recorder.entered[-1].attrs == {"tokens": 3}
    assert list(tel.utilization_report()["per_phase"]) == ["decode_record"]
    # a span outside the telemetry vocabulary is an annotation and no phase
    with eng._span("provision"):
        pass
    assert recorder.left[-1] == "serve.provision"
    assert list(tel.utilization_report()["per_phase"]) == ["decode_record"]


# ---------------------------------------------------------------------------
# counters where the work happens
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw,lens", [
    (dict(), [5, 13, 9]),                          # dense prefill only
    (dict(prefill_chunk=8), [5, 13, 21]),          # chunks of 8
    (dict(prefill_chunk=8), [17, 17, 17]),         # the same length thrice
], ids=["dense", "chunked", "chunked-equal"])
def test_prefill_tokens_dispatched_is_the_uncached_prompt_tokens(llama, kw,
                                                                 lens):
    eng = _engine(llama, prefix_cache=False, **kw)
    _serve(eng, lens)
    st = eng.stats()
    assert st["prefill_tokens_dispatched"] == sum(lens) \
        == st["prefill_tokens_executed"]
    bucket, chunk = 8, kw.get("prefill_chunk")
    pad = lambda n: -(-n // bucket) * bucket
    want = sum(pad(n) if not chunk or n <= chunk else
               sum(pad(min(chunk, n - p)) for p in range(0, n, chunk))
               for n in lens)
    assert st["prefill_tokens_padded"] == want >= sum(lens)


def test_prefill_tokens_dispatched_leaves_out_cached_prefixes(llama):
    eng = _engine(llama, prefill_chunk=8)
    p = _prompts([24])[0]
    for _ in range(2):                   # the second run hits the cache
        eng.submit(p, max_new_tokens=3)
        eng.run()
    st = eng.stats()
    assert st["cached_prefix_tokens"] > 0
    assert st["prefill_tokens_dispatched"] == st["prefill_tokens_executed"] \
        == 2 * len(p) - st["cached_prefix_tokens"]
    assert st["prefill_tokens_dispatched"] <= st["prefill_tokens_padded"]


@pytest.mark.parametrize("horizon", [1, 4])
def test_decode_kv_tokens_attended_closed_form(llama, horizon):
    """Two requests: prompt p, n new tokens.  Prefill makes the first
    token; decode step j = 1..n-1 attends the p + j positions written so
    far (its own row included), however the steps fall into horizons."""
    eng = _engine(llama, decode_horizon=horizon)
    lens, news = [5, 11], [6, 9]
    for p, n in zip(_prompts(lens), news):
        eng.submit(p, max_new_tokens=n)
    eng.run()
    want = sum((n - 1) * p + (n - 1) * n // 2 for p, n in zip(lens, news))
    assert eng.stats()["decode_kv_tokens_attended"] == want
    assert eng.stats()["tokens_generated"] == sum(news)


@pytest.mark.parametrize("page_size", [4, 8])
@pytest.mark.parametrize("horizon", [1, 4])
def test_decode_kv_pages_attended_closed_form(llama, horizon, page_size):
    """The same two requests: decode step j attends the
    ceil((p + j) / page_size) pages that hold its p + j positions — what
    the ragged kernel's page loop walks, crossing page boundaries inside
    and between horizons — and never more than the tokens, never less than
    tokens / page_size."""
    eng = _engine(llama, decode_horizon=horizon, page_size=page_size)
    lens, news = [5, 11], [6, 9]
    for p, n in zip(_prompts(lens), news):
        eng.submit(p, max_new_tokens=n)
    eng.run()
    want = sum(-(-(p + j) // page_size)
               for p, n in zip(lens, news) for j in range(1, n))
    st = eng.stats()
    assert st["decode_kv_pages_attended"] == want
    assert st["decode_kv_tokens_attended"] / page_size <= want \
        <= st["decode_kv_tokens_attended"]


@pytest.mark.parametrize("kw", [dict(prefill_chunk=8),
                                dict(prefill_chunk=8, overlap=True)],
                         ids=["sync", "overlap"])
def test_telemetry_on_and_off_give_equal_tokens_and_counters(llama, kw):
    lens = [13, 5, 21, 9]
    off, on = _engine(llama, **kw), _engine(llama, telemetry=True, **kw)
    assert _serve(off, lens) == _serve(on, lens)
    assert off.telemetry is None
    drop = ("jit_cache_misses",)
    a, b = off.stats(), on.stats()
    assert {k: v for k, v in a.items() if k not in drop} \
        == {k: v for k, v in b.items() if k not in drop}
    for key in ("prefill_tokens_dispatched", "prefill_tokens_padded",
                "prefill_kv_rows_written", "prefill_kv_pages_written",
                "decode_kv_tokens_attended", "decode_kv_pages_attended"):
        assert a[key] > 0


def test_counters_survive_a_snapshot(llama):
    eng = _engine(llama, prefill_chunk=8)
    _serve(eng, [13, 5])
    fresh = _engine(llama, prefill_chunk=8)
    fresh.restore(eng.snapshot())
    for key in ("prefill_tokens_dispatched", "prefill_tokens_padded",
                "prefill_kv_rows_written", "prefill_kv_pages_written",
                "decode_kv_tokens_attended", "decode_kv_pages_attended"):
        assert fresh.stats()[key] == eng.stats()[key] > 0


# ---------------------------------------------------------------------------
# idle time laid over host spans (hand-made lists)
# ---------------------------------------------------------------------------
def _op(start, dur):
    return ("%fusion = f32[8]{0} fusion()", start, dur)


SPANS = [("serve.step", 100, 900, {"step": 1}),
         ("serve.sched", 110, 290, {}),
         ("serve.prefill_dense", 200, 100, {"rid": 7}),
         ("serve.decode_dispatch", 420, 180, {}),
         ("serve.decode_sync", 600, 300, {}),
         ("serve.decode_record", 900, 90, {}),
         ("serve.step", 1200, 300, {"step": 2}),
         ("serve.sched", 1200, 300, {})]


@pytest.mark.parametrize("idle,want", [
    # a gap split across two spans (and the hole between them)
    ([(350, 450)], {"serve.sched": 50, "holes:serve.step": 20,
                    "serve.decode_dispatch": 30}),
    # a gap outside any step
    ([(1010, 1190)], {"outside:serve.step": 180}),
    # a hole inside a step: after the last child, before the step ends
    ([(990, 1000)], {"holes:serve.step": 10}),
    # the innermost span wins over its parent
    ([(150, 250)], {"serve.sched": 50, "serve.prefill_dense": 50}),
    # one gap over the end of a step, the caller, and the next step
    ([(950, 1300)], {"serve.decode_record": 40, "holes:serve.step": 10,
                     "outside:serve.step": 200, "serve.sched": 100}),
    # idle before the first step was entered
    ([(0, 100)], {"outside:serve.step": 100}),
], ids=["split", "outside", "hole", "innermost", "across", "before"])
def test_idle_by_span_on_hand_made_lists(idle, want):
    got = host_spans.idle_by_span(idle, SPANS)
    assert got == pytest.approx({k: v / 1e9 for k, v in want.items()})
    total = sum(b - a for a, b in idle) / 1e9
    assert sum(got.values()) == pytest.approx(total)
    under = host_spans.idle_under(got, "serve.step")
    assert under == pytest.approx(
        total - got.get("outside:serve.step", 0.0))
    assert host_spans.top(got)[0][1] == max(got.values())


def test_no_spans_is_nothing_to_read_not_zero():
    assert host_spans.idle_by_span([(0, 100)], []) == {}
    assert host_spans.idle_under({}, "serve.step") is None
    assert host_spans.idle_under({}, "outside:serve.step") is None
    assert host_spans.engine_line({}) == []
    # spans of another vocabulary hold no root either
    assert host_spans.idle_by_span([(0, 100)],
                                   [("serve.sched", 0, 50, {})]) == {}


def test_idle_intervals_and_engine_line():
    ops = [_op(100, 50), _op(120, 10), _op(200, 50), _op(250, 10)]
    assert host_spans.idle_intervals(ops) == [(150, 200)]
    assert host_spans.idle_intervals(ops, lo=40, hi=300) \
        == [(40, 100), (150, 200), (260, 300)]
    assert host_spans.idle_intervals([], lo=0, hi=9) == [(0, 9)]
    lines = {"python3": [("serve.cancel", 0, 5, {})],
             "main/123": SPANS, "worker": [("serve.step", 0, 1, {})]}
    assert host_spans.engine_line(lines) is SPANS


def test_clock_margins_tell_one_clock_from_two():
    modules = [("jit_decode_horizon(123)", 450, 400),
               ("jit__lambda(9)", 210, 80)]
    ok = host_spans.clock_margins(modules, SPANS, r"^jit_decode_horizon\(")
    assert ok == {"runs": 1, "min_start_margin_ns": 30,
                  "min_end_margin_ns": 50}
    late = [(n, s - 100, d) for n, s, d in modules]     # a skewed clock
    assert host_spans.clock_margins(
        late, SPANS, r"^jit_decode_horizon\(")["min_start_margin_ns"] < 0
    assert host_spans.clock_margins(modules, [], r"^jit_") is None
