"""Profiler (reference: python/paddle/profiler/ — Profiler profiler.py:358
with scheduler states, RecordEvent event_tracing.h, timer.py throughput).

TPU-native: device tracing delegates to jax.profiler (XPlane → TensorBoard /
perfetto, the CUPTI-chrome-trace analog); host annotations map RecordEvent →
jax.profiler.TraceAnnotation + named_scope so they appear in the same trace.
The benchmark `Timer` reproduces timer.py's ips accounting.
"""
from __future__ import annotations

import contextlib
import time
from enum import Enum
from typing import Callable, Iterable, Optional

import jax
from jax.experimental.xla_metadata import set_xla_metadata

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "device_span", "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "benchmark", "Timer", "SummaryView"]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """reference scheduler_fn: maps step -> ProfilerState."""
    period = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


REGION_KEY = "pt_region"


@contextlib.contextmanager
def device_span(name: str):
    """Name a region of a COMPILED program: every operation traced inside
    carries the frontend attribute ``pt_region="<name>"`` and the
    ``jax.named_scope`` ``name``.  A device trace names an "XLA Ops" event by
    its instruction's whole HLO text, frontend attributes included, so the
    trace splits by region (``benchmark/regions.py``); ``op_name`` in the
    compiled text carries the scope.  A fusion takes its root's label; the
    backward operations of a labelled forward carry the forward's label; an
    inner span replaces the outer one.  The label is compile-time text: it
    costs nothing at run time and there is nothing to switch on.  Names are
    lower case (jax lower-cases an attribute's value) and a contract: the
    benchmark's metric files match them."""
    with set_xla_metadata(**{REGION_KEY: name}), jax.named_scope(name):
        yield


class RecordEvent:
    """Host annotation (reference phi/api/profiler/event_tracing.h RecordEvent)."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ctx = None

    def begin(self):
        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()

    def end(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """reference profiler.py:358."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, emit_nvtx=False):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0], closed=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else (lambda step: ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._step = 0
        self._dir = "/tmp/paddle_tpu_profile"
        self._active = False
        self.timer = Timer()
        # eager per-op events collected via the dispatch hook:
        # (name, t_start_s, dur_s, out_shapes)
        self._op_events = []

    def _attach_op_timer(self):
        from ..core import dispatch as _dispatch
        _dispatch._op_timer[0] = self._op_events

    def _detach_op_timer(self):
        from ..core import dispatch as _dispatch
        if _dispatch._op_timer[0] is self._op_events:
            _dispatch._op_timer[0] = None

    def start(self):
        self.timer.begin()
        if self._timer_only:
            return
        state = self._scheduler(self._step)
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            try:
                jax.profiler.start_trace(self._dir)
            except Exception:
                pass  # a second concurrent device trace is a host-only run
            self._active = True
            self._attach_op_timer()

    def stop(self):
        if self._active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._active = False
            self._detach_op_timer()
            if self._on_trace_ready:
                self._on_trace_ready(self)

    def step(self, num_samples=None):
        self.timer.step(num_samples)
        if self._timer_only:
            self._step += 1
            return
        prev = self._scheduler(self._step)
        self._step += 1
        cur = self._scheduler(self._step)
        if prev in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) and \
                cur in (ProfilerState.CLOSED, ProfilerState.READY):
            if self._active:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._active = False
                self._detach_op_timer()
        elif cur in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) and \
                not self._active:
            try:
                jax.profiler.start_trace(self._dir)
            except Exception:
                pass
            self._active = True
            self._attach_op_timer()

    def step_info(self, unit="samples"):
        return self.timer.step_info(unit)

    def _op_stats(self):
        """Aggregate eager op events -> {name: [count, total_s, min, max]}."""
        agg = {}
        for name, _t0, dur, _shapes in self._op_events:
            e = agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            e[0] += 1
            e[1] += dur
            e[2] = min(e[2], dur)
            e[3] = max(e[3], dur)
        return agg

    def _device_op_stats(self):
        """Per-op SELF times from the newest jax XPlane chrome trace under
        self._dir (jit workloads: the eager hook sees only staged tracing,
        the device trace has the real kernel times). Returns the same
        aggregate mapping or {} when no trace exists."""
        import glob
        import gzip
        import json as _json
        import re
        files = sorted(glob.glob(
            f"{self._dir}/**/*.trace.json.gz", recursive=True))
        if not files:
            return {}
        try:
            with gzip.open(files[-1]) as f:
                data = _json.load(f)
        except Exception:
            return {}
        meta = {}
        for e in data.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "thread_name":
                meta[(e.get("pid"), e.get("tid"))] = e["args"].get("name")
        evs = [e for e in data.get("traceEvents", [])
               if e.get("ph") == "X"
               and meta.get((e.get("pid"), e.get("tid"))) == "XLA Ops"]
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        agg = {}
        stack = []
        for e in evs:
            ts, dur = e["ts"], e["dur"]
            name = re.sub(r"[.\d]+$", "", e["name"])
            while stack and stack[-1][1] <= ts:
                stack.pop()
            if stack:
                agg[stack[-1][2]][1] -= dur / 1e6
            en = agg.setdefault(name, [0, 0.0, float("inf"), 0.0])
            en[0] += 1
            en[1] += dur / 1e6
            en[2] = min(en[2], dur / 1e6)
            en[3] = max(en[3], dur / 1e6)
            stack.append((ts, ts + dur, name))
        return agg

    @staticmethod
    def _format_table(title, agg, unit_div):
        total = sum(e[1] for e in agg.values()) or 1e-12
        lines = [title,
                 f"{'Name':<40}{'Calls':>8}{'Total':>12}{'Avg':>12}"
                 f"{'Min':>12}{'Max':>12}{'Ratio %':>9}"]
        for name, (cnt, tot, mn, mx) in sorted(
                agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(
                f"{name[:39]:<40}{cnt:>8}"
                f"{tot / unit_div:>12.4f}{tot / cnt / unit_div:>12.4f}"
                f"{mn / unit_div:>12.4f}{mx / unit_div:>12.4f}"
                f"{100 * tot / total:>8.1f}%")
        return "\n".join(lines)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Operator / kernel statistics tables (reference
        profiler_statistic.py:1 OperatorView + DeviceView): eager per-op
        host times from the dispatch hook, plus device-kernel self-times
        parsed from the jax trace when one was captured."""
        div = {"s": 1.0, "ms": 1e-3, "us": 1e-6}.get(time_unit, 1e-3)
        parts = []
        op_agg = self._op_stats()
        if op_agg:
            parts.append(self._format_table(
                f"-- Operator Summary (host, {time_unit}) --", op_agg, div))
        dev_agg = self._device_op_stats()
        if dev_agg:
            parts.append(self._format_table(
                f"-- Device Kernel Summary (self time, {time_unit}) --",
                dev_agg, div))
        parts.append(f"-- Benchmark: {self.timer.step_info()} --")
        if not op_agg and not dev_agg:
            parts.append("(no events recorded; XPlane trace dir: "
                         + self._dir + ")")
        return "\n\n".join(parts)

    def export(self, path, format="json"):
        """Write the collected events as a chrome://tracing-loadable JSON
        (reference chrometracing_logger.cc)."""
        import json as _json
        if format != "json":
            raise ValueError(f"unsupported export format {format!r}")
        events = [{"name": "process_name", "ph": "M", "pid": 0,
                   "args": {"name": "paddle_tpu eager ops"}}]
        for name, t0, dur, shapes in self._op_events:
            events.append({
                "name": name, "ph": "X", "cat": "operator",
                "pid": 0, "tid": 0,
                "ts": round(t0 * 1e6, 3), "dur": round(dur * 1e6, 3),
                "args": {"output_shapes": [list(s) for s in shapes]},
            })
        with open(path, "w") as f:
            _json.dump({"traceEvents": events,
                        "displayTimeUnit": "ms"}, f)
        return path

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof._dir = dir_name
    return handler


def load_profiler_result(path):
    return None


class Timer:
    """Throughput benchmark (reference python/paddle/profiler/timer.py):
    tracks step latency + ips with warmup skipping."""

    def __init__(self, skip_steps=10):
        self.skip = skip_steps
        self.reset()

    def reset(self):
        self._count = 0
        self._total_time = 0.0
        self._total_samples = 0
        self._last = None
        self._step_time = 0.0

    def begin(self):
        self._last = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            dt = now - self._last
            self._step_time = dt
            self._count += 1
            if self._count > self.skip:
                self._total_time += dt
                if num_samples:
                    self._total_samples += num_samples
        self._last = now

    @property
    def ips(self):
        if self._total_time <= 0:
            return 0.0
        n = self._count - self.skip
        if self._total_samples:
            return self._total_samples / self._total_time
        return n / self._total_time

    @property
    def avg_step_time(self):
        n = max(self._count - self.skip, 1)
        return self._total_time / n if self._total_time else self._step_time

    def step_info(self, unit="samples"):
        return (f"avg_step_time: {self.avg_step_time * 1000:.2f} ms, "
                f"ips: {self.ips:.2f} {unit}/s")


class benchmark:
    """`paddle.profiler.benchmark()` style helper."""

    def __init__(self):
        self.timer = Timer()

    def begin(self):
        self.timer.begin()

    def step(self, num_samples=None):
        self.timer.step(num_samples)

    def end(self):
        pass

    def step_info(self, unit="samples"):
        return self.timer.step_info(unit)
