"""The host half of a profiler trace: the program's ``serve.*`` annotations
(``jax.profiler.TraceAnnotation``, plane ``/host:CPU``, one line per thread)
and what they say about the device's idle time.  ``trace_reduce.py`` reads
the device planes and names an idle gap by the operation that ENDED it; this
module names it by what the host was doing meanwhile.

A span here is ``(name, start_ns, duration_ns, stats)``; the host events are
on the clock the device events are on (checked on the chip by
``tools/span_probe.py``: ``clock_margins``).  The engine's thread is the
line that holds ``serve.step`` events — its name differs between runtimes
(``python3``, ``main/<tid>``).  On that line spans nest by containment:
``serve.step`` > ``serve.sched`` > ``serve.prefill_dense`` ...

Idle time is split three ways around a ROOT span (``serve.step``):
  ``<innermost span name>``  idle under that span (a child of the root);
  ``holes:<root>``           idle inside a root span but under no child —
                             host work the program has not named;
  ``outside:<root>``         idle under no root span: the caller of
                             ``step()`` (a load generator, a front end).
A program without such spans (an earlier commit) gives no spans, and every
function below then returns nothing rather than zero.

Everything below ``load`` is pure and runs on hand-made lists.
"""
import glob
import os
import re

HOST_PLANE = "/host:CPU"
PREFIX = "serve."
ROOT = "serve.step"


def load(logdir, prefix=PREFIX):
    """{line name: [span, ...]} of the host plane of the newest trace under
    ``logdir``: per thread line the events whose name starts with
    ``prefix``, with their stats; lines without one are left out."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    lines = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns), int(e.duration_ns),
                      {k: v for k, v in e.stats})
                     for e in line.events if e.name.startswith(prefix)]
            if spans:
                lines.setdefault(line.name, []).extend(spans)
    return lines


def engine_line(lines, root=ROOT):
    """The spans of the thread that ran the engine: the line with the most
    ``root`` events ([] where no line has one)."""
    best = max(lines.values(), default=[],
               key=lambda spans: sum(1 for s in spans if s[0] == root))
    return best if any(s[0] == root for s in best) else []


def idle_intervals(ops, lo=None, hi=None):
    """[(start_ns, end_ns)] in which no device operation ran: the gaps
    between consecutive events of ``ops`` (as ``trace_reduce.gaps`` finds
    them) and, where given, the lead-in from ``lo`` to the first operation
    and the tail from the last one to ``hi``."""
    out, end = [], lo
    for _, start, dur in sorted(ops, key=lambda e: e[1]):
        if end is not None and start > end:
            out.append((end, start))
        end = max(end if end is not None else start, start + dur)
    if hi is not None and end is not None and hi > end:
        out.append((end, hi))
    return out


def timeline(spans, root=ROOT):
    """The time inside ``root`` spans cut into [(t0, t1, label)], sorted and
    disjoint: label is the innermost span's name, or ``holes:<root>`` where
    the root has no child.  Spans must nest (one thread)."""
    out, stack = [], []                 # stack of [name, end]
    cursor = 0

    def emit(upto):
        nonlocal cursor
        if stack and upto > cursor and any(n == root for n, _ in stack):
            name = stack[-1][0]
            out.append((cursor, upto,
                        f"holes:{root}" if name == root else name))
        cursor = max(cursor, upto)

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        stack.append([name, start + dur])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def idle_by_span(idle, spans, root=ROOT):
    """{label: seconds} of the idle intervals laid over ``timeline(spans)``;
    what lies under no root span is ``outside:<root>``.  {} without spans:
    there is then nothing to attribute, which is not the same as zero."""
    segs = timeline(spans, root)
    if not segs:
        return {}
    out, i = {}, 0
    outside = f"outside:{root}"
    for a, b in sorted(idle):
        covered = 0
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            t0, t1, label = segs[j]
            part = min(b, t1) - max(a, t0)
            if part > 0:
                out[label] = out.get(label, 0) + part
                covered += part
            j += 1
        if b - a > covered:
            out[outside] = out.get(outside, 0) + (b - a) - covered
    return {k: v / 1e9 for k, v in out.items()}


def idle_under(by_span, what, root=ROOT):
    """One number out of ``idle_by_span``'s result — the trace term a
    ``quotient`` metric would name: ``"<root>"`` (all idle under root
    spans, holes included), ``"outside:<root>"``, ``"holes:<root>"`` or one
    span name.  None where there were no spans."""
    if not by_span:
        return None
    if what == root:
        return sum(v for k, v in by_span.items() if k != f"outside:{root}")
    return by_span.get(what, 0.0)


def top(by_span, n=10):
    """``[[label, seconds], ...]``, longest first: the shape of
    ``Trace.breakdown()``'s other two keys."""
    return [[k, v] for k, v in
            sorted(by_span.items(), key=lambda kv: -kv[1])[:n]]


def clock_margins(modules, spans, pattern, opens="serve.decode_dispatch",
                  closes="serve.decode_sync"):
    """Are the host spans and the device events on ONE clock?  For every
    device module run whose name matches ``pattern`` (the decode horizon):
    its start minus the start of the ``opens`` span nearest to it (nearest,
    not last-before: on two clocks the module may seem to start first), and
    the end of the first ``closes`` span after that span minus the module's
    end.  Both are >= 0 on one clock: the executable cannot start before
    the host issues it nor end after the host has its result.  Returns
    {"runs", "min_start_margin_ns", "min_end_margin_ns"}, or None where
    nothing could be paired."""
    rx = re.compile(pattern)
    o = [s for s in spans if s[0] == opens]
    c = sorted((s for s in spans if s[0] == closes), key=lambda s: s[1])
    starts, ends = [], []
    for name, start, dur in modules:
        if not rx.search(name) or not o:
            continue
        disp = min(o, key=lambda s: abs(s[1] - start))
        sync = next((s for s in c if s[1] >= disp[1]), None)
        if sync is None:
            continue
        starts.append(start - disp[1])
        ends.append(sync[1] + sync[2] - (start + dur))
    if not starts:
        return None
    return {"runs": len(starts), "min_start_margin_ns": min(starts),
            "min_end_margin_ns": min(ends)}
