"""From a profiler trace (``.xplane.pb``) to seconds: device busy time (the
union of the intervals in which an operation ran), time per operation name
(self time: a ``while`` does not count its body twice), and time of the
events whose label matches a regular expression.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.

An event here is ``(label, start_ns, duration_ns)``.  On this TPU runtime
(looked at by hand, PR 25) an operation's event name is its whole HLO
instruction, ``%closed_call.16 = bf16[16,8,8,128]{...} custom-call(s32[16,32]
..., custom_call_target="tpu_custom_call", ...``, with no JAX name stack among
its stats, and a module's is ``jit_<function>(<fingerprint>)``.  The label is
that name whole, so a regular expression can match the opcode, the custom-call
target and the operand shapes; ``short()`` cuts it for a breakdown.
Device planes are those named ``/device:TPU:<n>``; host planes are ignored.
The functions below the loader are pure and run on hand-made event lists.
"""
import bisect
import glob
import os
import re
import shutil
import tempfile
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"


def load(logdir):
    """{plane name: {line name: [event, ...]}} of the device planes of the
    newest trace under ``logdir`` (as ``jax.profiler.start_trace`` wrote
    it), keeping the two lines the metrics read."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    planes = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (MODULES, OPS):
                lines[line.name] = [_event(e) for e in line.events]
        planes[plane.name] = lines
    return planes


def _event(e):
    return (e.name, int(e.start_ns), int(e.duration_ns))


_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<shape>\(?[a-z0-9]+\[[0-9,]*\])?"
                  r"[^ ]* ?.*? (?P<op>[a-z\-]+)\(")


def short(label):
    """``%fusion.3 = bf16[16,4096]{...} fusion(...), kind=kLoop`` ->
    ``fusion.3 fusion bf16[16,4096]`` (plus the custom-call target)."""
    m = _HLO.match(label)
    if not m:
        return label.split(" = ")[0].lstrip("%")[:120]
    target = re.search(r'custom_call_target="([^"]+)"', label)
    parts = [m["name"], m["op"] + (f"[{target[1]}]" if target else ""),
             (m["shape"] or "").lstrip("(")]
    return " ".join(p for p in parts if p)[:120]


def union_ns(events):
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def self_ns(events):
    """{label: ns} where an event that encloses others (a ``while``, a
    ``conditional``, a call) keeps only the time its children do not cover,
    so that the values add up to the busy union."""
    out = {}
    stack = []                               # [label, stop, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            label, _, own = stack.pop()
            out[label] = out.get(label, 0) + max(own, 0)

    for label, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:                # only the part that lies inside the parent
            stack[-1][2] -= min(start + dur, stack[-1][1]) - start
        stack.append([label, start + dur, dur])
    close(float("inf"))
    return out


def matching_ns(events, pattern):
    """Union of the intervals of the events whose label matches ``pattern``
    (``re.search``)."""
    rx = re.compile(pattern)
    return union_ns([e for e in events if rx.search(e[0])])


def gaps(events, top=10):
    """The longest idle gaps between device operations, each named by the
    operation that ended it: [[label, seconds], ...]."""
    found, end = [], None
    for label, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            found.append((start - end, label))
        end = max(end or 0, start + dur)
    merged = {}
    for ns, label in found:
        key = "before " + short(label)
        merged[key] = merged.get(key, 0) + ns
    return [[k, v / 1e9] for k, v in
            sorted(merged.items(), key=lambda kv: -kv[1])[:top]]


def _any_in(sorted_starts, lo, hi):
    i = bisect.bisect_left(sorted_starts, lo)
    return i < len(sorted_starts) and sorted_starts[i] < hi


class Trace:
    """The reductions over every device plane of one traced window.
    Seconds are averaged over the planes (the chips used)."""

    def __init__(self, planes, window_s):
        if not planes:
            raise ValueError("the trace holds no /device:TPU:<n> plane")
        self.planes = planes
        self.window_s = float(window_s)

    def _mean(self, fn):
        vals = [fn(lines) for lines in self.planes.values()]
        return sum(vals) / len(vals)

    def busy_s(self):
        return self._mean(lambda ln: union_ns(ln.get(OPS, []))) / 1e9

    def idle_s(self):
        return max(self.window_s - self.busy_s(), 0.0)

    def matching_s(self, line, match, containing=None, not_containing=None):
        """Seconds of the events of ``line`` whose label matches ``match``.
        ``containing`` / ``not_containing`` (for the modules line): only
        module runs during which an operation matching that pattern ran /
        did not run — two executables of one name (``jit__lambda``) are told
        apart by what they execute."""
        def one(lines):
            events = lines.get(line, [])
            for rx, keep in ((containing, True), (not_containing, False)):
                if rx is not None:
                    starts = sorted(e[1] for e in lines.get(OPS, [])
                                    if re.search(rx, e[0]))
                    events = [e for e in events if keep == _any_in(
                        starts, e[1], e[1] + e[2])]
            return matching_ns(events, match)
        return self._mean(one) / 1e9

    def breakdown(self, top=10):
        """The device operations that took most (self) time and the longest
        idle gaps, on the first device plane."""
        lines = self.planes[sorted(self.planes)[0]]
        ops = {}
        for label, ns in self_ns(lines.get(OPS, [])).items():
            ops[short(label)] = ops.get(short(label), 0) + ns
        return {"device_ops": [[k, v / 1e9] for k, v in
                               sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
                "idle_gaps": gaps(lines.get(OPS, []), top)}


class Recording:
    """The profiler around the end of a window: ``start()`` when the traced
    part begins (the device idle), ``stop(t_end)`` after the window's last
    instant ``t_end`` (``time.perf_counter``) -> the ``Trace``.  The trace is
    written to a temporary directory, reduced here and deleted.  The Python
    tracer is off: it slows the host loop it would be measuring."""

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self, t_end):
        import jax
        jax.profiler.stop_trace()
        try:
            return Trace(load(self.dir), t_end - self.t0)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
