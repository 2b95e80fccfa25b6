"""The readers a per-layer metric file (``layer_metrics/<name>.json``) may
name.  A reader gets the run's context and the file's ``args`` and returns a
number, or None where there is nothing to read (the harness then leaves the
metric out of the line).

The context is a dict: ``facts`` (flat name -> number: counter differences
over the window, the sizes of the configuration, counts the driver made,
the end-to-end values of this run, the chip's published peaks),
``requests`` (one dict of seconds per request of the window) and ``trace``
(a ``trace_reduce.Trace`` or None).

A TERM of ``quotient`` is one of
  {"facts": ["a", "b", ...]}                    the product of those facts
  {"trace": "busy_s" | "idle_s" | "window_s"}   of the traced window
  {"trace": {"line": "XLA Ops" | "XLA Modules", "match": "<regex>",
             "containing": "<regex>", "not_containing": "<regex>"}}
                                  seconds of the matching events
                                  (trace_reduce.Trace.matching_s)
and may add  "times": [facts ...]  to multiply the term by facts.
"""
import statistics


def _term(ctx, term):
    value = 1.0
    for name in term.get("facts", []) + term.get("times", []):
        if ctx["facts"].get(name) is None:
            return None
        value *= ctx["facts"][name]
    if "trace" in term:
        trace, what = ctx.get("trace"), term["trace"]
        if trace is None:
            return None
        if isinstance(what, str):
            got = {"busy_s": trace.busy_s, "idle_s": trace.idle_s,
                   "window_s": lambda: trace.window_s}[what]()
        else:
            got = trace.matching_s(**what)
            if got == 0:
                return None              # no such event: nothing to read
        value *= got
    return value


def quotient(ctx, num, den, scale=1.0):
    n, d = _term(ctx, num), _term(ctx, den)
    if n is None or not d:
        return None
    return scale * n / d


def percentile(vals, q):
    """The q-th percentile (0 < q < 100; 50 = median) by
    ``statistics.quantiles(..., n=100)``'s default (exclusive) method."""
    return statistics.quantiles(vals, n=100)[int(q) - 1]


def request_percentile(ctx, field, q, scale=1.0):
    """A percentile of one field of the window's requests."""
    vals = [r[field] for r in ctx.get("requests", [])
            if r.get(field) is not None]
    if len(vals) < 2:
        return None
    return scale * percentile(vals, q)
