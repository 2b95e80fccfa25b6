"""Device time by what the PROGRAM called the work.

A device operation's event name is its whole HLO instruction, frontend
attributes included, and the program writes three kinds of name there:

  ``pt_region="<name>"``      `paddle_tpu.profiler.device_span`: the region of
                              model code the operation was traced under (a
                              fusion carries its root's; a backward operation
                              its forward's; an inner region replaces the
                              outer one);
  ``kernel_metadata={"kernel": ..., "role" | "pass": ...}``
                              a Pallas kernel of ours;
  ``ragged_dot_tiling=``      XLA's own grouped-matmul kernel.

``by_region`` splits the busy time by the first of these an operation has,
``unlabelled`` where it has none: operations XLA made by itself (copies,
layout changes, the loop plumbing of a ``while``) and program code no region
covers.  Times are SELF times (`trace_reduce.self_ns`): a ``while`` or a
``conditional`` keeps only what its children do not cover, so the values add
up to the busy union.  An event here is ``(label, start_ns, duration_ns)`` as
`trace_reduce.load` gives them on the ``XLA Ops`` line.  Pure: runs on
hand-made lists.
"""
import re

from benchmark import trace_reduce

REGION = re.compile(r'pt_region="([^"]+)"')
KERNEL = re.compile(r'kernel_metadata=\{\s*"kernel":\s*"([^"]+)"')
ROLE = re.compile(r'"(?:role|pass)":\s*"([^"]+)"')
RAGGED_DOT = "ragged_dot_tiling="
UNLABELLED = "unlabelled"


def label_of(name):
    """The program's name for the operation whose event is named ``name``."""
    region = REGION.search(name)
    if region:
        return region[1]
    kernel = KERNEL.search(name)
    if kernel:
        role = ROLE.search(name)
        return f"kernel:{kernel[1]}" + (f"/{role[1]}" if role else "")
    return "ragged_dot" if RAGGED_DOT in name else UNLABELLED


def by_region(ops):
    """{label: seconds of self time}, the largest first."""
    out = {}
    for name, ns in trace_reduce.self_ns(ops).items():
        label = label_of(name)
        out[label] = out.get(label, 0.0) + ns / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def top_ops(ops, n=10, only=None):
    """The ``n`` operations with the most self time as ``[short name, label,
    seconds]``; ``only``: those of that label."""
    rows = [[trace_reduce.short(name), label_of(name), ns / 1e9]
            for name, ns in trace_reduce.self_ns(ops).items()]
    if only is not None:
        rows = [r for r in rows if r[1] == only]
    return sorted(rows, key=lambda r: -r[2])[:n]


def top_unlabelled(ops, n=10):
    """The ``n`` longest operations no label covers: ``[short name,
    seconds]``."""
    return [[name, s] for name, _, s in top_ops(ops, n, only=UNLABELLED)]

