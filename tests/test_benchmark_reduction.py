"""The benchmark's trace reduction, guarded by tier-1.

Every per-layer number on a ledger line is a profiler trace read through
``benchmark/trace_reduce.py``, ``readers.py`` and ``host_spans.py``.  Their
tests need no chip and no model (hand-made event lists and the head of one
real trace), but they live under ``benchmark/tests``, which tier-1 does
not collect.  This module collects them from here: the two files are loaded
by path (``benchmark/tests`` is no package; each puts the repo root on
``sys.path`` itself) and their ``test_*`` functions, parametrisation
included, become this module's — no line is copied and nothing under
``benchmark/`` changes.  The two rehearsal files stay outside: they run the
drivers, and ``test_rehearsal.py`` fails on ``train_afmoe`` on a line only
a ``benchmark`` PR may change (PERF.md section 7).
"""
import importlib.util
import os

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests")


def _cases(filename):
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + filename[:-3], os.path.join(_DIR, filename))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {name: obj for name, obj in vars(module).items()
            if name.startswith("test_")}


for _file in ("test_trace_reduce.py", "test_host_spans.py",
              "test_regions.py"):
    _found = _cases(_file)
    # a name both files use would shadow a case without a word
    assert _found and not set(_found) & set(globals()), _file
    globals().update(_found)
