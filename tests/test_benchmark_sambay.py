"""The SambaY serve cell's driver, guarded by tier-1.

``benchmark/tests/test_rehearsal_sambay.py`` runs the cell's driver at a tiny
configuration on the CPU (the kernel interpreted), every planted fault of
``benchmark/tools/wrong_model_sambay.py`` against a toy's limits, and the
manifest's entries for the cell.  ``benchmark/tests`` is not collected by
tier-1, so this module collects that file's cases from here, as
``test_benchmark_mla_moe.py`` does for its family: the file is loaded by path
and its ``test_*`` functions and fixtures become this module's — no line is
copied.
"""
import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "test_rehearsal_sambay.py")
_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_rehearsal_sambay", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
_found = {name: obj for name, obj in vars(_module).items()
          if name.startswith("test_") or name in ("toy_limits", "arms")}
assert sum(name.startswith("test_") for name in _found) == 3, sorted(_found)
globals().update(_found)
