"""No fallback hides the device (ISSUE 22): a failing device probe raises
where it used to answer "cpu", the compile cache is placed from outside,
and a parent that spawns JAX workers never initialises a backend itself.
"""
import os
import subprocess
import sys

import pytest
import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def _no_devices(*_a, **_k):
    raise RuntimeError("Unable to initialize backend 'tpu'")


def test_auto_attention_impl_lets_a_device_error_through(monkeypatch):
    from paddle_tpu.models.llama import (build_llama_paged_decode,
                                         llama_config_tiny)
    monkeypatch.setattr(jax, "devices", _no_devices)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        build_llama_paged_decode(llama_config_tiny(), page_size=8,
                                 num_pages=4, attention_impl="auto")


def test_register_all_lets_a_device_error_through(monkeypatch):
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(pallas, "_registered", [False])
    monkeypatch.setattr(jax, "devices", _no_devices)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas.register_all()


def test_get_device_lets_a_backend_error_through(monkeypatch):
    import paddle_tpu as paddle
    monkeypatch.setattr(jax, "default_backend", _no_devices)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        paddle.get_device()


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              restore_cache_dir):
    from paddle_tpu.core.device import setup_compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # nothing set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert setup_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")


def test_only_the_helper_names_a_cache_path():
    """chip_smoke.py and serving/worker.py call the helper; no other code
    sets a compilation-cache directory."""
    hits = []
    for root in ("paddle_tpu", "perf"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in
             ("chip_smoke.py", "__graft_entry__.py")]
    setters = [os.path.relpath(p, REPO) for p in hits
               if "jax_compilation_cache_dir" in open(p).read()]
    assert setters == ["paddle_tpu/core/device.py"]
    for caller in ("chip_smoke.py", "paddle_tpu/serving/worker.py"):
        assert "setup_compile_cache()" in open(
            os.path.join(REPO, caller)).read(), caller


def test_process_fleet_supervisor_never_initialises_a_backend(tmp_path):
    """Importing procfleet / the launcher, building a ProcessFleet and
    serving through it leaves the PARENT without a JAX backend (a parent
    that holds the chip starves its workers), and every worker's hello
    says which platform it got — inherited from the environment, with no
    default injected by the supervisor."""
    code = """
import numpy as np
from jax._src import xla_bridge
import paddle_tpu.distributed.launch.main
from paddle_tpu.serving.procfleet import ProcessFleet
spec = {"seed": 1, "model": {"config": dict(vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=64), "prng_key": 1},
        "engine": dict(num_slots=2, page_size=4, num_pages=16,
                       attention_impl="ref", prompt_bucket=8,
                       decode_horizon=2)}
fleet = ProcessFleet(spec, num_workers=1, workdir=%r)
try:
    rid = fleet.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    done = fleet.run()
    assert len(done[rid].generated) == 3
    print("PLATFORM", fleet.stats()["per_worker"]["w0"]["platform"])
finally:
    fleet.shutdown()
assert not xla_bridge.backends_are_initialized(), "supervisor touched JAX"
print("SUPERVISOR_CLEAN")
""" % str(tmp_path)
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PLATFORM cpu" in out.stdout and "SUPERVISOR_CLEAN" in out.stdout
    # the workdir holds ports, specs, logs and snapshots — never the cache
    assert not any("jax_cache" in d or "jax_cache" in " ".join(fs)
                   for d, _, fs in os.walk(tmp_path))
