#!/usr/bin/env python3
"""Show on the chip that the SambaY cell's check can fail, and by how much.
Every arm prints one JSON line with every reading of the check beside its
limit (`drivers/serve_sambay.judge`):

  honest        the engine's check prompts against the reference as it is;
  the REFERENCE reading a deliberately wrong model, against the SAME engine
  outputs: ``bf16_reference`` (everything, the state too, in bfloat16 at the
  default matmul precision: the nearest precision below the stated one),
  ``window_511`` / ``window_513`` (a key fewer, a key more), ``lam0_next``
  (lam0 of the next layer's index), ``m_after_gate`` (the memory taken after
  silu(z)), ``bf16_scan_operands`` (a float32 state updated from u, dt, B, C
  rounded to bfloat16: at the published widths this arm PASSES — no limit
  sees it, `reference_sambay.py` and PERF.md section 7 say why; its
  ``operand_ratio`` reads exactly 1).

    python3 benchmark/tools/wrong_model_sambay.py --workload <cell>
        [--seeds 1,2] [--arms honest,bf16_reference,...]

One engine run a seed serves every arm.  Not a benchmark run: nothing is
timed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run                                    # noqa: E402


def reference_faults(conf):
    window = int(conf["sliding_window"])
    return {
        "honest": None,
        "bf16_reference": {"dtype": "bfloat16"},
        "window_511": {"window": window - 1},
        "window_513": {"window": window + 1},
        "lam0_next": {"lam0_shift": 1},
        "m_after_gate": {"m_after_gate": True},
        "bf16_scan_operands": {"round_scan_operands": True},
    }


def arms_of(conf, seed, names, devices, **engine_kw):
    """[(arm, passes, facts)] for one seed: ONE honest engine run judged
    against every reference arm."""
    import gc
    import jax.numpy as jnp
    from benchmark.drivers import serve_sambay as drv
    gc.collect()            # the last seed's weights and pool, before these
    cfg = drv.model_config(conf)
    params = drv.build_params(cfg, seed, jnp.dtype(conf["torch_dtype"]))
    eng = drv.build_engine(params, cfg, conf, devices, **engine_kw)
    got = drv.run_check_prompts(eng, cfg, conf, seed)
    del eng
    faults = reference_faults(conf)
    return [(arm,) + drv.judge(params, conf, got, faults[arm])
            for arm in names]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--arms", default=None)
    args = ap.parse_args()
    _, conf, *_ = run.load_cell(ROOT, args.workload)
    arms = args.arms.split(",") if args.arms else list(reference_faults(conf))
    import jax
    from paddle_tpu.core.device import setup_compile_cache
    setup_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for arm, ok, facts in arms_of(conf, seed, arms, jax.devices()):
            print(json.dumps({"cell": args.workload, "seed": seed, "arm": arm,
                              "passes": bool(ok), **facts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
