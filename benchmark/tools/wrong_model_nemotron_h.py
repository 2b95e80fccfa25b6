#!/usr/bin/env python3
"""Show on the chip that the Nemotron-H check can fail, and by how much.
Every arm prints one JSON line with every reading of the check beside its
limit (`drivers/serve_nemotron_h.judge`):

  honest        the engine's check prompts against the reference as it is;
  the REFERENCE reading a deliberately wrong model, against the SAME engine
  outputs: ``exchanged`` (layers 0 and 1 exchanged), ``no_d`` (the D x_t
  term dropped), ``no_conv_bias``, ``scale_1`` (routed_scaling_factor 1),
  ``bf16_reference`` (everything, the recurrent state too, in bfloat16: the
  nearest precision below the stated one);
  ``bf16_state``  the ENGINE keeping its SSM state in bfloat16 where the
  configuration states float32, against the reference as it is.

    python3 benchmark/tools/wrong_model_nemotron_h.py --workload <cell>
        [--seeds 1,2] [--arms honest,exchanged,...]

Not a benchmark run: nothing is timed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import run                                    # noqa: E402

REFERENCE_FAULTS = {
    "honest": None,
    "exchanged": "exchanged",            # made from the layer count below
    "no_d": {"drop_d": True},
    "no_conv_bias": {"drop_conv_bias": True},
    "scale_1": {"route_scale": 1.0},
    "bf16_reference": {"dtype": "bfloat16"},
}
ENGINE_FAULTS = {"bf16_state": {"ssm_state_dtype": "bfloat16"}}


def arms_of(conf, seed, names, devices, **engine_kw):
    """[(arm, passes, facts)] for one seed: ONE honest engine run judged
    against every reference arm, one engine run a planted engine fault."""
    import gc
    import jax.numpy as jnp
    from benchmark.drivers import serve_nemotron_h as drv
    gc.collect()            # the last seed's 9 GB of weights, before these
    rows = []
    cfg = drv.model_config(conf)
    params = drv.build_params(cfg, seed, jnp.dtype(conf["torch_dtype"]))
    ref_arms = [a for a in names if a in REFERENCE_FAULTS]
    if ref_arms:
        eng = drv.build_engine(params, cfg, conf, devices, **engine_kw)
        got = drv.run_check_prompts(eng, cfg, conf, seed)
        del eng
        for arm in ref_arms:
            fault = REFERENCE_FAULTS[arm]
            if fault == "exchanged":
                fault = {"layer_order": [1, 0] + list(
                    range(2, cfg.num_hidden_layers))}
            rows.append((arm,) + drv.judge(params, conf, got, fault))
    for arm in (a for a in names if a in ENGINE_FAULTS):
        wrong = drv.model_config(conf, **ENGINE_FAULTS[arm])
        eng = drv.build_engine(params, wrong, conf, devices, **engine_kw)
        got = drv.run_check_prompts(eng, wrong, conf, seed)
        del eng
        rows.append((arm,) + drv.judge(params, conf, got))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--arms", default=",".join(
        list(REFERENCE_FAULTS) + list(ENGINE_FAULTS)))
    args = ap.parse_args()
    cell, conf, mix, *_ = run.load_cell(ROOT, args.workload)
    import jax
    from paddle_tpu.core.device import setup_compile_cache
    setup_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for arm, ok, facts in arms_of(conf, seed, args.arms.split(","),
                                      jax.devices()):
            print(json.dumps({"cell": args.workload, "seed": seed, "arm": arm,
                              "passes": bool(ok), **facts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
