#!/usr/bin/env python3
"""Show on the chip that the latent-attention cell's check can fail, and by
how much.  Every arm prints one JSON line with every reading of the check
beside its limit (`drivers/serve_mla_moe.judge`):

  honest        the engine's check prompts against the reference as it is;
  the REFERENCE reading a deliberately wrong model, against the SAME engine
  outputs: ``bf16_reference`` (everything in bfloat16 at the default matmul
  precision: the nearest precision below the stated one), ``no_rope_key``
  (the shared rotary key r without its rotation), ``no_kv_norm`` (the
  latent's RMSNorm dropped), ``scale_nope`` (softmax scale 1 / sqrt(128)),
  ``scale_1`` (routed_scaling_factor 1), ``no_shared`` (shared experts
  dropped), ``exchanged`` (layers 1 and 2 exchanged).

    python3 benchmark/tools/wrong_model_mla_moe.py --workload <cell>
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

REFERENCE_FAULTS = {
    "honest": None,
    "bf16_reference": {"dtype": "bfloat16"},
    "no_rope_key": {"drop_rope_key": True},
    "no_kv_norm": {"drop_kv_norm": True},
    "scale_nope": {"softmax_scale": "nope"},
    "scale_1": {"route_scale": 1.0},
    "no_shared": {"drop_shared": True},
    "exchanged": "exchanged",            # made from the layer count below
}


def arms_of(conf, seed, names, devices, **engine_kw):
    """[(arm, passes, facts)] for one seed: ONE honest engine run judged
    against every reference arm."""
    import gc
    import jax.numpy as jnp
    from benchmark.drivers import serve_mla_moe as drv
    gc.collect()            # the last seed's weights and pool, before these
    cfg = drv.model_config(conf)
    params = drv.build_params(cfg, seed, jnp.dtype(conf["torch_dtype"]))
    eng = drv.build_engine(params, cfg, conf, devices, **engine_kw)
    got = drv.run_check_prompts(eng, cfg, conf, seed)
    del eng
    rows = []
    for arm in names:
        fault = REFERENCE_FAULTS[arm]
        if fault == "exchanged":
            order = list(range(cfg.num_hidden_layers))
            a = cfg.first_k_dense_replace        # the first two expert layers
            order[a], order[a + 1] = order[a + 1], order[a]
            fault = {"layer_order": order}
        rows.append((arm,) + drv.judge(params, conf, got, fault))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--arms", default=",".join(REFERENCE_FAULTS))
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
