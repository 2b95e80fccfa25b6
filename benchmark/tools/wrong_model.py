#!/usr/bin/env python3
"""Show on the chip that the reference check can fail: hold the system's
outputs to the reference once as it is and once with the REFERENCE reading a
deliberately wrong model (layers 0 and 1 exchanged).

    python3 benchmark/tools/wrong_model.py --workload <cell> [--seed 1]

Prints one JSON line per arm with the readings the check compares with its
tolerance.  Not a benchmark run: nothing is timed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import reference, run                         # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell, conf, mix, *_ = run.load_cell(ROOT, args.workload)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.device import setup_compile_cache
    from benchmark.drivers import serve, train
    setup_compile_cache()
    cfg = serve.model_config(conf)
    swapped = [1, 0] + list(range(2, cfg.num_hidden_layers))
    params = serve.build_params(cfg, args.seed,
                                jnp.dtype(conf["torch_dtype"]))
    arms = (("as it is", None), ("layers 0 and 1 swapped", swapped))
    rows = []
    if conf["driver"] == "serve":
        for arm, order in arms:
            eng = serve.build_engine(params, cfg, conf, jax.devices())
            rows.append((arm,) + serve.warm_up_and_check(
                eng, params, cfg, conf, mix, args.seed, order))
            del eng
    else:
        first = next(train.batches(mix, cfg.vocab_size, args.seed))
        wants = [reference.mean_nll(params, conf, *first, layer_order=order)
                 for _, order in arms]       # before the optimizer state
        init_opt, step = train.build_step(cfg, conf.get("step", {}))
        state = params + jax.jit(init_opt)(*params)
        got = float(jax.jit(step, donate_argnums=tuple(range(6)))(
            *state, first)[-1])
        for (arm, _), want in zip(arms, wants):
            rel = abs(got - want) / abs(want)
            rows.append((arm, rel <= reference.TRAIN_LOSS_RTOL, {
                "step_loss": got, "reference_loss": want,
                "relative_diff": rel, "rtol": reference.TRAIN_LOSS_RTOL}))
    for arm, ok, facts in rows:
        print(json.dumps({"cell": args.workload, "seed": args.seed,
                          "reference_model": arm, "passes": bool(ok),
                          **facts}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
