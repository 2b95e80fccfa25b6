#!/usr/bin/env python3
"""Show on the chip that the AFMoE check can fail, and by how much.  Every
arm goes through ``train_afmoe.check``, at the cell's sizes, and prints one
JSON line with every reading beside its limit:

  honest    the TIMED step's first call against the reference as it is;
  variants  the same against deliberately wrong references;
  lowp      the REFERENCE computed in bfloat16 where the configuration
            states float32 (Adam's moments too) — the nearest precision
            below, standing in for a system that cuts that corner — against
            the reference as it is: the limits must refuse it;
  given     the step against the reference ROUTED BY THE STEP'S OWN
            selections: what is left of each reading when no near-tie flips;
  faults    a planted fault in the step: ``unchanged`` leaves the experts'
            down projections, the router and the selection bias as they were
            (an optimizer that passes over them), ``half_tokens`` takes the
            gradient and the update from the first half of the sequence
            only.

    python3 benchmark/tools/wrong_model_afmoe.py --workload <cell>
        [--seeds 1,2] [--arms honest,lowp,given] [--variants a,b]
        [--faults unchanged,half_tokens]

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


def as_system(side, params, conf):
    """A ``reference_side`` dressed as ``train_afmoe.system_outputs``: the
    stand-in for a system that computes the way that reference did, its
    optimizer's moments in bfloat16."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference_afmoe as ref
    before = [np.asarray(ref.leaf(params, n)) for n in ref.grad_leaves(conf)]
    bias = np.asarray(params[1]["moe"]["router_bias"])
    load = np.stack([ref.load_of(s, conf["published"]["num_experts"])
                     for s in side["sel"]])
    rate = float(conf["step"]["learning_rate"])
    return {**side, "load": load, "before": before, "bias_before": bias,
            "rows": np.stack([ref.held_selection(s, conf).sum(0)
                              for s in side["sel"]]),
            "after": [ref.adamw_first_step(p, g, rate, moments=jnp.bfloat16)
                      for p, g in zip(before, side["grads"])],
            "bias_after": np.stack([
                ref.bias_after_step(b, l, conf["load_balance_coeff"])
                for b, l in zip(bias, load)])}


def planted(fault, step):
    """The step with a fault planted in it (a plain function, to be
    jitted)."""
    if fault == "unchanged":
        def faulty(ep, bp, hp, eo, bo, ho, batch):
            new = step(ep, bp, hp, eo, bo, ho, batch)
            kept = {k: bp["moe"][k]
                    for k in ("we_down", "router", "router_bias")}
            return (new[0], {**new[1], "moe": {**new[1]["moe"], **kept}}) \
                + new[2:]
        return faulty
    if fault == "half_tokens":
        return lambda ep, bp, hp, eo, bo, ho, batch: step(
            ep, bp, hp, eo, bo, ho,
            tuple(a[:, :a.shape[1] // 2] for a in batch))
    raise ValueError(fault)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--arms", default="honest,lowp")
    ap.add_argument("--variants", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args()
    arms = args.arms.split(",")
    cell, conf, mix, *_ = run.load_cell(ROOT, args.workload)
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.device import setup_compile_cache
    from benchmark.drivers import train_afmoe as drv
    from benchmark.drivers.train import batches
    setup_compile_cache()
    cfg, held = drv.model_config(conf)
    make = lambda seed: drv.build_params(cfg, held, seed,
                                         jnp.dtype(conf["torch_dtype"]))
    init_opt, step = drv.build_step(cfg, held, conf.get("step", {}))
    donated = lambda f: jax.jit(f, donate_argnums=tuple(range(6)))
    init_opt, honest_step = jax.jit(init_opt), donated(step)

    def say(seed, arm, system, want):
        ok, readings = drv.check(system, want, conf)
        print(json.dumps({"cell": args.workload, "seed": seed, "arm": arm,
                          "passes": ok, **readings}), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = make(seed)
        first = next(batches(mix, cfg.vocab_size, seed))
        # every reference before an optimizer state exists: they need room
        want = drv.reference_side(params, conf, first)
        wrong = {v: drv.reference_side(params, conf, first, variant=v)
                 for v in args.variants.split(",") if v}
        if "lowp" in arms:
            low = drv.reference_side(params, conf, first,
                                     compute=jnp.bfloat16)
            say(seed, "the reference in bfloat16 where float32 is stated, "
                "against the reference as it is",
                as_system(low, params, conf), want)
        system, state = drv.system_outputs(params, conf, init_opt,
                                           honest_step, first)
        del state, params
        if "honest" in arms:
            say(seed, "the step against the reference as it is", system, want)
        for v, side in wrong.items():
            say(seed, "the step against the reference with " + v, system,
                side)
        if "given" in arms:
            say(seed, "the step against the reference routed by the step's "
                "own selections", system, drv.reference_side(
                    make(seed), conf, first, given=list(system["sel"])))
        for fault in (f for f in args.faults.split(",") if f):
            faulty, state = drv.system_outputs(
                make(seed), conf, init_opt, donated(planted(fault, step)),
                first)
            del state
            if fault == "half_tokens":      # the rest: the honest step's
                faulty = {**system, **{k: faulty[k]
                                       for k in ("grads", "after")}}
            say(seed, "the step with the fault " + fault + ", against the "
                "reference as it is", faulty, want)
    return 0


if __name__ == "__main__":
    sys.exit(main())
