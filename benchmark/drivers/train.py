"""The training driver: the donated jitted train step (functional Llama-shaped
blocks + chunked cross-entropy head + AdamW), assembled the way the
program's own entry points assemble it (``chip_smoke.build_train_step``;
copied here so that a later edit there cannot move the yardstick), fed a
fresh batch of random token ids every step, prepared one step ahead.

Traffic file keys: ``batch``, ``seq``; the step's options (``head_chunks``,
``learning_rate``) are in the configuration file's ``step`` group.
"""
import time

import numpy as np

from benchmark import flops, reference, trace_reduce
from benchmark.drivers.serve import model_config, build_params, say


def train_kernels():
    """On a TPU the registry's attention and norm for the train block must
    be the Pallas ones; anything else is another program."""
    from paddle_tpu.core.dispatch import get_kernel
    for name in ("flash_attention_causal", "rms_norm"):
        k = get_kernel(name)
        if k is None or not (k.__module__ or "").startswith(
                "paddle_tpu.ops.pallas"):
            raise RuntimeError(f"{name} did not resolve to its Pallas "
                               f"implementation: {k}")


def build_step(cfg, step_conf):
    """(init_opt, step): ``init_opt(ep, bp, hp)`` -> the three optimizer
    states; ``step(ep, bp, hp, eo, bo, ho, batch)`` -> (state', loss)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import build_functional_llama
    from paddle_tpu.parallel.pipeline import _flatten, _unflatten

    rate = float(step_conf.get("learning_rate", 1e-4))
    opt = optimizer.AdamW(learning_rate=rate, parameters=[])
    lr = jnp.asarray(rate, jnp.float32)
    _, _, _, ea, ba, hl = build_functional_llama(
        cfg, dtype=jnp.bfloat16, n_micro=1,
        head_chunks=int(step_conf.get("head_chunks", 8)), init_params=False)

    def init_opt(ep, bp, hp):
        return tuple(opt.init_opt_state(_flatten(p)) for p in (ep, bp, hp))

    def loss_fn(ep, bp, hp, batch):
        x = ea(ep, batch)[0]
        for i in range(cfg.num_hidden_layers):
            x = ba(jax.tree_util.tree_map(lambda v: v[i], bp), x)
        return hl(hp, x[None], batch)

    def step(ep, bp, hp, eo, bo, ho, batch):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            ep, bp, hp, batch)
        new = [opt.apply_gradients_functional(_flatten(p), _flatten(g), o,
                                              lr=lr)
               for p, g, o in zip((ep, bp, hp), grads, (eo, bo, ho))]
        return tuple(_unflatten(n[0], p) for n, p in zip(new, (ep, bp, hp))) \
            + tuple(n[1] for n in new) + (loss,)

    return init_opt, step


def batches(traffic, vocab_size, seed):
    """Endless iterator of (inputs, labels), int32 [batch, seq]: labels are
    the inputs shifted by one (the head itself does not shift)."""
    rng = np.random.default_rng(seed)
    b, s = int(traffic["batch"]), int(traffic["seq"])
    while True:
        ids = rng.integers(0, vocab_size, (b, s + 1)).astype(np.int32)
        yield ids[:, :-1], ids[:, 1:]


def run(conf, traffic, seed, seconds, trace, t_start, devices, peak,
        check_kernels=True, layer_order=None):
    import jax
    import jax.numpy as jnp
    if check_kernels:
        train_kernels()
    cfg = model_config(conf)
    say(f"imports and devices: {time.perf_counter() - t_start:.1f}s")
    params = build_params(cfg, seed, jnp.bfloat16)
    say(f"weights: {time.perf_counter() - t_start:.1f}s")
    feed = batches(traffic, cfg.vocab_size, seed)
    first = next(feed)
    # the reference reads the initial parameters BEFORE the optimizer state
    # exists: its f32 scores and logits need the room
    want = reference.mean_nll(params, conf, *first,
                              layer_order=layer_order)
    say(f"reference loss: {time.perf_counter() - t_start:.1f}s")
    init_opt, step = build_step(cfg, conf.get("step", {}))
    state = params + jax.jit(init_opt)(*params)
    step = jax.jit(step, donate_argnums=tuple(range(6)))
    put = lambda b: tuple(jax.device_put(a) for a in b)
    *state, loss = step(*state, put(first))           # compiles; warms up
    got = float(loss)
    rel = abs(got - want) / abs(want)
    check = {"step_loss": got, "reference_loss": want, "relative_diff": rel,
             "rtol": reference.TRAIN_LOSS_RTOL}
    say(f"reference check: {check}")
    *state, loss = step(*state, put(next(feed)))      # a second, steady call
    jax.block_until_ready(loss)
    setup_s = time.perf_counter() - t_start
    say(f"window starts: {setup_s:.1f}s")

    clock = time.perf_counter
    trace_seconds = float(traffic.get("trace_seconds", 8)) if trace else 0
    rec, traced_from = None, None
    losses = []
    nxt = put(next(feed))
    t0 = clock()
    while clock() < t0 + seconds:
        if trace_seconds and rec is None \
                and clock() >= t0 + seconds - trace_seconds:
            jax.block_until_ready(losses[-1:])        # the device idle
            rec, traced_from = trace_reduce.Recording(), len(losses)
            rec.start()
        *state, loss = step(*state, nxt)
        losses.append(loss)
        nxt = put(next(feed))                 # the host, one step ahead
        if len(losses) > 1:
            losses[-2].block_until_ready()    # at most two steps in flight
    jax.block_until_ready(losses)
    t1 = clock()
    tr = rec.stop(t1) if rec is not None else None
    values = [float(x) for x in losses]
    bad = sum(1 for v in values if not np.isfinite(v))
    b, s = int(traffic["batch"]), int(traffic["seq"])
    facts = {"window_s": t1 - t0, "steps": len(values), "batch": b, "seq": s,
             "first_loss": values[0], "last_loss": values[-1],
             "train_flops_per_token":
                 flops.train_flops_per_token(conf, s),
             "flash_flops_per_step":
                 flops.flash_attention_flops_per_step(conf, b, s),
             "peak_flops": peak["flops_bf16"]}
    if rec is not None:
        facts["traced.steps"] = len(values) - traced_from
    say(f"window: {facts}")
    say(f"samples: {len(values)} steps")
    e2e = {"setup_s": setup_s,
           "train_tok_s": b * s * len(values) / facts["window_s"]}
    facts.update(e2e)
    return {"correct": bool(rel <= reference.TRAIN_LOSS_RTOL and bad == 0),
            "attempted": len(values), "failed": bad, "end_to_end": e2e,
            "facts": facts, "requests": [], "trace": tr, "check": check}
