"""The AFMoE training driver: the loop of ``drivers/train.py`` — the donated
jitted train step fed a fresh batch of random token ids every step, prepared
one step ahead — with ``build_functional_afmoe``'s blocks (layers of
different kinds, an expert-parallel rank's share of the experts), the
selection bias moved by the balancing rule after every step, the step's
routing counters, and the check against ``reference_afmoe.py``.

Traffic file keys: ``batch``, ``seq``, ``trace_seconds``; the step's options
(``head_chunks``, ``learning_rate``) are in the configuration's ``step``.
In the configuration ``num_experts`` is the experts HELD from
``expert_offset`` on and ``published.num_experts`` the router's width.

The check reads ONE program, the donated step the window times, on its
first call (the first batch, fresh optimizer state); the reference is
computed before that state exists, because its f32 programs need the room.
Compared, each with its limit in ``reference_afmoe.py``: (a) the last
block's output of the first sequence, (b) the share of (token, expert layer)
pairs whose held-expert selection differs, (c) the loss (read, not held:
reference_afmoe.py says why), (d) the gradients the OPTIMIZER received for
four leaves (its first moment / (1 - beta1)) against the reference's
``jax.grad``, (e) the change the step made to those four leaves and to the
selection bias against the stated rules (AdamW, the balancing rule) applied
on the host to the step's own gradient and load — a state left unchanged
reads 1; and the rows counted per held expert and the load per expert
against the step's own selections (no row lost).

Host spans (``jax.profiler.TraceAnnotation``, in a traced run's host plane):
``train.step_dispatch`` around the step's call, ``train.batch_put`` around
the upload of the next batch.
"""
import dataclasses
import time

import numpy as np

from benchmark import flops_afmoe, reference_afmoe, trace_reduce
from benchmark.drivers.serve import say
from benchmark.drivers.train import batches, train_kernels


def model_config(conf):
    """(AfmoeConfig, (offset, count) of the experts held) from a
    configuration file's public keys.  What the path cannot express is
    refused, not ignored."""
    from paddle_tpu.models.afmoe import AfmoeConfig
    names = {f.name for f in dataclasses.fields(AfmoeConfig)}
    keys = {k: v for k, v in conf.items() if k in names}
    keys["num_experts"] = conf["published"]["num_experts"]
    keys["layer_types"] = tuple(conf["layer_types"])
    if conf.get("model_type") != "afmoe" or conf.get("rope_scaling") \
            or any(conf.get(k, 1) != 1 for k in (
                "n_group", "topk_group", "num_expert_groups",
                "num_limited_groups")):
        raise ValueError("configuration asks for another model type, rope "
                         "scaling or group-limited routing, which the afmoe "
                         "path does not have")
    return AfmoeConfig(**keys), (int(conf.get("expert_offset", 0)),
                                 int(conf["num_experts"]))


def build_params(cfg, held, seed, dtype):
    """The weights (and the router's bias buffer), made on the device in ONE
    jitted call from the seed."""
    import jax
    from paddle_tpu.models.afmoe import build_functional_afmoe
    make = jax.jit(lambda key: build_functional_afmoe(
        cfg, key=key, dtype=dtype, experts_held=held)[:3])
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


COUNTERS = ("rows", "held_pairs", "load")


def build_step(cfg, held, step_conf):
    """(init_opt, step).  ``step(ep, bp, hp, eo, bo, ho, batch)`` ->
    (state', loss, out) with out = the step's counters — "rows" int32
    [expert layers, held], "held_pairs" int32 [expert layers], "load" int32
    [expert layers, all experts] — and what only the check reads: "sel"
    int32 [expert layers, B*S, k], "x" the last block's output [B, S, H]."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer
    from paddle_tpu.incubate.distributed.models.moe.dropless import \
        balance_bias_update
    from paddle_tpu.models.afmoe import (build_functional_afmoe, is_buffer,
                                         layer_params)
    from paddle_tpu.parallel.pipeline import _flatten, _unflatten

    rate = float(step_conf.get("learning_rate", 1e-4))
    opt = optimizer.AdamW(learning_rate=rate, parameters=[])
    lr = jnp.asarray(rate, jnp.float32)
    _, _, _, ea, ba, hl = build_functional_afmoe(
        cfg, dtype=jnp.bfloat16, experts_held=held,
        head_chunks=int(step_conf.get("head_chunks", 8)), init_params=False)

    def init_opt(ep, bp, hp):
        return tuple(opt.init_opt_state(_flatten(p)) for p in (ep, bp, hp))

    def loss_fn(ep, bp, hp, batch):
        x, routed = ea(ep, batch)[0], []
        for i in range(cfg.num_hidden_layers):
            x, r = ba(layer_params(bp, i, cfg.num_dense_layers), x, i)
            if r is not None:
                routed.append(r)
        out = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
        return hl(hp, x[None], batch), {**out, "x": x}

    def step(ep, bp, hp, eo, bo, ho, batch):
        (loss, out), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2), has_aux=True)(ep, bp, hp, batch)
        # a buffer (the router's selection bias) has no gradient entry: the
        # optimizer then leaves it, and its state, as they are
        new = [opt.apply_gradients_functional(
            _flatten(p), {k: v for k, v in _flatten(g).items()
                          if not is_buffer(k)}, o, lr=lr)
               for p, g, o in zip((ep, bp, hp), grads, (eo, bo, ho))]
        ep, bp, hp = (_unflatten(n[0], p)
                      for n, p in zip(new, (ep, bp, hp)))
        # the bias moves by the balancing rule, from this step's own load
        moe = bp["moe"]
        bp = {**bp, "moe": {**moe, "router_bias": balance_bias_update(
            moe["router_bias"], out["load"], cfg.load_balance_coeff)}}
        return (ep, bp, hp) + tuple(n[1] for n in new) + (loss, out)

    return init_opt, step


def reference_side(params, conf, first, variant=None, compute=None,
                   given=None):
    """What the reference gives for the first batch (``variant`` /
    ``compute`` / ``given``: a deliberately wrong, a lower-precision, or a
    told-how-to-route reference), on the host: "x" the last block's output
    of the first sequence, "sel" its selections per expert layer, "loss"
    the mean NLL of the batch, "grads" of ``grad_leaves``."""
    ref = reference_afmoe
    kw = {"variant": variant, **({} if compute is None
                                 else {"compute": compute})}
    seq, total = first[0].shape[1], 0.0
    for n, (row, labels) in enumerate(zip(*first)):     # a sequence a time
        x, sel = ref.hidden_states(
            params, conf, row, **kw, given=None if given is None
            else [g[n * seq:(n + 1) * seq] for g in given])
        total += ref.nll_sum(x, params, conf, labels,
                             **{k: v for k, v in kw.items() if k == "compute"})
        if n == 0:
            want = {"x": np.asarray(x, np.float32),
                    "sel": np.stack([np.asarray(s) for s in sel])}
    want["loss"] = total / first[1].size
    want["grads"] = [np.asarray(g, np.float32) for g in ref.gradients(
        params, conf, *first, ref.grad_leaves(conf), **kw, given=given)]
    return want


def system_outputs(params, conf, init_opt, step, first):
    """The TIMED step's first call, on the first batch and a fresh optimizer
    state -> (what the check compares, the state after it).  The gradients
    are the ones the optimizer received: its first moment / (1 - beta1)."""
    import jax
    ref = reference_afmoe
    names = ref.grad_leaves(conf)
    before = [np.asarray(ref.leaf(params, n)) for n in names]
    bias = np.asarray(params[1]["moe"]["router_bias"])
    state = params + init_opt(*params)
    *state, loss, out = step(*state, tuple(jax.device_put(a) for a in first))

    def moment1(name):
        flat, key = {"embed": (state[3], name[1]),
                     "head": (state[5], name[1])}.get(
                         name[0], (state[4], ".".join(name[:2])))
        return np.asarray(flat[key]["moment1"][tuple(name[2:])], np.float32)

    return {"loss": float(loss), "x": np.asarray(out["x"][0], np.float32),
            **{k: np.asarray(out[k]) for k in ("sel", "rows", "load")},
            "grads": [moment1(n) / np.float32(1 - ref.ADAMW["beta1"])
                      for n in names],
            "before": before,
            "after": [np.asarray(ref.leaf(state[:3], n)) for n in names],
            "bias_before": bias,
            "bias_after": np.asarray(state[1]["moe"]["router_bias"])}, state


def check(system, want, conf):
    """``system_outputs`` against ``reference_side`` -> (passes, readings)."""
    ref = reference_afmoe
    f64 = lambda a: np.asarray(a).astype(np.float64)
    seq = want["x"].shape[0]
    out = ref.rel_l2(system["x"], want["x"])
    differ = np.stack([(ref.held_selection(mine[:seq], conf)
                        != ref.held_selection(theirs, conf)).any(-1)
                       for mine, theirs in zip(system["sel"], want["sel"])])
    # the tokens whose selections agree in EVERY expert layer: no flipped
    # near-tie among them, so what is left is precision
    agree = ~differ.any(0)
    out_agree = ref.rel_l2(system["x"][agree], want["x"][agree])
    # every pair of the system's OWN selections (all sequences of the batch)
    # is among the load counted, and every held one has its row
    experts = conf["published"]["num_experts"]
    lost = sum(int(np.abs(ref.held_selection(sel, conf).sum(0) - rows).sum()
                   + np.abs(ref.load_of(sel, experts) - load).sum())
               for sel, rows, load in zip(system["sel"], system["rows"],
                                          system["load"]))
    names = [".".join(map(str, n)) for n in ref.grad_leaves(conf)]
    grad = {n: ref.rel_l2(a, b)
            for n, a, b in zip(names, system["grads"], want["grads"])}
    # the step's change of each leaf against the stated AdamW rule applied
    # to the gradient the step itself had (a leaf left as it was reads 1);
    # and, READ only, against the rule applied to the reference's gradient
    # (Adam's first step is lr * sign(g): this one counts flipped signs)
    rate = float(conf["step"]["learning_rate"])
    change = lambda p, q, g: ref.rel_l2(
        f64(q) - f64(p), f64(ref.adamw_first_step(p, g, rate)) - f64(p))
    update = {n: change(p, q, g) for n, p, q, g in zip(
        names, system["before"], system["after"], system["grads"])}
    update_ref = {n: change(p, q, g) for n, p, q, g in zip(
        names, system["before"], system["after"], want["grads"])}
    update["moe.router_bias"] = ref.rel_l2(
        f64(system["bias_after"]) - f64(system["bias_before"]),
        np.stack([f64(ref.bias_after_step(b, load,
                                          conf["load_balance_coeff"])) - f64(b)
                  for b, load in zip(system["bias_before"], system["load"])]))
    got = system["loss"]
    readings = {"output_rel_l2": out, "limit.output": ref.OUTPUT_REL_L2,
                "output_rel_l2_agreeing": out_agree,
                "limit.output_agreeing": ref.OUTPUT_AGREEING_REL_L2,
                "selection_diff_share": float(differ.mean()),
                "selection_diff_share_by_layer":
                    [float(d.mean()) for d in differ],
                "limit.selection": ref.SELECTION_DIFF_SHARE,
                "step_loss": got, "reference_loss": want["loss"],
                "loss_relative_diff":
                    abs(got - want["loss"]) / abs(want["loss"]),
                "grad_rel_l2": grad, "limit.grad": list(ref.GRAD_REL_L2),
                "update_rel_l2": update, "limit.update": ref.UPDATE_REL_L2,
                "update_rel_l2_by_reference_gradient": update_ref,
                "rows_lost": lost}
    ok = (out <= ref.OUTPUT_REL_L2
          and out_agree <= ref.OUTPUT_AGREEING_REL_L2
          and readings["selection_diff_share"] <= ref.SELECTION_DIFF_SHARE
          and all(g <= limit for g, limit in zip(grad.values(),
                                                 ref.GRAD_REL_L2))
          and max(update.values()) <= ref.UPDATE_REL_L2
          and lost == 0)                  # the loss is read, not held
    return bool(ok), readings


def run(conf, traffic, seed, seconds, trace, t_start, devices, peak,
        check_kernels=True):
    import jax
    import jax.numpy as jnp
    if check_kernels:
        train_kernels()
    cfg, held = model_config(conf)
    say(f"imports and devices: {time.perf_counter() - t_start:.1f}s")
    params = build_params(cfg, held, seed,
                          jnp.dtype(conf.get("torch_dtype", "bfloat16")))
    say(f"weights: {time.perf_counter() - t_start:.1f}s")
    feed = batches(traffic, cfg.vocab_size, seed)
    first = next(feed)
    init_opt, step = build_step(cfg, held, conf.get("step", {}))
    init_opt = jax.jit(init_opt)
    step = jax.jit(step, donate_argnums=tuple(range(6)))
    # the reference reads the initial parameters BEFORE the optimizer state
    # exists: its f32 scores, logits and gradients need the room.  Its
    # seconds are no part of set-up
    t_ref = time.perf_counter()
    want = reference_side(params, conf, first)
    reference_s = time.perf_counter() - t_ref
    say(f"reference done: {time.perf_counter() - t_start:.1f}s")
    # the step compiles and warms up on the first batch: the call the check
    # reads
    system, state = system_outputs(params, conf, init_opt, step, first)
    del params
    t_ref = time.perf_counter()
    passes, readings = check(system, want, conf)
    reference_s += time.perf_counter() - t_ref
    del system, want
    say(f"reference check: {readings}")
    put = lambda b: tuple(jax.device_put(a) for a in b)
    *state, loss, _ = step(*state, put(next(feed)))   # a second, steady call
    jax.block_until_ready(loss)
    compiled = step._cache_size()         # executables so far: nothing may
    setup_s = time.perf_counter() - t_start - reference_s   # compile inside
    say(f"window starts: {setup_s:.1f}s of set-up, "        # the window
        f"{reference_s:.1f}s of reference")

    clock = time.perf_counter
    span = jax.profiler.TraceAnnotation
    trace_seconds = float(traffic.get("trace_seconds", 8)) if trace else 0
    rec, traced_from, untraced_s = None, None, None
    losses, counters, done = [], [], []
    nxt = put(next(feed))
    t0 = clock()
    while clock() < t0 + seconds:
        if trace_seconds and rec is None \
                and clock() >= t0 + seconds - trace_seconds:
            jax.block_until_ready(losses[-1:])        # the device idle
            traced_from, untraced_s = len(losses), clock() - t0
            rec = trace_reduce.Recording()
            rec.start()
        with span("train.step_dispatch"):
            *state, loss, out = step(*state, nxt)
        losses.append(loss)
        # device values, read after the window ("x" and "sel" are let go)
        counters.append({k: out[k] for k in COUNTERS})
        del out
        with span("train.batch_put"):
            nxt = put(next(feed))             # the host, one step ahead
        if len(losses) > 1:
            losses[-2].block_until_ready()    # at most two steps in flight
            done.append(clock())              # when a step was seen finished
    jax.block_until_ready(losses)
    t1 = clock()
    tr = rec.stop(t1) if rec is not None else None
    values = [float(x) for x in losses]
    bad = sum(1 for v in values if not np.isfinite(v))
    # the counters, once: [steps, expert layers, held] and [steps, layers]
    rows = np.asarray(jnp.stack([c["rows"] for c in counters]), np.int64)
    pairs = np.asarray(jnp.stack([c["held_pairs"] for c in counters]),
                       np.int64)
    dropped = int(np.abs(rows.sum(-1) - pairs).sum())
    load = np.asarray(jnp.stack([c["load"] for c in counters]), np.int64)
    uneven = load.max(-1) / load.mean(-1)       # [steps, layers], all experts
    b, s = int(traffic["batch"]), int(traffic["seq"])
    per_token = rows.sum() / (len(values) * b * s)
    # a stall of the host or the chip shows as one long gap between steps
    # seen done: how long, before which step, and the rows that step routed
    gaps, pace = np.diff(done), {}
    if len(gaps):
        slowest = int(gaps.argmax()) + 1
        pace = {"step_s.median": float(np.median(gaps)),
                "step_s.max": float(gaps.max()), "step_s.max_at": slowest,
                "step_s.max_rows": rows[slowest].sum(-1).tolist()}
    facts = {"window_s": t1 - t0, "steps": len(values), "batch": b, "seq": s,
             "first_loss": values[0], "last_loss": values[-1],
             "moe.rows_routed": int(rows.sum()),
             "moe.rows_per_token": per_token,
             "moe.rows_dropped": dropped, "reference_s": reference_s, **pace,
             "compiled_in_window": step._cache_size() - compiled,
             "moe.load_max_over_mean":
                 float((rows.max(-1) / rows.mean(-1)).mean()),
             "moe.load_max_over_mean_all_experts": float(uneven.mean()),
             "moe.load_max_over_mean_all_experts.first_steps":
                 float(uneven[:10].mean()),
             "moe.load_max_over_mean_all_experts.last_steps":
                 float(uneven[-10:].mean()),
             "train_flops_per_token":
                 flops_afmoe.train_flops_per_token(conf, s, per_token),
             "flash_flops_per_step":
                 flops_afmoe.flash_attention_flops_per_step(conf, b, s),
             "peak_flops": peak["flops_bf16"]}
    if rec is not None:
        facts["traced.steps"] = len(values) - traced_from
        facts["traced.moe_gmm_flops"] = float(
            rows[traced_from:].sum() * flops_afmoe.moe_gmm_flops_per_row(conf))
    say(f"window: {facts}")
    say(f"samples: {len(values)} steps")
    e2e = {"setup_s": setup_s,
           "train_tok_s": b * s * len(values) / facts["window_s"]}
    facts.update(e2e)
    if rec is not None:
        # what the per-layer metrics of a traced run multiply by: the rate
        # of the steps BEFORE the recording (the profiler's start costs the
        # window a dozen steps; the end-to-end rate is never read then)
        facts["train_tok_s"] = b * s * traced_from / untraced_s
    return {"correct": bool(passes and bad == 0 and dropped == 0
                            and facts["compiled_in_window"] == 0),
            "attempted": len(values), "failed": bad, "end_to_end": e2e,
            "facts": facts, "requests": [], "trace": tr, "check": readings}
