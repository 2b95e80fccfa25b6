"""The serving driver of the SambaY family (Phi-4-mini-flash-reasoning):
`drivers/serve.py`'s load generator and window (``Load``, ``measure``) around
a ``ServingEngine`` built from a ``phi4flash`` configuration file, checked
against ``reference_sambay.py``.

The program is used only through what a user calls: ``SambaYConfig``,
``build_functional_sambay`` (the weights), ``ServingEngine`` with ``submit``
/ ``step`` / ``lookup`` / ``stats`` / ``check_invariants`` /
``recurrent_state``, and the public fields of ``Request``.

``correct`` holds the TIMED engine's own tokens to the reference's full
forward pass (every token through every layer, no cache): three check
prompts — one dense prefill longer than the window, one of two chunks whose
short last chunk takes its whole window out of the ring, one of three chunks
(window, state and tail carried; only the last chunk enters the second half
of the model) — each followed by CHECK_TOKENS - 1 decode steps that run past
a wrap of the window's ring.  Held to limits (`reference_sambay.py` has each
beside its reason, PERF.md its readings): the worst over the generated
positions of (the reference's maximum logit minus the reference logit of the
engine's token) and their mean, the worst relative error of the SSM state
each slot is left with, the worst relative error of the window's K/V rows
read out of the ring by position, and the state kept in float32 — and requires that nothing
compiled inside the window and the page accounting holds.  ``fault`` hands
the REFERENCE a wrong model (tools, tests).
"""
import dataclasses
import time

import numpy as np

from benchmark import flops_sambay as flops
from benchmark import reference_sambay as reference
from benchmark import traffic as traffic_gen
from benchmark.drivers import serve
from benchmark.drivers.serve import Load, say
from benchmark.drivers.serve_nemotron_h import (Tapped, build_engine,
                                                step_facts)
from benchmark.readers import percentile

# greedy tokens each reference-check prompt makes: with the lengths below
# every prompt's decode passes a multiple of the window, where the ring wraps
CHECK_TOKENS = 128


def model_config(conf, **cfg_overrides):
    """The program's config object from a configuration file's public keys.
    What the path cannot express is refused (`SambaYConfig.validate`), not
    ignored."""
    from paddle_tpu.models.sambay import SambaYConfig
    if conf.get("model_type") != "phi4flash":
        raise ValueError(f"configuration asks for model type "
                         f"{conf.get('model_type')!r}, which the sambay "
                         f"serving path does not have")
    names = {f.name for f in dataclasses.fields(SambaYConfig)}
    cfg = SambaYConfig(**{**{k: v for k, v in conf.items() if k in names},
                          **cfg_overrides})
    cfg.validate()
    return cfg


def build_params(cfg, seed, dtype):
    """The weights, made on the device in ONE jitted call from the seed, in
    the type they are served in."""
    import jax
    from paddle_tpu.models.sambay import build_functional_sambay
    make = jax.jit(lambda key: build_functional_sambay(cfg, key=key,
                                                       dtype=dtype))
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def check_lengths(conf, seed):
    """(rng, [one dense prefill, two chunks with a short last one, three
    chunks]): the lengths come from the seed but stay in one padding band
    each, so every seed uses the same executables — and each ends less than
    CHECK_TOKENS short of a multiple of the chunk (and so of the window)."""
    bucket, chunk = conf["engine"]["prompt_bucket"], \
        conf["engine"]["prefill_chunk"]
    rng = np.random.default_rng(seed + 2)
    return rng, [int(rng.integers(top - bucket + 1, top + 1))
                 for top in (chunk, chunk + bucket, 3 * chunk)]


def run_check_prompts(eng, cfg, conf, seed, warmup_lens=()):
    """The three check prompts through the engine — and with them, in the
    same pass, one prompt of each of ``warmup_lens`` — -> what it made of
    the three: {"prompts", "lens", "generated", "states" (what each slot
    holds beside its pages after its last consumed token)}."""
    rng, lens = check_lengths(conf, seed)
    draw = lambda t: rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
    prompts = [draw(t) for t in lens]
    # K decode steps follow every first token: the horizon compiles here too
    rids = [eng.submit(p, max_new_tokens=CHECK_TOKENS) for p in prompts]
    rest = [eng.submit(draw(t), max_new_tokens=CHECK_TOKENS)
            for t in warmup_lens]
    states = {}
    while not all(eng.lookup(r).finish_time for r in rids + rest):
        eng.step()
        # a finished request's slot keeps its state until another request
        # is admitted to it, the next step at the earliest: read it now
        for r in rids:
            if r not in states and eng.lookup(r).finish_time:
                states[r] = eng.recurrent_state(r)
    return {"prompts": prompts, "lens": lens,
            "generated": [eng.lookup(r).generated for r in rids],
            "states": [states[r] for r in rids]}


def judge(params, conf, got, fault=None):
    """Hold what `run_check_prompts` returned to the plain reference (or,
    with ``fault``, to a deliberately wrong one).  Returns (ok, facts)."""
    pad = 3 * conf["engine"]["prefill_chunk"] + CHECK_TOKENS  # one shape
    window = int(conf["sliding_window"])
    gaps, state_err, window_err, exact, ratio = [], [], [], [], []
    for prompt, generated, state in zip(got["prompts"], got["generated"],
                                        got["states"]):
        want = reference.check_generation(params, conf, prompt, generated,
                                          pad_to=pad, fault=fault)
        gaps += want["gaps"]
        state_err.append(reference.relative_errors(list(state["ssm"]),
                                                   want["states"]))
        # position p lies at row p mod W of the engine's ring
        rows = want["window_positions"] % window
        window_err.append([max(reference.relative_errors(
            [state["window_k"][j][rows], state["window_v"][j][rows]], kv))
            for j, kv in enumerate(want["window"])])
        exact.append(reference.bfloat16_share(state["ssm"]))
        (rounded,) = reference.relative_errors(
            [state["ssm"][0]], [want["first_state_rounded"]])
        ratio.append(state_err[-1][0] / max(rounded, 1e-30))
    state_err = np.asarray(state_err)            # [prompts, Mamba layers]
    window_err = np.asarray(window_err)          # [prompts, window layers]
    facts = {
        "check_prompt_lens": got["lens"], "check_positions": len(gaps),
        "worst_logit_gap": max(gaps), "mean_logit_gap": float(np.mean(gaps)),
        "logit_delta": reference.SERVE_LOGIT_DELTA,
        "mean_logit_gap_limit": reference.SERVE_MEAN_LOGIT_GAP,
        "worst_state_error": float(state_err.max()),
        "mean_state_error": float(state_err.mean()),
        "state_rtol": reference.SERVE_STATE_RTOL,
        "state_errors_by_layer": state_err.max(0).tolist(),
        "worst_window_error": float(window_err.max()),
        "window_rtol": reference.SERVE_WINDOW_RTOL,
        "window_errors_by_layer": window_err.max(0).tolist(),
        "first_layer_state_error": float(state_err[:, 0].max()),
        # the engine's first-layer error against the reference over its
        # error against the reference with bfloat16-rounded scan operands:
        # a reading, NOT a limit (`reference_sambay.py` says why)
        "operand_ratio": max(ratio),
        "state_bf16_share": max(exact),
        "state_bf16_share_limit": reference.SERVE_STATE_BF16_SHARE}
    ok = facts["worst_logit_gap"] <= facts["logit_delta"] \
        and facts["mean_logit_gap"] <= facts["mean_logit_gap_limit"] \
        and facts["worst_state_error"] <= facts["state_rtol"] \
        and facts["worst_window_error"] <= facts["window_rtol"] \
        and facts["state_bf16_share"] <= facts["state_bf16_share_limit"]
    return ok, facts


def warm_up_and_check(eng, params, cfg, conf, traffic, seed, fault=None):
    """Run the check prompts together with every executable shape the mix
    can produce, then hold their tokens, states and windows to the plain
    reference.  Returns (ok, facts); ``facts["reference_s"]`` is what the
    reference took."""
    lens = traffic_gen.warmup_lengths(traffic, conf["engine"]["prompt_bucket"])
    got = run_check_prompts(eng, cfg, conf, seed, lens)
    t_ref = time.perf_counter()
    ok, facts = judge(params, conf, got, fault)
    return ok, {**facts, "warmup_prompt_lens": lens,
                "reference_s": time.perf_counter() - t_ref}


def prefill_pairs(conf, prompt_lens):
    """The (query, key) pairs the prefill of these prompts REQUIRES, summed
    over the attention layers: every window layer the min(t + 1, W) keys of
    each token; the K/V layer and each cross layer the LAST token's T keys
    (nothing reads another token's output of those layers)."""
    counts, w = flops.layer_counts(conf), int(conf["sliding_window"])
    total = 0
    for t in prompt_lens:
        short = min(t, w)
        windowed = short * (short + 1) // 2 + (t - short) * w
        total += counts["window"] * windowed \
            + (counts["full"] + counts["cross"]) * t
    return total


def window_facts(conf, load, facts, snaps, t0, t1, peak):
    """The facts the per-layer metrics read, from the window's counter
    differences (``snaps``: the stats() snapshots ``measure`` took)."""
    first, last = snaps[0], snaps[-1]
    diff = lambda a, b, k: b[k] - a[k]
    names = ("shared_kv_tokens_attended_decode",
             "window_tokens_attended_decode", "ssm_live_slot_steps",
             "prefill_tokens_self_decoder", "prefill_tokens_cross_decoder",
             "shared_kv_rows_written", "prefill_tokens_dispatched")
    out = {k: diff(first, last, k) for k in names}
    for k in ("shared_kv_bytes_per_token", "window_state_bytes",
              "ssm_state_bytes", "shared_kv_reading_layers"):
        out[k] = last[k]
    # the prompts whose prefill ended in the window
    firsts = [(r["prompt_len"], r["first"]) for r in load.done] + [
        (rec["prompt_len"], load.eng.lookup(rid).first_token_time)
        for rid, rec in load.open.items()]
    out["required_flops_window"] = flops.required_flops(
        conf, prefill_tokens=out["prefill_tokens_self_decoder"],
        cross_tokens=out["prefill_tokens_cross_decoder"],
        decode_tokens=facts["decode_tokens"],
        logit_tokens=facts["tokens_generated"],
        prefill_pairs=prefill_pairs(
            conf, [t for t, f in firsts if f and t0 <= f <= t1]),
        decode_pairs=out["shared_kv_tokens_attended_decode"]
        + out["window_tokens_attended_decode"])
    out["peak_flops"] = peak["flops_bf16"]
    out["peak_hbm_bytes_per_s"] = peak["hbm_bytes_per_s"]
    if len(snaps) == 3:             # [window start, trace start, window end]
        st = snaps[1]
        row = flops.shared_kv_bytes_per_token(conf)
        out["traced.shared_kv_bytes_decode"] = row * diff(
            st, last, "shared_kv_tokens_attended_decode")
        out["traced.window_bytes_decode"] = row * diff(
            st, last, "window_tokens_attended_decode")
        # a live slot's state is read once and written once a step
        out["traced.selective_update_bytes"] = 2 \
            * flops.state_bytes_per_slot(conf) \
            * diff(st, last, "ssm_live_slot_steps")
        out["traced.selective_scan_required_s"] = flops.scan_required_s(
            conf, diff(st, last, "prefill_tokens_self_decoder"),
            peak["flops_bf16"], peak["hbm_bytes_per_s"])
    return out


def run(conf, traffic, seed, seconds, trace, t_start, devices, peak,
        fault=None, cfg_overrides=None, **overrides):
    """One run of one cell.  Returns the dict ``run.py`` prints from."""
    import jax.numpy as jnp
    cfg = model_config(conf, **(cfg_overrides or {}))
    say(f"imports and devices: {time.perf_counter() - t_start:.1f}s")
    params = build_params(cfg, seed, jnp.dtype(conf["torch_dtype"]))
    say(f"weights: {time.perf_counter() - t_start:.1f}s")
    eng = build_engine(params, cfg, conf, devices, **overrides)
    say(f"engine: {time.perf_counter() - t_start:.1f}s")
    ref_ok, check = warm_up_and_check(eng, params, cfg, conf, traffic, seed,
                                      fault)
    say(f"reference check: {check}")
    say(f"warm-up and check: {time.perf_counter() - t_start:.1f}s")
    tapped = Tapped(eng)
    load = Load(tapped, traffic, cfg.vocab_size, seed)
    load.run_until(lambda: len(load.done) >= int(traffic["clients"]))
    # the reference's seconds are not the program's: nothing here moves them
    setup_s = time.perf_counter() - t_start - check["reference_s"]
    say(f"ramp done, window starts: {setup_s:.1f}s of set-up beside "
        f"{check['reference_s']:.1f}s of reference")

    tapped.snapshots.clear()
    tapped.steps.clear()
    t0 = time.perf_counter()
    facts, in_window, good, tr = serve.measure(
        load, seconds, float(traffic.get("trace_seconds", 8)) if trace else 0)
    t1 = t0 + facts["window_s"]
    try:
        eng.check_invariants()
        invariants = True
    except AssertionError as e:
        say(f"check_invariants failed: {e}")
        invariants = False
    facts.update(window_facts(conf, load, facts, tapped.snapshots, t0, t1,
                              peak))
    facts.update(step_facts(tapped.steps, t0))
    say(f"window: {facts}")
    say(f"samples: {len(good)} requests behind the percentiles")
    e2e = {"setup_s": setup_s,
           "out_tok_s": facts["tokens_generated"] / facts["window_s"]}
    if len(good) >= 2:
        e2e["ttft_p90_ms"] = 1e3 * percentile([r["ttft_s"] for r in good], 90)
        e2e["tpot_p90_ms"] = 1e3 * percentile(
            [r["tpot_s"] for r in good if r["tpot_s"] is not None], 90)
    facts.update(e2e)
    facts.update({k: v for k, v in conf["engine"].items()
                  if isinstance(v, (int, float))})
    return {
        "correct": bool(ref_ok and invariants
                        and facts["compiled_in_window"] == 0),
        "attempted": len(in_window) + load.refused,
        "failed": load.refused + sum(1 for r in in_window if not r["ok"]),
        "end_to_end": e2e, "facts": facts, "requests": good, "trace": tr,
        "check": check,
    }
