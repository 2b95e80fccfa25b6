"""The serving driver of the Nemotron-H hybrid: `drivers/serve.py`'s load
generator and window (``Load``, ``measure``) around a ``ServingEngine`` built
from a ``nemotron_h`` configuration file, checked against
``reference_nemotron_h.py``.

The program is used only through what a user calls: ``NemotronHConfig``,
``build_functional_nemotron_h`` (the weights), ``ServingEngine`` with
``submit`` / ``step`` / ``run`` / ``lookup`` / ``stats`` / ``check_invariants``
/ ``recurrent_state``, and the public fields of ``Request``.

``correct`` holds the TIMED engine's own tokens to the reference's full
forward pass: four check prompts (two through dense prefill, two through
chunked prefill with the state carried), each followed by CHECK_TOKENS - 1
decode steps, the reference ROUTED BY THE ENGINE'S OWN SELECTIONS (the
slot's ``moe_sel`` log: a near-tie at the router's rank k falls either way
at bfloat16, and a reference that went its own way there would measure the
fall and not the arithmetic).  Held to limits (`reference_nemotron_h.py`
has each beside its readings): the worst over the generated positions of
(the reference's maximum logit minus the reference logit of the engine's
token), the worst relative error of the recurrent state the slots are left
with, the share of the engine's selections outside the reference's own
top-k and how far below it the worst of them scored, and the state kept in
float32 — and requires that no routed row fell beyond its grouped product's
row bound, nothing compiled inside the window and the page accounting
holds.  ``fault`` hands the REFERENCE a wrong model and
``cfg_overrides`` the ENGINE a wrong configuration (tools, tests).
"""
import dataclasses
import time

import numpy as np

from benchmark import flops_nemotron_h as flops
from benchmark import reference_nemotron_h as reference
from benchmark import traffic as traffic_gen
from benchmark.drivers import serve
from benchmark.drivers.serve import Load, say
from benchmark.readers import percentile

# greedy tokens each reference-check prompt makes: the state is stored and
# read back once a decode step, so a rounding of the STORED state grows with
# the steps while the arithmetic's own error does not
CHECK_TOKENS = 128


def model_config(conf, **cfg_overrides):
    """The program's config object from a configuration file's public keys:
    the router's width is the PUBLISHED expert count, the experts held are
    ``(expert_offset, n_routed_experts)``.  What the path cannot express is
    refused (`NemotronHConfig.validate`), not ignored."""
    from paddle_tpu.models.nemotron_h import NemotronHConfig
    if conf.get("model_type") != "nemotron_h" \
            or conf.get("num_nextn_predict_layers") \
            or conf.get("sliding_window") is not None:
        raise ValueError("configuration asks for another model type, a "
                         "drafting head or a sliding window, which the "
                         "nemotron_h serving path does not have")
    names = {f.name for f in dataclasses.fields(NemotronHConfig)}
    keys = {k: v for k, v in conf.items() if k in names}
    keys["n_routed_experts"] = conf["published"]["n_routed_experts"]
    keys["experts_held"] = (int(conf.get("expert_offset", 0)),
                            int(conf["n_routed_experts"]))
    keys.update(cfg_overrides)
    cfg = NemotronHConfig(**keys)
    cfg.validate()
    return cfg


def build_params(cfg, seed, dtype):
    """The weights, made on the device in ONE jitted call from the seed, in
    the type they are served in."""
    import jax
    from paddle_tpu.models.nemotron_h import build_functional_nemotron_h
    make = jax.jit(lambda key: build_functional_nemotron_h(cfg, key=key,
                                                           dtype=dtype))
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def build_engine(params, cfg, conf, devices, **overrides):
    from paddle_tpu.inference.paged import ServingEngine
    return ServingEngine(params, cfg, dtype=params[0]["tok"].dtype,
                         **{**conf["engine"], **overrides})


class Tapped:
    """The engine as ``Load`` and ``measure`` see it, keeping every
    ``stats()`` snapshot they take (the window's first, the one at the
    trace's start with a trace, the window's last) and when every
    ``step()`` began and how long it took: a run that reads a few per cent
    low says in its ``window:`` line whether one step stalled."""

    def __init__(self, eng):
        self._eng, self.snapshots, self.steps = eng, [], []

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def stats(self):
        self.snapshots.append(self._eng.stats())
        return self.snapshots[-1]

    def step(self):
        t0 = time.perf_counter()
        out = self._eng.step()
        self.steps.append((t0, time.perf_counter() - t0))
        return out


def check_lengths(conf, seed):
    """Two prompts through dense prefill, two through chunked prefill; the
    lengths come from the seed but stay in one padding band each, so every
    seed uses the same executables."""
    bucket = conf["engine"]["prompt_bucket"]
    chunk = conf["engine"]["prefill_chunk"]
    rng = np.random.default_rng(seed + 2)
    return rng, [int(rng.integers(top - bucket + 1, top + 1)) for top in
                 (chunk // 2, chunk, chunk + bucket, chunk + chunk // 2)]


def run_check_prompts(eng, cfg, conf, seed, warmup_lens=()):
    """The four check prompts through the engine — and with them, in the
    same pass, one prompt of each of ``warmup_lens`` — -> what it made of
    the four: {"prompts", "lens", "generated", "states" (each slot's
    recurrent state and selection log after its last consumed token)}."""
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
    chunk = conf["engine"]["prefill_chunk"]
    pad = chunk + chunk // 2 + CHECK_TOKENS      # one shape for every seed
    gaps, state_err, exact, short = [], [], [], []
    pairs = strays = 0
    for prompt, generated, state in zip(got["prompts"], got["generated"],
                                        got["states"]):
        want = reference.check_generation(
            params, conf, prompt, generated, state["moe_sel"], pad_to=pad,
            fault=fault)
        gaps += want["gaps"]
        state_err.append(reference.state_errors(list(state["ssm"]),
                                                want["states"]))
        exact.append(reference.bfloat16_share(state["ssm"]))
        short.append(want["short"])
        pairs, strays = pairs + want["pairs"], strays + want["strays"]
    state_err = np.asarray(state_err)            # [prompts, Mamba layers]
    facts = {
        "check_prompt_lens": got["lens"], "check_positions": len(gaps),
        "worst_logit_gap": max(gaps), "mean_logit_gap": float(np.mean(gaps)),
        "logit_delta": reference.SERVE_LOGIT_DELTA,
        "worst_state_error": float(state_err.max()),
        "mean_state_error": float(state_err.mean()),
        "state_rtol": reference.SERVE_STATE_RTOL,
        "state_errors_by_layer": state_err.max(0).tolist(),
        "first_layer_state_error": float(state_err[:, 0].max()),
        "mean_state_errors_by_layer": state_err.mean(0).tolist(),
        "state_bf16_share": max(exact),
        "state_bf16_share_limit": reference.SERVE_STATE_BF16_SHARE,
        # the engine's selections over the check's consumed tokens against
        # the reference's own top-k on the same hidden states
        "selections": pairs, "selections_strayed": strays,
        "stray_share": strays / max(pairs, 1),
        "stray_share_limit": reference.SERVE_STRAY_SHARE,
        "worst_stray_short": max(short),
        "stray_short_limit": reference.SERVE_STRAY_SHORT}
    ok = facts["worst_logit_gap"] <= facts["logit_delta"] \
        and facts["worst_state_error"] <= facts["state_rtol"] \
        and facts["state_bf16_share"] <= facts["state_bf16_share_limit"] \
        and facts["stray_share"] <= facts["stray_share_limit"] \
        and facts["worst_stray_short"] <= facts["stray_short_limit"]
    return ok, facts


def warm_up_and_check(eng, params, cfg, conf, traffic, seed, fault=None):
    """Run the four reference-check prompts together with every executable
    shape the mix can produce, then hold the four's tokens, states and
    selections to the plain reference.  Returns (ok, facts);
    ``facts["reference_s"]`` is what the reference took."""
    lens = traffic_gen.warmup_lengths(traffic, conf["engine"]["prompt_bucket"])
    got = run_check_prompts(eng, cfg, conf, seed, lens)
    t_ref = time.perf_counter()
    ok, facts = judge(params, conf, got, fault)
    return ok, {**facts, "warmup_prompt_lens": lens,
                "reference_s": time.perf_counter() - t_ref}


def step_facts(steps, t0):
    """How the window's engine steps went on the host's clock: the median,
    the longest, when it began (seconds into the window) and how many took
    over a second."""
    took = [d for t, d in steps if t >= t0] or [0.0]
    at = max((d, t - t0) for t, d in steps if t >= t0)[1] if steps else 0.0
    return {"step_s.median": float(np.median(took)), "step_s.max": max(took),
            "step_s.max_at": at, "steps_over_1s": sum(d > 1 for d in took)}


def window_facts(conf, load, facts, snaps, t0, t1, peak):
    """The facts the per-layer metrics read, from the window's counter
    differences (``snaps``: the stats() snapshots ``measure`` took)."""
    first, last = snaps[0], snaps[-1]
    diff = lambda a, b, k: b[k] - a[k]
    names = ("moe_pairs_held", "moe_experts_touched_decode",
             "moe_experts_touched_prefill", "moe_expert_layer_calls_decode",
             "moe_expert_layer_calls_prefill", "moe_rows_dropped",
             "ssm_slot_resets", "decode_state_bytes_moved",
             "decode_kv_tokens_attended", "prefill_tokens_dispatched",
             "moe.load_ratio_sum")
    out = {k: diff(first, last, k) for k in names}
    out["moe_experts_held"] = last["moe_experts_held"]
    out["ssm_state_bytes"] = last["ssm_state_bytes"]
    calls = out["moe_expert_layer_calls_decode"] \
        + out["moe_expert_layer_calls_prefill"]
    out["moe.load_max_over_mean"] = out["moe.load_ratio_sum"] / max(calls, 1)
    # the (query, key) pairs of the prompts whose prefill ended in the window
    firsts = [(r["prompt_len"], r["first"]) for r in load.done] + [
        (rec["prompt_len"], load.eng.lookup(rid).first_token_time)
        for rid, rec in load.open.items()]
    prefill_pairs = sum(t * (t + 1) / 2 for t, f in firsts
                        if f and t0 <= f <= t1)
    out["required_flops_window"] = flops.required_flops(
        conf, prefill_tokens=out["prefill_tokens_dispatched"],
        decode_tokens=facts["decode_tokens"],
        logit_tokens=facts["tokens_generated"],
        routed_rows=out["moe_pairs_held"], prefill_pairs=prefill_pairs,
        decode_pairs=out["decode_kv_tokens_attended"])
    out["peak_flops"] = peak["flops_bf16"]
    out["peak_hbm_bytes_per_s"] = peak["hbm_bytes_per_s"]
    if len(snaps) == 3:             # [window start, trace start, window end]
        st = snaps[1]
        touched = diff(st, last, "moe_experts_touched_decode") \
            + diff(st, last, "moe_experts_touched_prefill")
        out["traced.moe_weight_bytes"] = touched \
            * flops.expert_weight_bytes(conf)
        out["traced.state_bytes"] = diff(st, last, "decode_state_bytes_moved")
        out["traced.ssd_flops"] = flops.ssd_scan_flops_per_token(conf) \
            * diff(st, last, "prefill_tokens_dispatched")
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
    # serve.measure by its module: perf/prefill_probe.py hooks it there
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
                        and facts["compiled_in_window"] == 0
                        and facts["moe_rows_dropped"] == 0),
        "attempted": len(in_window) + load.refused,
        "failed": load.refused + sum(1 for r in in_window if not r["ok"]),
        "end_to_end": e2e, "facts": facts, "requests": good, "trace": tr,
        "check": check,
    }
