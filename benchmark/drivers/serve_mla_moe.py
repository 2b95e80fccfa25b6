"""The serving driver of the latent-attention + sparse-expert family:
`drivers/serve.py`'s load generator and window (``Load``, ``measure``) around
a ``ServingEngine`` built from a ``deepseek_v3`` configuration file, checked
against ``reference_mla_moe.py``.

The program is used only through what a user calls: ``MlaMoeConfig``,
``build_functional_mla_moe`` (the weights), ``ServingEngine`` with ``submit``
/ ``step`` / ``lookup`` / ``stats`` / ``check_invariants`` /
``recurrent_state``, and the public fields of ``Request``.

``correct`` holds the TIMED engine's own tokens to the reference's full
forward pass (the expanded attention, no cache): three check prompts — one
that a single dense prefill covers, one of three chunks (its later chunks
read the earlier ones' rows back from the latent pages), and one whose first
1,536 tokens are the second's, which the prefix cache must serve from the
second's pages — each followed by CHECK_TOKENS - 1 decode steps through the
latent cache, the reference ROUTED BY THE ENGINE'S OWN SELECTIONS (the slots'
``moe_sel`` logs: a near-tie at the router's rank 6 of 64 falls either way at
bfloat16, and a reference that went its own way there would measure the fall
and not the arithmetic).  Held to limits (`reference_mla_moe.py` has each
beside its reason, PERF.md its readings): the worst over the generated
positions of (the reference's maximum logit minus the reference logit of the
engine's token), the root mean square of (the engine's log-probability of
its token minus the reference's), the share of the engine's selections
outside the reference's own top-k and how far below it the worst of them
scored — and requires that the third prompt's prefix came from the cache,
that no routed row fell beyond its grouped product's row bound, nothing
compiled inside the window and the page accounting holds.  ``fault`` hands
the REFERENCE a wrong model (tools, tests).
"""
import dataclasses
import time

import numpy as np

from benchmark import flops_mla_moe as flops
from benchmark import reference_mla_moe as reference
from benchmark import traffic as traffic_gen
from benchmark.drivers import serve
from benchmark.drivers.serve import Load, say
from benchmark.drivers.serve_nemotron_h import (Tapped, build_engine,
                                                step_facts)
from benchmark.readers import percentile

# greedy tokens each reference-check prompt makes (16 decode horizons): the
# check's decisive reading is a root mean square over these positions
CHECK_TOKENS = 128
# tokens of the second check prompt that the third one starts with: whole
# pages, and a chunk and a half, so the third's suffix starts inside a chunk
SHARED_PREFIX = 1.5


def model_config(conf, **cfg_overrides):
    """The program's config object from a configuration file's public keys:
    the router's width is the PUBLISHED expert count, the experts held are
    ``(expert_offset, n_routed_experts)``.  What the path cannot express is
    refused (`MlaMoeConfig.validate`), not ignored."""
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    if conf.get("model_type") != "deepseek_v3":
        raise ValueError(f"configuration asks for model type "
                         f"{conf.get('model_type')!r}, which the mla_moe "
                         f"serving path does not have")
    names = {f.name for f in dataclasses.fields(MlaMoeConfig)}
    keys = {k: v for k, v in conf.items() if k in names}
    keys["n_routed_experts"] = conf["published"]["n_routed_experts"]
    keys["experts_held"] = (int(conf.get("expert_offset", 0)),
                            int(conf["n_routed_experts"]))
    keys.update(cfg_overrides)
    cfg = MlaMoeConfig(**keys)
    cfg.validate()
    return cfg


def build_params(cfg, seed, dtype):
    """The weights, made on the device in ONE jitted call from the seed, in
    the type they are served in."""
    import jax
    from paddle_tpu.models.mla_moe import build_functional_mla_moe
    make = jax.jit(lambda key: build_functional_mla_moe(cfg, key=key,
                                                        dtype=dtype))
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def check_lengths(conf, seed):
    """(rng, [one chunk, three chunks, the prefix hit's length], shared):
    the lengths come from the seed but stay in one padding band each, so
    every seed uses the same executables."""
    eng = conf["engine"]
    bucket, chunk, page = eng["prompt_bucket"], eng["prefill_chunk"], \
        eng["page_size"]
    rng = np.random.default_rng(seed + 2)
    draw = lambda top: int(rng.integers(top - bucket + 1, top + 1))
    shared = int(SHARED_PREFIX * chunk) // page * page
    return rng, [draw(chunk), draw(2 * chunk + 2 * bucket),
                 draw(2 * chunk + bucket)], shared


def run_check_prompts(eng, cfg, conf, seed, warmup_lens=()):
    """The three check prompts through the engine — and with the first two,
    in the same pass, one prompt of each of ``warmup_lens`` — -> what it
    made of the three: {"prompts", "lens", "generated", "logs" (each slot's
    selection and log-probability logs, read when its request finished),
    "shared", "cached_tokens" (what the prefix cache served of the third)}."""
    rng, lens, shared = check_lengths(conf, seed)
    draw = lambda t: rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
    prompts = [draw(lens[0]), draw(lens[1])]
    prompts.append(np.concatenate([prompts[1][:shared],
                                   draw(lens[2] - shared)]))
    logs = {}

    def run(rids, rest=()):
        while not all(eng.lookup(r).finish_time for r in list(rids) + list(rest)):
            eng.step()
            # a finished request's slot keeps its logs until another request
            # is admitted to it, the next step at the earliest: read them now
            for r in rids:
                if r not in logs and eng.lookup(r).finish_time:
                    logs[r] = eng.recurrent_state(r)

    # K decode steps follow every first token: the horizon compiles here too
    rids = [eng.submit(p, max_new_tokens=CHECK_TOKENS) for p in prompts[:2]]
    run(rids, [eng.submit(draw(t), max_new_tokens=CHECK_TOKENS)
               for t in warmup_lens])
    before = eng.stats()["cached_prefix_tokens"]
    rids.append(eng.submit(prompts[2], max_new_tokens=CHECK_TOKENS))
    run(rids)
    return {"prompts": prompts, "lens": lens, "shared": shared,
            "generated": [eng.lookup(r).generated for r in rids],
            "logs": [logs[r] for r in rids],
            "cached_tokens": eng.stats()["cached_prefix_tokens"] - before}


def judge(params, conf, got, fault=None):
    """Hold what `run_check_prompts` returned to the plain reference (or,
    with ``fault``, to a deliberately wrong one).  Returns (ok, facts)."""
    eng = conf["engine"]
    pad = 2 * eng["prefill_chunk"] + 2 * eng["prompt_bucket"] + CHECK_TOKENS
    shared = got["shared"]
    gaps, logp_err, short = [], [], []
    pairs = strays = 0
    for i, (prompt, generated, log) in enumerate(zip(
            got["prompts"], got["generated"], got["logs"])):
        sel = log["moe_sel"]
        if i == 2:      # the cached prefix's selections are the second's log
            sel = np.concatenate([got["logs"][1]["moe_sel"][:, :shared],
                                  sel[:, shared:]], axis=1)
        want = reference.check_generation(
            params, conf, prompt, generated, sel, log["logp"], pad_to=pad,
            fault=fault)
        gaps += want["gaps"]
        logp_err += want["logp_err"]
        short.append(want["short"])
        pairs, strays = pairs + want["pairs"], strays + want["strays"]
    facts = {
        "check_prompt_lens": got["lens"], "check_positions": len(gaps),
        "worst_logit_gap": max(gaps), "mean_logit_gap": float(np.mean(gaps)),
        "logit_delta": reference.SERVE_LOGIT_DELTA,
        "logp_rms_error": float(np.sqrt(np.mean(np.square(logp_err)))),
        "worst_logp_error": float(np.abs(logp_err).max()),
        "logp_rms_limit": reference.SERVE_LOGP_RMS,
        # the engine's selections over the check's consumed tokens against
        # the reference's own top-k on the same hidden states
        "selections": pairs, "selections_strayed": strays,
        "stray_share": strays / max(pairs, 1),
        "stray_share_limit": reference.SERVE_STRAY_SHARE,
        "worst_stray_short": max(short),
        "stray_short_limit": reference.SERVE_STRAY_SHORT,
        "prefix_tokens_shared": shared,
        "prefix_tokens_from_cache": got["cached_tokens"]}
    ok = facts["worst_logit_gap"] <= facts["logit_delta"] \
        and facts["logp_rms_error"] <= facts["logp_rms_limit"] \
        and facts["stray_share"] <= facts["stray_share_limit"] \
        and facts["worst_stray_short"] <= facts["stray_short_limit"] \
        and facts["prefix_tokens_from_cache"] == shared
    return ok, facts


def warm_up_and_check(eng, params, cfg, conf, traffic, seed, fault=None):
    """Run the check prompts together with every executable shape the mix
    can produce, then hold their tokens, logs and selections to the plain
    reference.  Returns (ok, facts); ``facts["reference_s"]`` is what the
    reference took."""
    lens = traffic_gen.warmup_lengths(traffic, conf["engine"]["prompt_bucket"])
    got = run_check_prompts(eng, cfg, conf, seed, lens)
    t_ref = time.perf_counter()
    ok, facts = judge(params, conf, got, fault)
    return ok, {**facts, "warmup_prompt_lens": lens,
                "reference_s": time.perf_counter() - t_ref}


def window_facts(conf, facts, snaps, peak):
    """The facts the per-layer metrics read, from the window's counter
    differences (``snaps``: the stats() snapshots ``measure`` took)."""
    first, last = snaps[0], snaps[-1]
    diff = lambda a, b, k: b[k] - a[k]
    names = ("moe_pairs_held", "moe_experts_touched_decode",
             "moe_experts_touched_prefill", "moe_expert_layer_calls_decode",
             "moe_expert_layer_calls_prefill", "moe_rows_dropped",
             "latent_tokens_attended_decode", "latent_pairs_attended_prefill",
             "latent_rows_written", "prefill_tokens_dispatched",
             "cached_prefix_tokens")
    out = {k: diff(first, last, k) for k in names}
    out["moe_experts_held"] = last["moe_experts_held"]
    out["latent_bytes_per_token"] = last["latent_bytes_per_token"]
    out["required_flops_window"] = flops.required_flops(
        conf, prefill_tokens=out["prefill_tokens_dispatched"],
        decode_tokens=facts["decode_tokens"],
        logit_tokens=facts["tokens_generated"],
        routed_rows=out["moe_pairs_held"],
        prefill_pairs=out["latent_pairs_attended_prefill"],
        decode_pairs=out["latent_tokens_attended_decode"])
    out["peak_flops"] = peak["flops_bf16"]
    out["peak_hbm_bytes_per_s"] = peak["hbm_bytes_per_s"]
    if len(snaps) == 3:             # [window start, trace start, window end]
        st = snaps[1]
        touched = diff(st, last, "moe_experts_touched_decode") \
            + diff(st, last, "moe_experts_touched_prefill")
        out["traced.moe_weight_bytes"] = touched \
            * flops.expert_weight_bytes(conf)
        out["traced.latent_bytes_decode"] = flops.latent_bytes_per_token(
            conf) * diff(st, last, "latent_tokens_attended_decode")
        out["traced.mla_prefill_flops"] = flops.expanded_attention_flops(
            conf, diff(st, last, "latent_pairs_attended_prefill"))
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
    try:
        eng.check_invariants()
        invariants = True
    except AssertionError as e:
        say(f"check_invariants failed: {e}")
        invariants = False
    facts.update(window_facts(conf, facts, tapped.snapshots, peak))
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
