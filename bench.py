"""Benchmark: LLaMA-architecture causal-LM training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Headline: the 271M-param LLaMA config (BASELINE.json config #4 family) on the
compiled donate-buffers train step with the Pallas flash-attention kernel
asserted engaged. `vs_baseline` is the ratio to 36,285.8 tok/s/chip — a
number from a deleted record of an earlier installation (BENCH_r02), not
measured on this code; the next `benchmark` PR replaces it (ROADMAP A1).

Train-step design (PERF.md §5): NO rematerialization (unrolled block loop),
the vocab-chunked online-logsumexp head (`head_chunks=8` — the [B,S,32000]
logits tensor never materializes, which is what makes no-remat fit in
15.75 GB), FA block sizes (512, 1024), XLA's own AdamW chain (the fused
Pallas AdamW is opt-in).  Every speed once quoted here came from those
deleted records: not measured on this code.

MFU is reported against the chip's bf16 peak using model FLOPs
(6·N_params + causal-attention 6·L·S·H per token).

Extras (the remaining BASELINE.md measurement-plan rows): ViT-L/16 and
ResNet-50 (compiled functional train steps) images/sec, ERNIE-base MLM
tokens/sec, SD-1.5-scale UNet images/sec, and the S=8192 long-context LLaMA
config.

Serving traces run standalone via `--trace {serving,shared-prefix,
spec-decode,failover}`; `--json PATH` dumps the selected trace's metrics dict as a
BENCH_r0x-style artifact and `--seed` reproduces/varies the generated
trace (each trace's default seed reproduces the PERF.md numbers).  Trace
engines run with telemetry ON (overhead gated >= 0.97x by `make
obs-check`, PERF.md §13); artifacts embed the full observability metrics
snapshot plus an SLO report (TTFT/TPOT/step-latency quantiles, goodput at
a TTFT deadline) and are schema-validated by perf/check_obs.py.
"""
from __future__ import annotations

import itertools
import json
import os
import time

import numpy as np

R2_BASELINE_TPS = 36285.8   # from a deleted record of an earlier
                            # installation; not measured on this code


def _setup_compile_cache():
    """Persistent XLA compilation cache, placed by the package's one helper
    (JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache).  With
    the cache primed (perf/prime_cache.py, run whenever bench configs
    change) a later run on the same machine pays ~zero compile."""
    from paddle_tpu.core.device import setup_compile_cache
    setup_compile_cache()

_PEAK_BF16 = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12), ("v3", 123e12),
)



def _sync(x):
    """Every timed window ends in a value fetch: the device->host read is
    the barrier (and the loss is wanted on the host anyway)."""
    import jax
    return float(np.asarray(jax.device_get(x)))

def _ttft_report(ttfts_s, slo_ttft_s):
    """Shared TTFT readout for EVERY serving trace — delegates to the one
    percentile implementation (paddle_tpu.observability.slo) instead of the
    two hand-rolled np.percentile blocks the traces used to carry:
    p50/p95/p99 plus goodput at the trace's TTFT deadline (requests whose
    first token arrived in time; the share of throughput an SLO would
    actually credit)."""
    from paddle_tpu.observability import slo_report
    rep = slo_report([{"ttft_s": float(t), "tokens": 0, "timed_out": False}
                      for t in ttfts_s], ttft_deadline_s=slo_ttft_s)
    return {
        "ttft_p50_ms": rep["ttft"]["p50_ms"],
        "ttft_p95_ms": rep["ttft"]["p95_ms"],
        "ttft_p99_ms": rep["ttft"]["p99_ms"],
        "slo_ttft_ms": rep["ttft_deadline_ms"],
        "goodput_on_time_requests": rep["on_time_requests"],
        "goodput_fraction": rep["goodput_fraction"],
    }


def _fused_sampling_report(stats):
    """Tokens-not-logits steady-state indicator (ISSUE 16): of all engine
    dispatches, how many emitted their tokens on-device (fused greedy
    argmax / in-horizon sampling) instead of returning logits for host
    sampling.  Greedy-only traffic must report fused_frac 1.0; drift below
    a trace's established value is a regression bench_trend flags."""
    steps = stats["decode_steps"] + stats["verify_steps"]
    fused = stats["fused_sample_steps"]
    return {
        "fused_sample_steps": int(fused),
        "dispatches": int(steps),
        "fused_frac": round(fused / steps, 4) if steps else 0.0,
    }


def _chip_peak_flops(device):
    kind = device.device_kind.lower()
    for key, peak in _PEAK_BF16:
        if key in kind:
            return peak
    raise ValueError(f"no bf16 peak on record for device kind "
                     f"{device.device_kind!r}: add it to _PEAK_BF16 with "
                     f"its source instead of guessing")


def _llama_train_tps(cfg, B, S, steps, warmup, dtype, assert_fa=True,
                     remat=False):
    """Shared timed-train-step scaffold: unrolled block loop, NO remat by
    default (the chunked-CE head frees the HBM that remat used to buy —
    round-4 ablation, PERF.md), donated buffers. Returns
    (tokens_per_sec, n_params, loss)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import build_functional_llama
    from paddle_tpu.parallel.pipeline import _flatten, _unflatten
    from paddle_tpu import optimizer
    from paddle_tpu.core.dispatch import get_kernel

    if assert_fa:
        # the perf contract: Pallas flash attention must be engaged
        k = get_kernel("flash_attention_causal")
        assert k is not None and "pallas" in (k.__module__ or ""), \
            f"Pallas flash attention not engaged: {k}"

    ep, bp, hp, ea, ba, hl = build_functional_llama(cfg, dtype=dtype,
                                                    n_micro=1, head_chunks=8)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=[])
    L = cfg.num_hidden_layers
    blk = jax.checkpoint(ba) if remat else ba

    def loss_fn(ep, bp, hp, batch):
        x = ea(ep, batch)[0]
        for i in range(L):
            x = blk(jax.tree_util.tree_map(lambda v: v[i], bp), x)
        return hl(hp, x[None], batch)

    eo = opt.init_opt_state(_flatten(ep))
    bo = opt.init_opt_state(_flatten(bp))
    ho = opt.init_opt_state(_flatten(hp))
    lr = jnp.asarray(1e-4, jnp.float32)

    def step(ep, bp, hp, eo, bo, ho, batch):
        loss, (ge, gb, gh) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            ep, bp, hp, batch)
        ne, neo = opt.apply_gradients_functional(_flatten(ep), _flatten(ge), eo, lr=lr)
        nb, nbo = opt.apply_gradients_functional(_flatten(bp), _flatten(gb), bo, lr=lr)
        nh, nho = opt.apply_gradients_functional(_flatten(hp), _flatten(gh), ho, lr=lr)
        return (_unflatten(ne, ep), _unflatten(nb, bp), _unflatten(nh, hp),
                neo, nbo, nho, loss)

    step = jax.jit(step, donate_argnums=tuple(range(6)))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32))
    batch = (ids, ids)
    for _ in range(warmup):
        ep, bp, hp, eo, bo, ho, loss = step(ep, bp, hp, eo, bo, ho, batch)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        ep, bp, hp, eo, bo, ho, loss = step(ep, bp, hp, eo, bo, ho, batch)
    _sync(loss)
    tps = B * S * steps / (time.perf_counter() - t0)
    n_params = sum(int(np.prod(v.shape)) for v in
                   list(_flatten(ep).values()) + list(_flatten(bp).values()) +
                   list(_flatten(hp).values()))
    return tps, n_params, float(loss)


def bench_llama():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                          num_hidden_layers=16, num_attention_heads=16,
                          num_key_value_heads=16, max_position_embeddings=2048)
        B, S, steps, warmup = 8, 2048, 20, 3
    else:  # CPU smoke
        cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=384,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=4, max_position_embeddings=256)
        B, S, steps, warmup = 2, 128, 5, 1

    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    tps, n_params, loss = _llama_train_tps(cfg, B, S, steps, warmup, dtype,
                                           assert_fa=on_tpu)
    # model FLOPs/token: 6N + causal attn 6·L·S·H (PaLM MFU convention)
    flops_tok = 6.0 * n_params + 6.0 * cfg.num_hidden_layers * S * cfg.hidden_size
    peak = _chip_peak_flops(jax.devices()[0]) if on_tpu else None
    return {
        "tokens_per_sec": round(tps, 1),
        "n_params": n_params,
        "on_tpu": on_tpu,
        # off-TPU these are meaningless — emit null, not bogus ratios
        "mfu": round(flops_tok * tps / peak, 4) if on_tpu else None,
        "model_flops_per_token": round(flops_tok / 1e9, 3),
        "chip_peak_tflops_bf16": peak / 1e12 if on_tpu else None,
        "device_kind": jax.devices()[0].device_kind,
        "loss": round(loss, 4),
    }


def bench_llama_long_context():
    """Long-context extra: the same 271M architecture at S=8192 (first-class
    long-sequence support; the asserted Pallas flash attention keeps the
    8k x 8k score matrix out of HBM)."""
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                      num_hidden_layers=16, num_attention_heads=16,
                      num_key_value_heads=16, max_position_embeddings=8192)
    tps, _, _ = _llama_train_tps(cfg, 2, 8192, 6, 1, jnp.bfloat16,
                                 assert_fa=True)
    return round(tps, 1)


def bench_vit_l16(B=64):
    """ViT-L/16 framework train step (AdamW via apply_gradients_functional —
    the same optimizer path every compiled trainer in the framework uses),
    images/sec (BASELINE.md #2)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer import functional_state
    from paddle_tpu.vision.models import vit_l_16

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    steps, warmup = (20, 2) if on_tpu else (2, 1)
    if not on_tpu:
        B = 2
    paddle.seed(0)
    model = vit_l_16(num_classes=1000)
    # bf16 everywhere on TPU (a partial cast breaks conv dtype checks)
    cast = (lambda v: v.astype(jnp.bfloat16)) if on_tpu else (lambda v: v)
    params = {n: cast(p._value) for n, p in model.named_parameters()}
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=[])
    opt_state = opt.init_opt_state(params)
    lr = jnp.asarray(1e-4, jnp.float32)

    def loss_fn(params, x, y):
        with functional_state(model, params):
            logits = model(Tensor(x))
        lv = logits._value.astype(jnp.float32)
        logp = jax.nn.log_softmax(lv, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

    def step(params, opt_state, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        new, new_state = opt.apply_gradients_functional(params, g, opt_state,
                                                        lr=lr)
        return new, new_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    x = cast(jnp.asarray(rng.normal(0, 1, (B, 3, 224, 224)).astype(np.float32)))
    y = jnp.asarray(rng.integers(0, 1000, (B,)).astype(np.int32))
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    return round(B * steps / (time.perf_counter() - t0), 1)


def bench_resnet50(B=256):
    """ResNet-50 framework train step (Momentum via
    apply_gradients_functional), images/sec (BASELINE.md #1; the eager
    dygraph mode benches the per-op dispatch path instead, but its ~50
    unique conv shapes each pay their own compile — the compiled step is
    the comparable throughput number. BN running stats are frozen under
    the functional capture).

    30-step window ending in a value fetch (see _sync); B=256 amortizes
    the small-spatial tail stages."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer import functional_state
    from paddle_tpu.vision.models import resnet50

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    steps, warmup = (30, 2) if on_tpu else (1, 1)
    if not on_tpu:
        B = 2
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    model.eval()  # frozen BN stats; conv/bn compute unchanged
    cast = (lambda v: v.astype(jnp.bfloat16)
            if v.dtype == jnp.float32 else v) if on_tpu else (lambda v: v)
    params = {n: cast(p._value) for n, p in model.named_parameters()}
    buffers = {n: cast(b._value) for n, b in model.named_buffers()}
    opt = optimizer.Momentum(learning_rate=1e-3, momentum=0.9, parameters=[])
    opt_state = opt.init_opt_state(params)
    lr = jnp.asarray(1e-3, jnp.float32)

    def loss_fn(params, x, y):
        full = dict(params)
        full.update(buffers)
        with functional_state(model, full):
            logits = model(Tensor(x))
        lv = logits._value.astype(jnp.float32)
        logp = jax.nn.log_softmax(lv, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))

    def step(params, opt_state, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y)
        new, new_state = opt.apply_gradients_functional(params, g, opt_state,
                                                        lr=lr)
        return new, new_state, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    x = cast(jnp.asarray(rng.normal(0, 1, (B, 3, 224, 224)).astype(np.float32)))
    y = jnp.asarray(rng.integers(0, 1000, (B,)).astype(np.int32))
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    _sync(loss)
    return round(B * steps / (time.perf_counter() - t0), 1)


def bench_ernie_mlm():
    """ERNIE-3.0-base MLM pretrain step, tokens/sec (BASELINE.md #3; the
    sharding-stage-2 variant is exercised in tests/test_model_families.py —
    this is the single-chip throughput number)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer import functional_state
    from paddle_tpu.models.ernie import ErnieForMaskedLM, ernie_config_base

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    B, S, steps, warmup = (64, 512, 20, 2) if on_tpu else (2, 64, 1, 1)
    paddle.seed(0)
    cfg = ernie_config_base()
    model = ErnieForMaskedLM(cfg)
    cast = (lambda v: v.astype(jnp.bfloat16)
            if v.dtype == jnp.float32 else v) if on_tpu else (lambda v: v)
    params = {n: cast(p._value) for n, p in model.named_parameters()}

    def loss_fn(params, ids, labels):
        with functional_state(model, params):
            loss, _ = model(Tensor(ids), labels=Tensor(labels))
        return loss._value.astype(jnp.float32)

    @jax.jit
    def step(params, ids, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, ids, labels)
        new = jax.tree_util.tree_map(
            lambda p, gg: p - 1e-4 * gg.astype(p.dtype), params, g)
        return new, loss

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32))
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32))
    for _ in range(warmup):
        params, loss = step(params, ids, labels)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, ids, labels)
    _sync(loss)
    tps = B * S * steps / (time.perf_counter() - t0)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    flops_tok = 6.0 * n_params + 6.0 * cfg.num_hidden_layers * S * cfg.hidden_size
    peak = _chip_peak_flops(jax.devices()[0]) if on_tpu else None
    return {"tokens_per_sec": round(tps, 1),
            "mfu": round(flops_tok * tps / peak, 4) if on_tpu else None}


def bench_sd_unet():
    """SD-1.5-scale UNet denoise train step, images/sec (BASELINE.md #5;
    64x64 latents, 77-token cross-attention context)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer import functional_state
    from paddle_tpu.models.unet import UNet2DConditionModel, unet_config_sd15

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    B, steps, warmup = (8, 10, 2) if on_tpu else (1, 1, 1)
    paddle.seed(0)
    model = UNet2DConditionModel(unet_config_sd15())
    cast = (lambda v: v.astype(jnp.bfloat16)
            if v.dtype == jnp.float32 else v) if on_tpu else (lambda v: v)
    params = {n: cast(p._value) for n, p in model.named_parameters()}

    def loss_fn(params, lat, t, ctx, noise):
        with functional_state(model, params):
            pred = model(Tensor(lat), Tensor(t), Tensor(ctx))
        return jnp.mean((pred._value.astype(jnp.float32)
                         - noise.astype(jnp.float32)) ** 2)

    @jax.jit
    def step(params, lat, t, ctx, noise):
        loss, g = jax.value_and_grad(loss_fn)(params, lat, t, ctx, noise)
        new = jax.tree_util.tree_map(
            lambda p, gg: p - 1e-4 * gg.astype(p.dtype), params, g)
        return new, loss

    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    lat = jnp.asarray(rng.normal(0, 1, (B, 4, 64, 64)), dt)
    t = jnp.asarray(rng.integers(0, 1000, (B,)).astype(np.int32))
    ctx = jnp.asarray(rng.normal(0, 1, (B, 77, 768)), dt)
    noise = jnp.asarray(rng.normal(0, 1, (B, 4, 64, 64)), dt)
    for _ in range(warmup):
        params, loss = step(params, lat, t, ctx, noise)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, loss = step(params, lat, t, ctx, noise)
    _sync(loss)
    return round(B * steps / (time.perf_counter() - t0), 2)


def bench_llama_decode():
    """Decode/serving throughput on the 271M config (VERDICT r4 missing #6:
    inference as a first-class perf surface, reference paddle/fluid/inference/).

    Reports, for B in {1, 8}: prefill tokens/s (prompt 128) and steady-state
    per-step decode tokens/s over the jitted KV-cache decode path
    (`models/llama.py build_llama_decode`, cache bucketed to 256)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (LlamaConfig, build_functional_llama,
                                         _generate_executables)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                      num_hidden_layers=16, num_attention_heads=16,
                      num_key_value_heads=16, max_position_embeddings=2048)
    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=jnp.bfloat16, n_micro=1)
    params = (ep, bp, hp)
    T_prompt, n_decode = 128, 64
    out = {}
    rng = np.random.default_rng(0)
    for B in (1, 8):
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (B, T_prompt)).astype(np.int32))
        prefill, decode, sample = _generate_executables(cfg, 256, 0.0, 0, 1.0,
                                                        dtype=jnp.bfloat16)
        key = jax.random.PRNGKey(0)
        # warmup/compile
        logits, cache = prefill(params, ids)
        tok = sample(logits, key)
        logits2, cache = decode(params, tok, cache)
        _sync(logits2[0, 0])
        # timed prefill (fresh cache each call)
        n_pre = 8
        t0 = time.perf_counter()
        for _ in range(n_pre):
            logits, cache = prefill(params, ids)
        _sync(logits[0, 0])
        pre_tps = B * T_prompt * n_pre / (time.perf_counter() - t0)
        # timed decode loop (serving-shaped: sample + step per token)
        logits, cache = prefill(params, ids)
        tok = sample(logits, key)
        t0 = time.perf_counter()
        for _ in range(n_decode):
            logits, cache = decode(params, tok, cache)
            tok = sample(logits, key)
        _sync(tok[0])
        dec_tps = B * n_decode / (time.perf_counter() - t0)
        # fused whole-generation executable (prefill + fori_loop decode in
        # ONE dispatch — the serving fast path; the per-step loop above
        # pays one host dispatch per token)
        from paddle_tpu.models.llama import llama_generate_fused
        n_new = 64
        outp = llama_generate_fused(params, cfg, ids, max_new_tokens=n_new,
                                    dtype=jnp.bfloat16)     # compile
        _sync(outp[0, -1])
        t0 = time.perf_counter()
        reps = 3
        for r in range(reps):
            outp = llama_generate_fused(params, cfg, ids,
                                        max_new_tokens=n_new, seed=r,
                                        dtype=jnp.bfloat16)
        _sync(outp[0, -1])
        fused_tps = B * n_new * reps / (time.perf_counter() - t0)
        out[f"b{B}"] = {"prefill_tokens_per_sec": round(pre_tps, 1),
                        "decode_tokens_per_sec": round(dec_tps, 1),
                        "fused_generate_tokens_per_sec": round(fused_tps, 1)}
    return out


def bench_serving(seed=0, tp=None):
    """Paged-KV continuous-batching serving throughput on a mixed-length
    Poisson-ish request trace, vs the static-batch `llama_generate_fused`
    baseline (PERF.md §8) — and, since ISSUE 10, an A/B of the
    double-buffered async host loop (`overlap=True`) against the
    synchronous engine on the same trace.

    The engine (inference/paged.py ServingEngine) holds a fixed slot set,
    admits arrivals into freed slots between jitted decode horizons, and
    stores KV in pooled pages — so a short request neither pays for the
    longest sequence in its batch nor blocks the batch on its own exit.
    The static baseline batches the same requests in arrival order and pads
    every prompt/generation to its batch max (what the fixed-batch fused
    path must do).  Throughput counts USEFUL tokens only (each request's
    own generation budget), so padding waste shows up honestly.

    Overlap A/B protocol (PERF.md §17): the synchronous engine drives the
    token-paced arrival schedule and RECORDS the step index of every
    submission; the overlapped engine replays that step-indexed schedule,
    so both modes serve the identical workload (token-time pacing would
    otherwise couple arrivals to the overlap drain's bounded lag and
    penalize it by an artifact).  Greedy outputs are asserted bit-equal
    across every round and both modes BEFORE any number is reported; the
    win is gated on the BEST per-round paired ratio (the same load-robust
    pattern as the telemetry-overhead gate — transient stalls poison
    pairs, a real regression poisons all of them)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (LlamaConfig, build_functional_llama,
                                         llama_generate_fused)
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import Telemetry

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    slo_ttft = 0.25 if on_tpu else 2.0   # TTFT deadline for goodput readout
    if on_tpu:
        # GQA serving config of the 271M family (4 kv heads — the realistic
        # serving shape, and the ragged kernel's native GQA grid)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=16,
                          num_attention_heads=16, num_key_value_heads=4,
                          max_position_embeddings=2048)
        dtype = jnp.bfloat16
        n_req, slots, page_size, horizon = 16, 8, 64, 32
        len_lo, len_hi, new_lo, new_hi = 32, 192, 16, 96
        t_bucket, new_bucket = 128, 32
    else:   # CPU: small GQA config, but big enough that compute (not
        # dispatch) decides the comparison — same code path as TPU
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=768, num_hidden_layers=3,
                          num_attention_heads=8, num_key_value_heads=2,
                          max_position_embeddings=512)
        dtype = jnp.float32
        n_req, slots, page_size, horizon = 12, 4, 16, 12
        len_lo, len_hi, new_lo, new_hi = 16, 128, 4, 96
        t_bucket, new_bucket = 64, 16

    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    params = (ep, bp, hp)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (int(t),)).astype(np.int32)
               for t in rng.integers(len_lo, len_hi, n_req)]
    max_news = [int(m) for m in rng.integers(new_lo, new_hi, n_req)]
    arrivals = np.concatenate([[0.0], np.cumsum(
        rng.exponential(sum(max_news) / (2.0 * n_req), n_req - 1))])

    # per-seq page-table width + pool sized for the trace's worst case (+
    # headroom): the table width bounds the attention grid, so keeping it
    # tight matters as much as the pool size
    worst = (max(t_bucket * ((len(p) + t_bucket - 1) // t_bucket)
                 for p in prompts) + max(max_news) + horizon) \
        // page_size + 2

    def mk_engine(overlap):
        eng = ServingEngine(params, cfg, num_slots=slots,
                            page_size=page_size,
                            num_pages=(slots + 2) * worst,
                            max_pages_per_seq=worst, dtype=dtype,
                            decode_horizon=horizon, prompt_bucket=t_bucket,
                            overlap=overlap, telemetry=Telemetry())
        # warm the executables — one dummy request per prompt-length
        # bucket in the trace (warms every prefill executable) plus the
        # decode horizon; the measured drives reuse the SAME engine so
        # nothing compiles inside the timed windows
        for Tb in sorted({((len(p) + t_bucket - 1) // t_bucket) * t_bucket
                          for p in prompts}):
            eng.submit(rng.integers(0, cfg.vocab_size,
                                    (Tb,)).astype(np.int32),
                       max_new_tokens=horizon + 1)
        eng.run()
        return eng

    def drive(eng, sched=None):
        """One timed pass over the trace.  sched=None: submit request i
        once `arrivals[i]` generated tokens have passed (Poisson
        inter-arrivals in token time), RECORDING each submission's step
        index.  sched=[...]: replay that step-indexed schedule — the
        mode-independent workload the overlap A/B compares on.  Returns
        (tokens/s, wall seconds, per-request token lists, schedule,
        request records)."""
        base_tok = eng.tokens_generated
        s0 = eng._step_seq
        i = 0
        rids = {}
        sched_out = []
        depth_max = 0
        t0 = time.perf_counter()
        while i < n_req or eng.num_active or eng._queue \
                or eng.inflight_depth:
            if sched is None:
                while (i < n_req
                       and eng.tokens_generated - base_tok >= arrivals[i]):
                    sched_out.append(eng._step_seq - s0)
                    rids[i] = eng.submit(prompts[i],
                                         max_new_tokens=max_news[i])
                    i += 1
                if eng.num_active == 0 and not eng._queue \
                        and not eng.inflight_depth:
                    if i >= n_req:
                        break
                    sched_out.append(eng._step_seq - s0)   # idle jump
                    rids[i] = eng.submit(prompts[i],
                                         max_new_tokens=max_news[i])
                    i += 1
            else:
                while i < n_req and eng._step_seq - s0 >= sched[i]:
                    rids[i] = eng.submit(prompts[i],
                                         max_new_tokens=max_news[i])
                    i += 1
            eng.step()
            depth_max = max(depth_max, eng.inflight_depth)
        eng.quiesce()
        _sync(eng._pages_k[0, 0, 0, 0, 0])
        dt = time.perf_counter() - t0
        reqs = [eng._finished[rids[j]] for j in range(n_req)]
        outs = [list(r.generated) for r in reqs]
        eng.release_cache()     # identical cache state for the next round
        return sum(max_news) / dt, dt, outs, sched_out, reqs, depth_max

    eng_off = mk_engine(False)
    eng = mk_engine(True)       # the overlapped engine is the headline one
    rounds = 3
    tps_off_all, tps_on_all, p50_off_all, p50_on_all = [], [], [], []
    reqs_all, sections_all, depth_all = [], [], []
    outs0 = None
    for _ in range(rounds):
        eng_off.telemetry.reset_window()
        eng.telemetry.reset_window()
        tps_off, dt_off, outs_off, sched, _reqs, _d = drive(eng_off)
        tps_on, dt_engine, outs_on, _, round_reqs, depth = \
            drive(eng, sched=sched)
        reqs_all.append(round_reqs)
        depth_all.append(depth)
        # capture the overlapped engine's full telemetry sections PER
        # ROUND, so the reported artifact can describe the same (best)
        # round everywhere — the window resets at the next round's start
        sections_all.append({
            "metrics": eng.telemetry.snapshot(eng.stats()),
            "slo_report": eng.telemetry.slo_report(slo_ttft,
                                                   window_s=dt_engine),
            "utilization": eng.telemetry.utilization_report(
                window_s=dt_engine),
            "memory": eng.telemetry.memory_report(eng.stats()),
            "compile": eng.telemetry.compile_report(),
        })
        # bit-exact overlap-on vs overlap-off on every round, and across
        # rounds (the cache is released between rounds) — or no number
        # below may be reported
        assert outs_off == outs_on, \
            "overlap changed greedy outputs"
        if outs0 is None:
            outs0 = outs_off
        assert outs_off == outs0, "greedy outputs drifted across rounds"
        tps_off_all.append(tps_off)
        tps_on_all.append(tps_on)
        p50_off_all.append(eng_off.telemetry.slo_report(
            slo_ttft, window_s=dt_off)["step_latency"]["p50_ms"])
        p50_on_all.append(eng.telemetry.slo_report(
            slo_ttft, window_s=dt_engine)["step_latency"]["p50_ms"])
    pair_ratios = [a / b for a, b in zip(tps_on_all, tps_off_all)]
    best = max(range(rounds), key=lambda r: pair_ratios[r])
    overlap_report = {
        "enabled": True,
        "rounds": rounds,
        "tokens_per_sec_on": round(tps_on_all[best], 1),
        "tokens_per_sec_off": round(tps_off_all[best], 1),
        "best_paired_ratio": round(pair_ratios[best], 4),
        "pair_ratios": [round(x, 4) for x in pair_ratios],
        "median_ratio": round(sorted(pair_ratios)[rounds // 2], 4),
        # best-vs-best across rounds (load-robust, like the ratio gate: a
        # transient stall inflates one round's p50, a real host-loop
        # regression inflates every round's)
        "step_host_p50_ms_on": min(p50_on_all),
        "step_host_p50_ms_off": min(p50_off_all),
        "step_host_p50_ms_on_all": p50_on_all,
        "step_host_p50_ms_off_all": p50_off_all,
        "step_host_p50_reduced": min(p50_on_all) <= min(p50_off_all),
        "outputs_bit_exact": True,
        "overlap_steps": eng.stats()["overlap_steps"],
        "quiesces": eng.stats()["quiesces"],
        "inflight_depth_max": max(depth_all),      # measured, not asserted
        # a SINGLE-core host cannot overlap host work with XLA compute —
        # they time-slice one core, so parity (not a win) is the best
        # demonstrable result there; check_obs.py gates accordingly
        "host_cpu_count": os.cpu_count(),
        "arrival_pacing": "step-replay (mode-independent; recorded on the "
                          "synchronous engine's token-paced drive)",
    }
    # headline numbers come from the overlapped engine's best paired round
    # — INCLUDING the latency/TTFT stats, so every reported figure
    # describes the same round
    serving_tps = tps_on_all[best]
    measured = reqs_all[best]
    lat = [r.finish_time - r.submit_time for r in measured]
    ttfts = [r.ttft for r in measured]
    useful = sum(max_news)

    # static-batch fused baseline: batches of `slots` in arrival order, each
    # padded to its batch max (prompt AND generation); bucketed shapes so
    # the executable count stays small.  Run twice, time the second — the
    # first full pass absorbs every compile
    def run_baseline():
        t0 = time.perf_counter()
        done_at = []
        for b0 in range(0, n_req, slots):
            bp_ = prompts[b0:b0 + slots]
            bn = max_news[b0:b0 + slots]
            Tmax = ((max(len(p) for p in bp_) + t_bucket - 1)
                    // t_bucket) * t_bucket
            Nmax = ((max(bn) + new_bucket - 1) // new_bucket) * new_bucket
            ids = np.zeros((len(bp_), Tmax), np.int32)
            for j, p in enumerate(bp_):
                ids[j, :len(p)] = p
            out = llama_generate_fused(params, cfg, ids, max_new_tokens=Nmax,
                                       dtype=dtype)
            _sync(out[0, -1])
            done_at.extend([time.perf_counter() - t0] * len(bp_))
        return time.perf_counter() - t0, done_at

    run_baseline()                         # compile warm-up
    dt_base, base_done = run_baseline()
    base_tps = useful / dt_base
    res = {
        # the overlapped engine's best paired round (its sync twin rides
        # in the `overlap` section for the A/B)
        "serving_tokens_per_sec": round(serving_tps, 1),
        "static_fused_tokens_per_sec": round(base_tps, 1),
        "speedup_vs_static": round(serving_tps / base_tps, 3),
        "overlap": overlap_report,
        "n_requests": n_req,
        "useful_tokens": int(useful),
        "mean_request_latency_s": round(float(np.mean(lat)), 3),
        "static_mean_completion_s": round(float(np.mean(base_done)), 3),
        **_ttft_report(ttfts, slo_ttft),
        "decode_horizon": horizon,
        "page_size": page_size,
        "num_slots": slots,
        # ISSUE 16 tokens-not-logits steady state: dispatches whose tokens
        # were consumed on-device (fused greedy argmax / in-horizon
        # sampling) vs total steady-state dispatches — greedy traffic
        # should pin fused_frac at 1.0 (no logits ever leave the device)
        "fused_sampling": _fused_sampling_report(eng.stats()),
        "engine_stats": eng.stats(),
        # full telemetry snapshot + SLO report + observatory sections,
        # ALL captured from the best paired round's window — every figure
        # in the artifact describes the same round (ISSUE 7 sections,
        # schema-gated by perf/check_obs.py)
        **sections_all[best],
    }
    if tp:
        res["tp"] = _bench_serving_tp_block(seed, int(tp))
    return res


def _bench_serving_tp_block(seed, tp):
    """Tensor-parallel serving arm (``--trace serving --tp N``; ROADMAP
    item 1, PERF.md §25): shard ONE ServingEngine over an ``mp`` mesh of
    the first N devices (CPU hosts: N forced-host virtual devices, set by
    ``__main__`` before jax imports) and report the ``tp`` artifact block:

      * greedy outputs of the f32-collective TP engine BIT-EXACT vs the
        single-chip engine on the same mixed trace — asserted every
        round, then reported (the overlap A/B's bar);
      * paired tokens/s single vs TP.  On a forced-host mesh all "chips"
        time-slice one CPU, so the ratio measures sharding dispatch
        overhead, not a speedup — PERF.md §25 records that framing; on a
        real multi-chip host the same arm reads as the TP speedup;
      * the per-rank collective profile from the SPMD sanitizer's
        profiled trace of the TP engine's executables
        (``dist.collective_s`` per kind, ``max_rank_skew_s``) plus the
        execution-side ``decode_sync_frac`` attribution for both arms.
        ``tp_collective_frac`` — the TP arm's decode_sync_frac, the
        ceiling on the collective tax — is the bench_trend drift column;
      * the quantized (EQuARX int8) AllReduce arm: ``parity_report``
        reused with per-arm engine/build kwargs so the ONLY delta under
        measurement is the per-layer AllReduce grid (gated
        exact_match >= 0.99, teacher-forced logit drift reported), plus
        its paired tokens/s vs the f32-collective TP engine."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis.spmd_sanitize import spmd_sanitize
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.observability import Telemetry
    from paddle_tpu.serving.quant import parity_report

    devs = jax.devices()
    if len(devs) < tp:
        raise SystemExit(f"--tp {tp}: only {len(devs)} devices visible "
                         "(CPU hosts need the forced-host flag set before "
                         "jax import — run via bench.py __main__)")
    if 8 % tp:
        raise SystemExit(f"--tp {tp} must divide the TP config's 8 "
                         "attention heads (use 2, 4 or 8)")
    on_tpu = any(d.platform == "tpu" for d in devs)
    mesh = build_mesh({"mp": tp}, devices=devs[:tp])
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    nkv = max(2, tp)             # one KV-head group per rank once tp > 2
    cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                      intermediate_size=768, num_hidden_layers=3,
                      num_attention_heads=8, num_key_value_heads=nkv,
                      max_position_embeddings=512)
    page_size, horizon, t_bucket, slots = 16, 8, 32, 4
    # margin-engineered model (the quant/spec-decode construction):
    # embedding-dominated residual + tied LM head keep greedy argmax
    # margins far above both psum reassociation noise and the int8
    # AllReduce grid, so bit-exactness measures the ENGINE, not the
    # noise floor of near-uniform random logits
    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1,
                                            key=jax.random.PRNGKey(7))
    bp = {k: (v * 0.15 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    params = (ep, bp, hp)

    rng = np.random.default_rng(seed)
    n_req = 8
    prompts = [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
               for t in rng.integers(12, 90, n_req)]
    max_news = [int(m) for m in rng.integers(8, 25, n_req)]
    useful = sum(max_news)
    worst = (max(t_bucket * ((len(p) + t_bucket - 1) // t_bucket)
                 for p in prompts) + max(max_news) + horizon) \
        // page_size + 2

    def mk_engine(mesh_=None, telemetry=None, **kw):
        return ServingEngine(params, cfg, num_slots=slots,
                             page_size=page_size,
                             num_pages=(slots + 2) * worst,
                             max_pages_per_seq=worst, dtype=dtype,
                             decode_horizon=horizon, prompt_bucket=t_bucket,
                             attention_impl="auto" if on_tpu else "ref",
                             mesh=mesh_, telemetry=telemetry, **kw)

    def warm(eng):
        for Tb in sorted({((len(p) + t_bucket - 1) // t_bucket) * t_bucket
                          for p in prompts}):
            eng.submit(rng.integers(1, cfg.vocab_size,
                                    (Tb,)).astype(np.int32),
                       max_new_tokens=horizon + 1)
        eng.run()
        eng.release_cache()

    def drive(eng):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, max_news)]
        done = eng.run()
        _sync(jax.tree_util.tree_leaves(eng._pages_k)[0]
              .reshape(-1)[0].astype(jnp.float32))
        dt = time.perf_counter() - t0
        outs = [list(done[r].generated) for r in rids]
        eng.release_cache()
        return useful / dt, outs

    tel_s = Telemetry()
    tel_tp = Telemetry()
    eng_s = mk_engine(telemetry=tel_s)
    eng_tp = mk_engine(mesh_=mesh, telemetry=tel_tp)
    warm(eng_s)
    # the TP engine's warm pass traces every executable — run it under
    # the profiled SPMD sanitizer so the artifact carries the per-rank
    # collective schedule/skew profile (the multichip dryrun's readout,
    # landed in the bench artifact)
    with spmd_sanitize(n_ranks=tp, profile=True) as san:
        warm(eng_tp)
    san.verify()
    coll = san.skew_report()

    rounds = 3
    tps_s_all, tps_tp_all = [], []
    outs0 = None
    for _ in range(rounds):
        tps_s, outs_s = drive(eng_s)
        tps_t, outs_t = drive(eng_tp)
        assert outs_s == outs_t, \
            "TP engine changed greedy outputs vs single-chip"
        if outs0 is None:
            outs0 = outs_s
        assert outs_s == outs0, "greedy outputs drifted across rounds"
        tps_s_all.append(tps_s)
        tps_tp_all.append(tps_t)
    pair_ratios = [t / s for t, s in zip(tps_tp_all, tps_s_all)]
    best = max(range(rounds), key=lambda r: pair_ratios[r])

    # execution-side attribution: decode_sync_frac is the share of
    # request latency blocked on device sync during decode — on the TP
    # arm that sync INCLUDES the per-layer AllReduce, so the TP number is
    # the ceiling on the collective tax (subtract the single-chip arm's
    # to isolate it)
    dsync_s = tel_s.attribution_report()["decode_sync_frac"]
    dsync_tp = tel_tp.attribution_report()["decode_sync_frac"]

    # quantized-AllReduce arm: same engine, int8 wire format
    eng_q = mk_engine(mesh_=mesh, quantized_allreduce=True)
    warm(eng_q)
    tps_q_all = []
    for _ in range(rounds):
        tps_q, outs_q = drive(eng_q)
        assert outs_q == outs0, \
            "quantized AllReduce flipped greedy outputs vs the f32-" \
            "collective TP engine"
        tps_q_all.append(tps_q)

    # the parity harness, re-aimed: both arms TP, kv_dtype/quantize OFF —
    # the only difference under measurement is the AllReduce grid
    parity = parity_report(
        params, cfg, kv_dtype=None, quantize=None,
        engine_kw=dict(attention_impl="auto" if on_tpu else "ref",
                       dtype=dtype),
        ref_engine_kw={"mesh": mesh},
        q_engine_kw={"mesh": mesh, "quantized_allreduce": True},
        ref_build_kw={"mesh": mesh},
        q_build_kw={"mesh": mesh, "quantized_allreduce": True})
    assert parity["exact_match"] >= 0.99, \
        f"quantized-AllReduce greedy exact-match " \
        f"{parity['exact_match']} < 0.99: {parity}"

    st = eng_tp.stats()
    assert st["tp_degree"] == tp
    eng_tp.check_invariants()
    return {
        "tp_degree": tp,
        "devices": {"count": len(devs), "platform": devs[0].platform,
                    "forced_host": not on_tpu},
        "outputs_bit_exact": True,
        "rounds": rounds,
        "tokens_per_sec_tp": round(tps_tp_all[best], 1),
        "tokens_per_sec_single": round(tps_s_all[best], 1),
        "best_paired_ratio": round(pair_ratios[best], 4),
        "pair_ratios": [round(x, 4) for x in pair_ratios],
        "tokens_per_sec_quantized": round(max(tps_q_all), 1),
        "quantized_vs_f32_ratio": round(max(tps_q_all)
                                        / tps_tp_all[best], 4),
        # bench_trend drift column: the TP arm's decode_sync_frac
        "tp_collective_frac": round(float(dsync_tp), 4),
        "attribution": {
            "decode_sync_frac_tp": round(float(dsync_tp), 4),
            "decode_sync_frac_single": round(float(dsync_s), 4),
        },
        # trace-time per-rank collective profile (dist.collective_s /
        # dist.max_rank_skew_s — the skew_report metric names)
        "collectives": {
            "events": coll["events"],
            "total_s": coll["total_s"],
            "per_kind": coll["per_kind"],
            "max_rank_skew_s": coll["max_rank_skew_s"],
            "per_rank_total_s": coll["per_rank_total_s"],
            "straggler": coll["straggler"],
        },
        "quantized_parity": parity,
        "engine_stats": st,
    }


def bench_serving_shared_prefix(seed=7):
    """Prefix-cache + chunked-prefill serving trace (PERF.md §10): N users
    share one system prompt, then each sends multi-turn follow-ups whose
    prompts embed the full prior conversation — the dominant production
    traffic shape, and the one the PR 1 engine re-prefilled from token
    zero every time.

    Two engines run the SAME trace: the prefix-cache + chunked-prefill
    engine and the PR 1-equivalent engine (prefix_cache=False,
    prefill_chunk=None).  Reported: cache hit-rate, prefill tokens
    actually executed vs requested (the saved tokens are the win), TTFT
    p50/p95 per engine, useful tokens/sec per engine.  Greedy outputs of
    the two engines are asserted token-identical before any number is
    reported — a fast cache that decodes differently is a bug, not a
    result."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import Telemetry

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    slo_ttft = 0.2 if on_tpu else 1.0    # TTFT deadline for goodput readout
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=16,
                          num_attention_heads=16, num_key_value_heads=4,
                          max_position_embeddings=2048)
        dtype = jnp.bfloat16
        n_users, n_turns = 8, 3
        sys_len, msg_lo, msg_hi, new_lo, new_hi = 256, 16, 48, 16, 48
        slots, page_size, horizon, t_bucket, chunk = 8, 64, 32, 128, 256
    else:
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=768, num_hidden_layers=3,
                          num_attention_heads=8, num_key_value_heads=2,
                          max_position_embeddings=1024)
        dtype = jnp.float32
        n_users, n_turns = 6, 2
        sys_len, msg_lo, msg_hi, new_lo, new_hi = 64, 8, 24, 8, 24
        slots, page_size, horizon, t_bucket, chunk = 4, 16, 8, 32, 64

    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    params = (ep, bp, hp)
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32)
    msgs = [[rng.integers(0, cfg.vocab_size,
                          (int(rng.integers(msg_lo, msg_hi)),)).astype(np.int32)
             for _ in range(n_turns)] for _ in range(n_users)]
    budgets = [[int(rng.integers(new_lo, new_hi)) for _ in range(n_turns)]
               for _ in range(n_users)]

    # pool sized for the trace worst case (+ headroom so the comparison
    # measures caching, not eviction pressure)
    worst_tokens = sys_len + n_turns * (msg_hi + new_hi)
    worst = worst_tokens // page_size + 2
    # whole working set (live slots + every user's cached conversation)
    # fits: this trace measures caching; eviction pressure has its own
    # tests and fault drills
    n_pages = (n_users + slots + 1) * worst

    def run_trace(prefix_cache, prefill_chunk):
        eng = ServingEngine(params, cfg, num_slots=slots,
                            page_size=page_size, num_pages=n_pages,
                            max_pages_per_seq=worst, dtype=dtype,
                            decode_horizon=horizon, prompt_bucket=t_bucket,
                            prefix_cache=prefix_cache,
                            prefill_chunk=prefill_chunk,
                            telemetry=Telemetry())

        def once():
            convs = [list(system) for _ in range(n_users)]
            outputs, ttfts, useful = [], [], 0
            for turn in range(n_turns):
                rids = {}
                for u in range(n_users):
                    convs[u].extend(int(t) for t in msgs[u][turn])
                    rids[u] = eng.submit(np.asarray(convs[u], np.int32),
                                         max_new_tokens=budgets[u][turn])
                    useful += budgets[u][turn]
                done = eng.run()
                for u in range(n_users):
                    r = done[rids[u]]
                    convs[u].extend(r.generated)
                    outputs.append(list(r.generated))
                    ttfts.append(r.first_token_time - r.submit_time)
            return outputs, ttfts, useful

        # pass 1 absorbs every compile (the cache is dropped after, so the
        # measured pass re-discovers the same hit pattern with every
        # executable warm); pass 2 is timed
        once()
        eng.release_cache()
        base = (eng.cache_hit_tokens, eng.prefill_tokens, eng.cow_copies,
                eng.cache_evictions)
        base_misses = dict(eng.jit_cache_misses)
        # scope the SLO report to the timed pass (pass 1 absorbed compiles)
        eng.telemetry.reset_window()
        t0 = time.perf_counter()
        outputs, ttfts, useful = once()
        dt = time.perf_counter() - t0
        _sync(eng._pages_k[0, 0, 0, 0, 0])
        stats = {
            "tokens_per_sec": round(useful / dt, 1),
            **_ttft_report(ttfts, slo_ttft),
            "prefill_tokens_executed": int(eng.prefill_tokens - base[1]),
            "cache_hit_tokens": int(eng.cache_hit_tokens - base[0]),
            "cow_copies": int(eng.cow_copies - base[2]),
            "cache_evictions": int(eng.cache_evictions - base[3]),
            # full engine counters (cumulative, incl. warm-pass compiles)
            "engine_stats": eng.stats(),
            # per-model-fn compile-cache misses DURING THE TIMED PASS only
            # (the recompile sanitizer's ledger, PERF.md §12) — a warmed
            # timed pass that recompiled is a bogus number, so this must
            # be all-zeros
            "jit_cache_misses_timed_pass": {
                k: int(v - base_misses.get(k, 0))
                for k, v in eng.jit_cache_misses.items()
            },
            # full telemetry snapshot + SLO report over the timed pass
            "metrics": eng.telemetry.snapshot(eng.stats()),
            "slo_report": eng.telemetry.slo_report(slo_ttft, window_s=dt),
            # host/device decomposition + memory/compile observatory over
            # the timed pass (compile counts are engine-cumulative)
            "utilization": eng.telemetry.utilization_report(window_s=dt),
            "memory": eng.telemetry.memory_report(eng.stats()),
            "compile": eng.telemetry.compile_report(),
        }
        return outputs, stats

    out_cache, s_cache = run_trace(True, chunk)
    out_plain, s_plain = run_trace(False, None)
    # bit-exact greedy parity cache-on vs PR 1 engine, or the numbers lie
    assert out_cache == out_plain, "prefix cache changed greedy outputs"
    requested = s_cache["prefill_tokens_executed"] \
        + s_cache["cache_hit_tokens"]
    return {
        "trace": {"n_users": n_users, "n_turns": n_turns,
                  "system_prompt_tokens": sys_len,
                  "prefill_chunk": chunk, "page_size": page_size,
                  "num_slots": slots},
        "cache_hit_rate": round(s_cache["cache_hit_tokens"] / requested, 4),
        "prefill_tokens_requested": int(requested),
        "prefill_tokens_saved": s_cache["cache_hit_tokens"],
        "outputs_bit_exact": True,
        "prefix_cache": s_cache,
        "pr1_engine": s_plain,
        "speedup_vs_pr1": round(s_cache["tokens_per_sec"]
                                / s_plain["tokens_per_sec"], 3),
    }


def bench_serving_spec_decode(seed=0):
    """Lossless self-speculative decoding trace (PERF.md §11): prompt-lookup
    n-gram drafting + the K+1-position `verify_step` vs the SAME engine
    with speculation off, on a repetitive/extractive workload.

    Speculation only pays when the output stream is predictable, and raw
    random weights have no linguistic redundancy — their greedy outputs
    are arbitrary.  The trace therefore biases the model toward echo
    behavior (block weights down-scaled so the residual stream stays
    embedding-dominated, LM head tied to the embedding transpose), which
    makes greedy decode settle into repetition — the structural analog of
    extractive / template / multi-turn-echo traffic, independent of model
    quality.  Both engines run the SAME model and trace; greedy outputs
    are asserted bit-identical before any number is reported, and the
    measured acceptance rate prints alongside the speedup so the result
    can't overclaim (acceptance ~1.0 here is the trace's design point;
    mixed traffic sits in between — parity holds at ANY acceptance)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import Telemetry

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    slo_ttft = 0.25 if on_tpu else 2.0   # TTFT deadline for goodput readout
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=16,
                          num_attention_heads=16, num_key_value_heads=4,
                          max_position_embeddings=2048)
        dtype = jnp.bfloat16
        n_req, slots, page_size, horizon, t_bucket = 16, 8, 64, 16, 128
        len_lo, len_hi, max_new, spec_k = 32, 128, 192, 8
    else:
        # bigger than the other CPU shakeout configs ON PURPOSE: ~65 MB of
        # f32 weights exceeds typical L3, so decode is memory-bound the way
        # TPU batch-1 decode is MXU-starved — the regime speculation is
        # for.  (At cache-resident sizes the comparison just measures the
        # host's momentary cache state and flips run to run.)
        cfg = LlamaConfig(vocab_size=4096, hidden_size=512,
                          intermediate_size=1536, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=2,
                          max_position_embeddings=512)
        dtype = jnp.float32
        n_req, slots, page_size, horizon, t_bucket = 8, 4, 16, 16, 32
        len_lo, len_hi, max_new, spec_k = 16, 48, 96, 8

    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    bp = {k: (v * 0.05 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    params = (ep, bp, hp)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
               for t in rng.integers(len_lo, len_hi, n_req)]
    # pool sized so the whole trace (live slots + retired pages parked in
    # the prefix cache) fits without eviction churn: this trace measures
    # speculation, not memory pressure (eviction has its own drills)
    worst = (len_hi + max_new) // page_size + 2
    # warm prompts fixed up front so BOTH engines see the identical set
    warm = [rng.integers(1, cfg.vocab_size, (Tb,)).astype(np.int32)
            for Tb in sorted({((len(p) + t_bucket - 1) // t_bucket)
                              * t_bucket for p in prompts})]

    def run_trace(spec):
        eng = ServingEngine(params, cfg, num_slots=slots,
                            page_size=page_size,
                            num_pages=(n_req + slots + 2) * worst,
                            max_pages_per_seq=worst, dtype=dtype,
                            decode_horizon=horizon, prompt_bucket=t_bucket,
                            speculative=spec, telemetry=Telemetry())
        # warm every executable (prefill buckets + horizon + verify)
        for w in warm:
            eng.submit(w, max_new_tokens=horizon + spec_k + 2)
        eng.run()
        base_stats = eng.stats()
        # scope the SLO report to the timed window below
        eng.telemetry.reset_window()
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        done = eng.run()
        dt = time.perf_counter() - t0
        _sync(eng._pages_k[0, 0, 0, 0, 0])
        outs = [done[r].output_ids for r in rids]
        ttfts = [done[r].first_token_time - done[r].submit_time for r in rids]
        stats = eng.stats()
        prop = stats["draft_tokens_proposed"] - base_stats[
            "draft_tokens_proposed"]
        acc = stats["draft_tokens_accepted"] - base_stats[
            "draft_tokens_accepted"]
        return outs, {
            "tokens_per_sec": round(n_req * max_new / dt, 1),
            **_ttft_report(ttfts, slo_ttft),
            "draft_tokens_proposed": int(prop),
            "draft_tokens_accepted": int(acc),
            "accept_rate": round(acc / prop, 4) if prop else None,
            "verify_steps": stats["verify_steps"]
            - base_stats["verify_steps"],
            "decode_steps": stats["decode_steps"]
            - base_stats["decode_steps"],
            # greedy spec traffic: every dispatch (horizon AND verify)
            # must be token-emitting — fused_frac 1.0
            "fused_sampling": _fused_sampling_report(stats),
            "engine_stats": stats,
            # full telemetry snapshot + SLO report over the timed window
            "metrics": eng.telemetry.snapshot(stats),
            "slo_report": eng.telemetry.slo_report(slo_ttft, window_s=dt),
            # host/device decomposition + memory/compile observatory over
            # the timed window (compile counts are engine-cumulative)
            "utilization": eng.telemetry.utilization_report(window_s=dt),
            "memory": eng.telemetry.memory_report(stats),
            "compile": eng.telemetry.compile_report(),
        }

    out_off, s_off = run_trace(None)
    out_on, s_on = run_trace(spec_k)
    # lossless or the numbers lie: bit-exact greedy parity asserted FIRST
    for a, b in zip(out_off, out_on):
        np.testing.assert_array_equal(a, b)
    return {
        "trace": {"n_requests": n_req, "max_new_tokens": max_new,
                  "speculative_k": spec_k, "decode_horizon": horizon,
                  "num_slots": slots, "page_size": page_size,
                  "seed": int(seed)},
        "outputs_bit_exact": True,
        "useful_tokens": int(n_req * max_new),
        "accept_rate": s_on["accept_rate"],
        "speculative": s_on,
        "baseline": s_off,
        "speedup_vs_no_spec": round(s_on["tokens_per_sec"]
                                    / s_off["tokens_per_sec"], 3),
    }


def bench_serving_failover(seed=0, perfetto=None):
    """Replica-failover drill trace (ISSUE 9; PERF.md §16): a 2-replica
    ``serving.ReplicaFleet`` with periodic full-KV engine snapshots serves
    a mixed-length greedy trace while a seeded ``serve.crash`` kills
    replica r0 mid-trace.  The fleet revives r0 from its newest intact
    snapshot and migrates whatever the snapshot misses by re-prefill of
    prompt + streamed tokens.

    ZERO lost requests and bit-equal outputs vs the uninterrupted
    single-engine run are ASSERTED before anything is reported; the
    artifact then carries the measured recovery time (the failover
    handler's wall clock: detect -> restore -> migrate) and
    goodput-at-deadline through the shared ``slo_report`` schema
    (validated by ``perf/check_obs.py --trace failover``).

    Since ISSUE 12 the replicas run with telemetry ON and the artifact
    additionally carries the fleet-wide observability plane: the
    ``fleet`` block gains bucket-wise MERGED replica histograms +
    per-replica gauges (``ReplicaFleet.stats_snapshot``), and the
    ``stitched`` block summarizes the cross-component Perfetto trace —
    the crashed request must read as ONE timeline (router span ->
    replica r0 -> migration flow-event -> surviving/revived replica).
    ``perfetto`` (or ``--perfetto PATH``) writes the stitched trace
    JSON for ui.perfetto.dev."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import HealthSentinel, Telemetry
    from paddle_tpu.serving import ReplicaFleet
    from paddle_tpu.resilience import inject

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    slo_ttft = 0.25 if on_tpu else 2.0
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    n_req, slots, page_size, horizon = 10, 2, 8, 4
    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    params = (ep, bp, hp)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (int(t),)).astype(np.int32)
               for t in rng.integers(8, 48, n_req)]
    max_news = [int(m) for m in rng.integers(8, 24, n_req)]

    def factory():
        # sentinel-ON replicas (ISSUE 13): every replica watches its own
        # queue/occupancy/burn trends; fires land in the flight ring the
        # failover dump captures.  Revived replicas get a fresh sentinel
        # with the rest of their telemetry.
        return ServingEngine(params, cfg, num_slots=slots,
                             page_size=page_size, num_pages=96,
                             max_pages_per_seq=16, dtype=dtype,
                             attention_impl="auto" if on_tpu else "ref",
                             prompt_bucket=16, decode_horizon=horizon,
                             telemetry=Telemetry(
                                 sentinel=HealthSentinel(
                                     slo_ttft_s=slo_ttft)))

    # the uninterrupted single-engine reference (the bit-exactness bar)
    eng = factory()
    ref_rids = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, max_news)]
    ref_done = eng.run()
    refs = [np.asarray(ref_done[r].output_ids) for r in ref_rids]

    crash_at = int(rng.integers(6, 14))   # serve.crash consult index
    with tempfile.TemporaryDirectory() as snap_root:
        fleet = ReplicaFleet(factory, num_replicas=2,
                             snapshot_root=snap_root, snapshot_every=4,
                             snapshot_mode="full_kv")
        t0 = time.perf_counter()
        with inject({"serve.crash": dict(match={"engine": "r0"},
                                         at=crash_at)}, seed=seed) as plan:
            # two arrival waves: the second lands AFTER the last periodic
            # snapshot, so the failover exercises both recovery paths —
            # snapshot restore for wave 1, re-prefill migration for
            # whatever the snapshot misses
            wave1 = n_req * 2 // 3
            frids = [fleet.submit(p, max_new_tokens=m)
                     for p, m in zip(prompts[:wave1], max_news[:wave1])]
            fleet.run(max_rounds=5)
            frids += [fleet.submit(p, max_new_tokens=m)
                      for p, m in zip(prompts[wave1:], max_news[wave1:])]
            done = fleet.run()
        dt = time.perf_counter() - t0
    assert plan.fired("serve.crash") == 1, "the crash drill did not fire"
    lost = len(frids) - len(done)
    assert lost == 0, f"failover lost {lost} requests"
    # zero lost AND bit-equal asserted BEFORE reporting
    for frid, ref in zip(frids, refs):
        np.testing.assert_array_equal(np.asarray(done[frid].output_ids),
                                      ref)
    # fleet-wide observability plane (ISSUE 12): the stats_snapshot merges
    # replica histograms bucket-wise + keeps gauges per replica; the
    # stitcher produces ONE Perfetto view whose flow events bind the
    # crashed request's spans across router/r0(crashed)/survivor tracks
    st = fleet.stats_snapshot(ttft_deadline_s=slo_ttft)
    useful = sum(max_news)
    ev = [e["event"] for e in fleet.flight.events()]
    stitcher = fleet.stitcher()
    stitched = stitcher.summary()
    assert len(stitched["max_chain"]) >= 3, \
        f"crashed request did not stitch across components: {stitched}"
    # ISSUE 13: stitched critical-path attribution across router + crashed
    # + revived replicas — EVERY end-to-end request (the crashed/migrated
    # ones included) must decompose into exact disjoint segments summing
    # to its traced e2e, asserted BEFORE anything is reported
    attribution = fleet.attribution_report()
    assert attribution["requests"] == n_req, \
        f"attribution saw {attribution['requests']}/{n_req} requests"
    assert attribution["exact_requests"] == attribution["requests"], \
        f"attribution not exact on {attribution['requests'] - attribution['exact_requests']} request(s)"
    slow = fleet.slow_requests()
    if perfetto:
        stitcher.export_chrome(perfetto)
        stitched["perfetto_path"] = perfetto
    dump = fleet.flight.last_dump()
    return {
        "trace": {"n_requests": n_req, "num_replicas": 2,
                  "snapshot_every": 4, "crash_at_consult": crash_at,
                  "decode_horizon": horizon, "num_slots": slots,
                  "page_size": page_size, "seed": int(seed)},
        "lost_requests": 0,
        "outputs_bitexact": True,
        "useful_tokens": int(useful),
        "tokens_per_sec": round(useful / dt, 1),
        "recovery_ms_p50": st["recovery"]["p50_ms"],
        "recovered_from_snapshot": "restore" in ev,
        "fleet": st,
        "stitched": stitched,
        # ISSUE 13: per-request critical-path attribution (exactness
        # asserted above) + the aggregated health-sentinel view + the
        # fleet tail-outlier capture
        "attribution": attribution,
        "alerts": st["alerts"],
        "slow_requests": {
            "captured": len(slow),
            "slowest": {k: slow[0][k] for k in
                        ("component", "rid", "e2e_s")} if slow else None,
        },
        # the merged failover dump (dying replica's flight ring + the
        # router's last-N routing decisions in ONE artifact)
        "failover_dump": {
            "reason": dump["reason"] if dump else None,
            "routing_decisions": len((dump or {}).get("extra", {})
                                     .get("routing_decisions") or []),
            "replica_ring_events": len((dump or {}).get("extra", {})
                                       .get("replica_ring") or []),
        },
        "slo_report": fleet.slo_report(slo_ttft, window_s=dt),
        "metrics": fleet.metrics_snapshot(),
    }


def bench_serving_failover_proc(seed=0):
    """Cross-PROCESS failover drill (ISSUE 17; `--trace failover --proc`):
    the same zero-loss bar as :func:`bench_serving_failover`, but the
    replica boundary is a real OS process and the crash is a real
    ``SIGKILL`` — no injected exception, no shared address space, the
    dead worker's host state is simply GONE and recovery runs over the
    wire (newest intact snapshot restore + adopt re-prefill).

    Three paired arms from ONE deterministic spec (`paddle.seed` +
    explicit PRNG key, so every process builds bit-identical weights):

      * **single** — the uninterrupted in-process engine: the
        bit-exactness reference and the no-fleet throughput bar.
      * **thread** — a 2-replica ``ReplicaFleet`` (thread boundary) with
        an injected ``serve.crash``: what PR 9's failover costs when the
        supervisor can reach into the replica's memory.
      * **proc** — a 2-worker ``ProcessFleet``; one worker is
        SIGKILL'ed mid-decode and the supervisor recovers it zero-loss.

    ZERO lost requests and bit-equal greedy outputs are ASSERTED for
    both fleet arms BEFORE anything is reported; the proc arm
    additionally asserts wall-clock recovery was measured, the RPC plane
    carried real traffic, the stitched trace crosses the process
    boundary, and EVERY spawned worker generation (the killed one
    included) filed a passing invariants report."""
    import signal
    import tempfile
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.serving import ProcessFleet, ReplicaFleet
    from paddle_tpu.serving.worker import build_from_spec
    from paddle_tpu.resilience import inject

    spec = {
        "seed": 2024,
        "model": {"config": dict(vocab_size=128, hidden_size=64,
                                 intermediate_size=192,
                                 num_hidden_layers=2,
                                 num_attention_heads=4,
                                 num_key_value_heads=4,
                                 max_position_embeddings=128),
                  "prng_key": 1, "n_micro": 1},
        "engine": dict(num_slots=2, page_size=4, num_pages=64,
                       max_pages_per_seq=24, attention_impl="ref",
                       prompt_bucket=8, decode_horizon=2),
    }
    n_req, n_new = 8, 16
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 128, (int(t),)).astype(np.int32)
               for t in rng.integers(3, 8, n_req)]
    useful = n_req * n_new

    # proc FIRST: real worker processes, real SIGKILL mid-decode.  A
    # parent that has touched a JAX backend holds the accelerator and its
    # children then cannot get it — so the workers are spawned (and shut
    # down) before this process builds its own in-process arms below
    with tempfile.TemporaryDirectory() as workdir:
        fl = ProcessFleet(spec, workdir=workdir, num_workers=2,
                          snapshot_every=3, trace_every=2)
        try:
            t0 = time.perf_counter()
            pfrids = [fl.submit(p, max_new_tokens=n_new) for p in prompts]
            while fl.tokens_streamed < 6:
                fl.step()
            victim = fl._workers[0]
            dead_key = victim.key()
            os.kill(victim.pid, signal.SIGKILL)
            pdone = fl.run()
            proc_dt = time.perf_counter() - t0
            assert len(pdone) == len(pfrids), "proc arm lost requests"
            proc_outs = [list(pdone[frid].generated) for frid in pfrids]
            st = fl.stats()
            assert st["failovers"] >= 1, "the SIGKILL drill never failed over"
            assert st["worker_restarts"].get("w0", 0) >= 1
            assert st["recovery"]["count"] >= 1 \
                and st["recovery"]["p50_ms"] > 0.0, \
                "no wall-clock recovery time was measured"
            assert st["rpc"]["calls"] > 0
            stitched = fl.stitcher().summary()
            assert len(stitched["max_chain"]) >= 2, \
                f"trace did not cross the process boundary: {stitched}"
        finally:
            fl.shutdown()
        fl.assert_worker_invariants()
        reports = {k: {kk: r.get(kk) for kk in
                       ("invariants_ok", "kind", "via")}
                   for k, r in sorted(fl.final_reports.items())}

    # single: the uninterrupted reference (and the no-fleet throughput bar)
    params, cfg, ekw = build_from_spec(spec)
    eng = ServingEngine(params, cfg, **ekw)
    rids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    t0 = time.perf_counter()
    ref_done = eng.run()
    single_dt = time.perf_counter() - t0
    refs = [list(ref_done[r].generated) for r in rids]
    eng.release_cache()
    assert proc_outs == refs, \
        "proc arm diverged from the uninterrupted engine"

    # thread: PR 9's in-process replica fleet under an injected crash
    fleet = ReplicaFleet(lambda: ServingEngine(params, cfg, **ekw),
                         num_replicas=2)
    t0 = time.perf_counter()
    with inject({"serve.crash": dict(match={"engine": "r0"}, at=6)},
                seed=seed) as plan:
        tfrids = [fleet.submit(p, max_new_tokens=n_new) for p in prompts]
        tdone = fleet.run()
    thread_dt = time.perf_counter() - t0
    assert plan.fired("serve.crash") == 1
    assert len(tdone) == len(tfrids), "thread arm lost requests"
    for frid, ref in zip(tfrids, refs):
        assert list(tdone[frid].generated) == ref, \
            "thread arm diverged from the uninterrupted engine"
    thread_st = fleet.stats()

    assert reports[dead_key]["via"] == "replacement_restore"

    proc_tps = useful / proc_dt
    thread_tps = useful / thread_dt
    return {
        "trace": {"n_requests": n_req, "max_new_tokens": n_new,
                  "num_workers": 2, "snapshot_every": 3,
                  "seed": int(seed), "kill": "SIGKILL mid-decode"},
        "lost_requests": 0,
        "outputs_bitexact": True,
        "useful_tokens": int(useful),
        "single": {"tokens_per_sec": round(useful / single_dt, 1)},
        "thread": {"tokens_per_sec": round(thread_tps, 1),
                   "failovers": thread_st["failovers"],
                   "migrations": thread_st["migrations"]},
        "proc": {"tokens_per_sec": round(proc_tps, 1),
                 "failovers": st["failovers"],
                 "worker_restarts": st["worker_restarts"],
                 "spawns": st["spawns"],
                 "rpc": st["rpc"],
                 "recovery": st["recovery"]},
        "boundary_overhead_x": round(thread_tps / proc_tps, 2),
        # check_obs gates recovery p50 under a HOST-AWARE ceiling
        # (single-core hosts get slack) — the wall-clock half of the
        # elastic trace's virtual-clock economics (ROADMAP item 5)
        "host_cpu_count": os.cpu_count(),
        "stitched": {"max_chain": stitched["max_chain"],
                     "components": stitched.get("components"),
                     "flow_events": stitched.get("flow_events")},
        "worker_invariants_ok": True,
        "final_reports": reports,
    }


def bench_serving_elastic(seed=0):
    """Elastic cache-affinity fleet trace (ISSUE 14; PERF.md §21): a
    seeded DIURNAL shared-prefix scenario replayed against four fleet
    arms — fixed-1, fixed-2, fixed-peak, and an ``ElasticFleet`` that
    scales 1..peak on the sentinel's ``queue_growth``/``fleet_idle``
    signals and drains replicas zero-loss through the live-migration
    path — plus a least-loaded fixed-2 arm that demonstrates the
    chain-splitting problem ``PrefixAffinityRouter`` exists to fix.

    Everything runs on a ROUND-DRIVEN VIRTUAL CLOCK (each fleet
    heartbeat = ``dt`` virtual seconds, modeling every replica as its
    own concurrently-stepping host — the only honest fleet-economics
    model when all replicas time-share one bench CPU), so every
    reported number is DETERMINISTIC for a given seed: arrival pacing,
    TTFT, replica-seconds, the scale-event timeline, hit rates.

    Asserted BEFORE reporting, on every arm: zero lost requests and
    greedy streams bit-equal the uninterrupted single-engine run —
    across every scale-up and drain event.  The elastic arm must log
    >= 1 scale-up AND >= 1 scale-down.  Gates (check_obs ``--trace
    elastic``): elastic >= every fixed arm on goodput-per-replica-hour
    (on-time requests per replica-hour of virtual uptime), and
    fleet-wide prefix-cache hit rate with affinity routing >= 0.9x the
    single-engine rate (least-loaded routing demonstrably splits the
    chains; affinity must recover the gap)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import Telemetry
    from paddle_tpu.serving import (AutoscalePolicy, ElasticFleet,
                                    LeastLoadedRouter, PrefixAffinityRouter,
                                    ReplicaFleet, VirtualClock,
                                    make_scenario, replay_fleet)

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    slots, page_size, horizon, t_bucket = 2, 8, 4, 32
    n_req, n_users, peak = 40, 6, 3
    dt = 0.5            # virtual seconds per fleet round
    slo_v = 3.0         # virtual-seconds TTFT deadline

    # two diurnal peaks with a deep valley between them: the peak
    # (~2.4x a single replica's round capacity) forces scale-up, the
    # valley pays fixed fleets for idle replicas the elastic arm drains
    sc = make_scenario("elastic-diurnal", seed=seed + 5, n_requests=n_req,
                       vocab=cfg.vocab_size, arrival="diurnal",
                       mean_interarrival_s=0.8, diurnal_period_s=30.0,
                       diurnal_amplitude=0.97, prompt_len=(5, 12),
                       max_new=(10, 18), shared_prefix_users=n_users,
                       system_prompt_len=24)

    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    params = (ep, bp, hp)

    def factory():
        return ServingEngine(params, cfg, num_slots=slots,
                             page_size=page_size, num_pages=160,
                             max_pages_per_seq=16, dtype=dtype,
                             attention_impl="auto" if on_tpu else "ref",
                             prompt_bucket=t_bucket, decode_horizon=horizon,
                             telemetry=Telemetry())

    # the uninterrupted single-engine reference: greedy outputs (the
    # bit-equality bar for every arm — a request's greedy continuation
    # depends only on its prompt) and the single-engine hit rate (the
    # bar affinity routing must approach fleet-wide)
    ref_eng = factory()
    rids = [ref_eng.submit(r.prompt, max_new_tokens=r.max_new_tokens)
            for r in sc.requests]
    ref_done = ref_eng.run()
    refs = {r.idx: list(ref_done[rid].generated)
            for r, rid in zip(sc.requests, rids)}
    rst = ref_eng.stats()
    hit_single = rst["cached_prefix_tokens"] / max(
        1, rst["cached_prefix_tokens"] + rst["prefill_tokens_executed"])

    def policy():
        # grow on 2-deep growth over a 2.0v window (>= 3 queued), drain
        # when mean load per routable replica sits <= 1.0 for a whole
        # 2.5v window — a 2-slot replica at load 1 is half empty
        return AutoscalePolicy(
            min_replicas=1, max_replicas=peak,
            queue_growth=2.0, queue_min_depth=3.0, growth_window_s=2.0,
            growth_fire_frac=0.34, idle_per_replica=1.0,
            idle_window_s=2.5, min_samples=3, scale_cooldown_s=2.0,
            dt_per_round=dt)

    def run_arm(label, *, elastic=False, n_fixed=1, affinity=True):
        vc = VirtualClock(dt)
        # max_imbalance=2: these replicas only have 2 slots — affinity
        # may queue a request at most 2 deeper than the idlest replica
        router = PrefixAffinityRouter(max_imbalance=2) if affinity \
            else LeastLoadedRouter()
        if elastic:
            fleet = ElasticFleet(factory, policy=policy(), router=router,
                                 clock=vc)
        else:
            fleet = ReplicaFleet(factory, num_replicas=n_fixed,
                                 router=router, clock=vc)
        res = replay_fleet(fleet, sc, slo_ttft_s=slo_v, virtual_clock=vc,
                           collect_tokens=True)
        # ZERO lost + bit-equal across every scale/drain event, per arm
        lost = [rec["idx"] for rec in res["records"]
                if rec["rejected"] or rec["tokens"] == 0]
        assert not lost, f"{label}: lost/empty requests {lost}"
        for rec in res["records"]:
            assert rec["stream"] == refs[rec["idx"]], \
                f"{label}: request {rec['idx']} diverged from the " \
                f"uninterrupted single-engine reference"
        hit = fleet.fleet_hit_rate()
        rep = res["report"]
        rh = res["replica_seconds"] / 3600.0
        section = {
            "requests": n_req,
            "on_time_requests": rep["on_time_requests"],
            "goodput_fraction": rep["goodput_fraction"],
            "replica_seconds_v": round(res["replica_seconds"], 2),
            "goodput_per_replica_hour": round(
                rep["on_time_requests"] / rh, 1) if rh else 0.0,
            "window_v_s": round(res["window_s"], 2),
            "hit_rate": hit["hit_rate"],
            "migrations": fleet.stats()["migrations"],
            "slo_report": rep,
        }
        return fleet, section

    _, fixed1 = run_arm("fixed-1", n_fixed=1)
    fl2a, fixed2 = run_arm("fixed-2 affinity", n_fixed=2)
    _, fixed2_ll = run_arm("fixed-2 least-loaded", n_fixed=2,
                           affinity=False)
    _, fixedp = run_arm(f"fixed-{peak}", n_fixed=peak)
    efleet, elastic = run_arm("elastic", elastic=True)

    est = efleet.stats()
    assert est["scale_ups"] >= 1 and est["scale_downs"] >= 1, \
        f"elastic arm never scaled: {est['scale_ups']} up / " \
        f"{est['scale_downs']} down"
    fixed_arms = {"1": fixed1, "2": fixed2, "peak": fixedp}
    # a fixed arm at 0 goodput/replica-hour is a DEGENERATE baseline,
    # not a free win: report ratio 0.0 so the check_obs floor fails the
    # trace instead of a fabricated pass
    ratios = {k: round(elastic["goodput_per_replica_hour"]
                       / v["goodput_per_replica_hour"], 4)
              if v["goodput_per_replica_hour"] else 0.0
              for k, v in fixed_arms.items()}
    # the routing gate is the CONTROLLED arm (fixed-2 affinity vs the
    # single engine — same replica count the least-loaded split arm
    # runs): elastic's hit rate additionally pays replica churn (drained
    # caches die, fresh replicas start cold) and is reported, not gated
    hit_ratio = round(fixed2["hit_rate"] / hit_single, 4) \
        if hit_single else 1.0
    return {
        "trace": {"n_requests": n_req, "shared_prefix_users": n_users,
                  "arrival": "diurnal", "mean_interarrival_s": 0.8,
                  "diurnal_period_s": 30.0,
                  "diurnal_amplitude": 0.97, "dt_round_s": dt,
                  "slo_ttft_v_s": slo_v, "peak_replicas": peak,
                  "seed": int(seed), "scenario_signature":
                  sc.signature()[:16],
                  "clock": "round-driven virtual (deterministic; each "
                           "replica modeled as its own host)"},
        "lost_requests": 0,           # asserted per arm above
        "outputs_bitexact": True,     # asserted per arm above
        "scale_ups": est["scale_ups"],
        "scale_downs": est["scale_downs"],
        "drain_migrations": est["drain_migrations"],
        "scale_events": efleet.scale_events,
        "goodput_per_replica_hour": {
            "elastic": elastic["goodput_per_replica_hour"],
            "fixed": {k: v["goodput_per_replica_hour"]
                      for k, v in fixed_arms.items()},
            "ratios_elastic_vs_fixed": ratios,
            "min_ratio": min(ratios.values()),
        },
        "hit_rate": {
            "single_engine": round(hit_single, 4),
            "affinity_fixed2": fixed2["hit_rate"],
            "least_loaded_fixed2": fixed2_ll["hit_rate"],
            "elastic": elastic["hit_rate"],
            "ratio_vs_single": hit_ratio,
            "split_demonstrated": fixed2_ll["hit_rate"]
            < fixed2["hit_rate"],
        },
        "router": fl2a.router.stats(),
        "arms": {"fixed_1": fixed1, "fixed_2_affinity": fixed2,
                 "fixed_2_least_loaded": fixed2_ll,
                 f"fixed_{peak}": fixedp, "elastic": elastic},
        "autoscale": est["autoscale"],
        "fleet": efleet.stats_snapshot(ttft_deadline_s=slo_v),
        "slo_report": elastic["slo_report"],
        # ROADMAP item-5 leftover (closed in ISSUE 19): this trace's
        # economics are VIRTUAL-clock — each replica modeled as its own
        # concurrently-stepping host, which today's autoscaler (threads on
        # one process) cannot deliver in wall time.  The artifact says so
        # explicitly, and the wall-clock side of the story lives in the
        # --proc failover arm (real worker processes, real SIGKILL, a
        # HOST-AWARE recovery ceiling in check_obs) — so the elastic gate
        # stays deterministic while proc-smoke carries the machine-varying
        # measurement, instead of the two drifting apart as hosts vary.
        "parallelism": {
            "model": "virtual (round-driven clock; replicas modeled as "
                     "concurrent hosts)",
            "wall_clock_arm": "bench.py --trace failover --proc "
                              "(ProcessFleet; host-aware recovery ceiling "
                              "in check_obs)",
            "note": "re-measure this trace on wall clock when the "
                    "autoscaler scales ProcessFleet workers "
                    "(ROADMAP item 5 runway)"},
        "host_cpu_count": os.cpu_count(),
    }


def bench_serving_disagg(seed=0):
    """Disaggregated prefill/decode A/B (ISSUE 19; PERF.md §26): a
    PREFILL-HEAVY trace (long prompts, short generations) replayed
    against two fleet arms at a FIXED chip count of 4:

      * colocated-TP — 2 interchangeable replicas, each a ServingEngine
        TP-sharded over its own mp=2 submesh, running CHUNKED prefill
        (the TPOT-protecting configuration: a colocated replica must
        interleave long prefills with its resident decodes);
      * disaggregated — 1 prefill-role replica (DENSE prefill + first
        tokens, mp=2 on chips 0-1) handing head-sharded KV pages to 1
        decode-role replica (mp=2 on chips 2-3) via
        ``export_kv``/``import_kv``.  Equal mp degree on both sides, so
        every handoff is RANK-LOCAL.

    Both arms run on a round-driven VirtualClock shared by the fleet AND
    every replica's Telemetry (one clock domain: request stamps, TTFT,
    the kv_transfer gap, deadlines), so every reported number is
    deterministic for a given seed.  Asserted BEFORE reporting, per arm:
    zero lost requests and greedy streams bit-equal the uninterrupted
    single-chip engine (the TP arms add psum reassociation; the
    margin-engineered params keep argmax above that noise).  Gates
    (check_obs ``--trace disagg``): TTFT p95 win ratio at fixed chips,
    every handoff rank-local with zero fallbacks, the transfer visible
    as an EXACT ``kv_transfer`` attribution segment, and the
    ``kv_transfer_frac`` / ``disagg_ttft_p95_ms`` bench_trend columns.

    Methodology caveat (the §25 framing, carried): forced-host "chips"
    time-slice one CPU, so WALL-clock throughput is dispatch overhead,
    not speedup — every gated number here is virtual-clock.  And the
    round model prices a dense-prefill round and a chunk round
    identically (dt each), so the colocated arm's chunked prefill is
    charged only its ROUND COUNT — the TPOT stall dense prefill would
    inflict on co-resident decodes is the reason colocated serving
    chunks, but it is not itself priced by this clock."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.observability import Telemetry
    from paddle_tpu.serving import (ReplicaFleet, VirtualClock,
                                    make_scenario, replay_fleet)

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(
            "disagg trace needs 4 devices (2 submeshes of mp=2) — CPU "
            "hosts get them via the forced-host flag bench.py __main__ "
            "sets for --trace disagg")
    on_tpu = any(d.platform == "tpu" for d in devs)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512)
    page_size, horizon, t_bucket = 16, 4, 32
    dt = 0.5            # virtual seconds per fleet round
    slo_v = 6.0         # virtual-seconds TTFT deadline
    n_req = 24

    # margin-engineered params (the TP/quant construction): greedy argmax
    # stays far above psum reassociation noise, so bit-exactness measures
    # the ENGINES, not the noise floor of near-uniform random logits
    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1,
                                            key=jax.random.PRNGKey(7))
    bp = {k: (v * 0.15 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    params = (ep, bp, hp)

    # prefill-heavy: 40-88 token prompts, 8-13 new tokens — the workload
    # disaggregation exists for (prefill rounds dominate a colocated
    # slot's dwell time)
    sc = make_scenario("disagg-prefill-heavy", seed=seed + 9,
                       n_requests=n_req, vocab=cfg.vocab_size,
                       arrival="poisson", mean_interarrival_s=0.8,
                       prompt_len=(40, 88), max_new=(8, 13))
    worst = (96 + 13 + horizon) // page_size + 2

    def mk_engine(mesh, vc, slots, **kw):
        return ServingEngine(params, cfg, num_slots=slots,
                             page_size=page_size,
                             num_pages=(slots + 2) * worst,
                             max_pages_per_seq=worst, dtype=dtype,
                             attention_impl="auto" if on_tpu else "ref",
                             prompt_bucket=t_bucket, decode_horizon=horizon,
                             mesh=mesh, telemetry=Telemetry(clock=vc), **kw)

    # uninterrupted single-chip reference: the bit-equality bar for BOTH
    # TP arms (a request's greedy continuation depends only on its prompt)
    ref_eng = ServingEngine(params, cfg, num_slots=2, page_size=page_size,
                            num_pages=4 * worst, max_pages_per_seq=worst,
                            dtype=dtype,
                            attention_impl="auto" if on_tpu else "ref",
                            prompt_bucket=t_bucket, decode_horizon=horizon)
    rids = [ref_eng.submit(r.prompt, max_new_tokens=r.max_new_tokens)
            for r in sc.requests]
    ref_done = ref_eng.run()
    refs = {r.idx: list(ref_done[rid].generated)
            for r, rid in zip(sc.requests, rids)}

    def run_arm(label, *, roles):
        vc = VirtualClock(dt)
        if roles is None:
            # colocated: interchangeable replicas, chips 0-1 and 2-3,
            # chunked prefill (one page-sized chunk per round), 3 slots
            # each — 6 slots / 4 chips total
            nxt = itertools.cycle((devs[:2], devs[2:4]))

            def factory(role="any"):
                mesh = build_mesh({"mp": 2}, devices=next(nxt))
                return mk_engine(mesh, vc, 3, prefill_chunk=page_size)
            fleet = ReplicaFleet(factory, num_replicas=2, clock=vc)
        else:
            # disagg: prefill on chips 0-1 (2 slots, DENSE prefill),
            # decode on chips 2-3 (4 slots) — 6 slots / 4 chips total
            def factory(role="any"):
                if role == "prefill":
                    return mk_engine(build_mesh({"mp": 2},
                                                devices=devs[:2]), vc, 2)
                return mk_engine(build_mesh({"mp": 2},
                                            devices=devs[2:4]), vc, 4)
            fleet = ReplicaFleet(factory, num_replicas=2, roles=roles,
                                 clock=vc)
        res = replay_fleet(fleet, sc, slo_ttft_s=slo_v, virtual_clock=vc,
                           collect_tokens=True)
        lost = [rec["idx"] for rec in res["records"]
                if rec["rejected"] or rec["tokens"] == 0]
        assert not lost, f"{label}: lost/empty requests {lost}"
        for rec in res["records"]:
            assert rec["stream"] == refs[rec["idx"]], \
                f"{label}: request {rec['idx']} diverged from the " \
                f"uninterrupted single-chip reference"
        ttfts = [rec["ttft_s"] for rec in res["records"]]
        rep = res["report"]
        section = {
            "requests": n_req,
            "on_time_requests": rep["on_time_requests"],
            "goodput_fraction": rep["goodput_fraction"],
            "ttft_p50_v_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
            "ttft_p95_v_ms": round(float(np.percentile(ttfts, 95)) * 1e3, 1),
            "window_v_s": round(res["window_s"], 2),
            "replica_seconds_v": round(res["replica_seconds"], 2),
            "migrations": fleet.stats()["migrations"],
            "slo_report": rep,
        }
        return fleet, section

    _, col = run_arm("colocated-tp", roles=None)
    fleet_d, dis = run_arm("disagg", roles=["prefill", "decode"])

    dst = fleet_d.stats()
    assert dst["handoffs"] == n_req and dst["handoffs_pending"] == 0, \
        f"disagg arm: {dst['handoffs']}/{n_req} handoffs " \
        f"({dst['handoffs_pending']} pending)"
    attr = fleet_d.attribution_report(top_k=4)
    # the virtual clock's TTFT resolution is ONE ROUND (dt): dense
    # prefill + first token land within the submit round, so the disagg
    # arm's measured TTFT quantizes to 0.  The win ratio floors BOTH
    # arms at one round — a conservative ratio, not a divide-by-zero win
    q = dt * 1e3
    win = round(max(col["ttft_p95_v_ms"], q) / max(dis["ttft_p95_v_ms"], q),
                4)
    kv = dict(dst["kv_transfer"])
    kv_frac = attr["segments"].get("kv_transfer", {}).get("frac", 0.0)
    return {
        "trace": {"n_requests": n_req, "arrival": "poisson",
                  "mean_interarrival_s": 0.8, "prompt_len": [40, 88],
                  "max_new": [8, 13], "dt_round_s": dt,
                  "slo_ttft_v_s": slo_v, "seed": int(seed),
                  "scenario_signature": sc.signature()[:16],
                  "clock": "round-driven virtual, shared by fleet AND "
                           "replica telemetry (one clock domain; "
                           "deterministic)"},
        "chips": {"total": 4, "colocated": "2 replicas x mp=2",
                  "disagg": "prefill mp=2 (chips 0-1) + decode mp=2 "
                            "(chips 2-3)"},
        "lost_requests": 0,           # asserted per arm above
        "outputs_bitexact": True,     # asserted per arm above
        "arms": {"colocated_tp": col, "disagg": dis},
        "ttft": {"colocated_p95_v_ms": col["ttft_p95_v_ms"],
                 "disagg_p95_v_ms": dis["ttft_p95_v_ms"],
                 "colocated_p50_v_ms": col["ttft_p50_v_ms"],
                 "disagg_p50_v_ms": dis["ttft_p50_v_ms"],
                 "resolution_v_ms": q,
                 "win_ratio": win,
                 "note": "virtual TTFT quantizes to whole rounds; the "
                         "ratio floors both arms at one round (dt)"},
        "kv_transfer": {"handoffs": dst["handoffs"],
                        "fallbacks": dst["handoff_fallbacks"],
                        "pending": dst["handoffs_pending"], **kv,
                        "kv_transfer_frac": kv_frac,
                        "frac_note": "share of stitched virtual e2e "
                                     "spent in the handoff gap (1 round "
                                     "per handoff; compute spans are "
                                     "zero-width on the round clock)"},
        "roles": dst["roles"],
        "attribution": {"requests": attr["requests"],
                        "exact_requests": attr["exact_requests"],
                        "segments": attr["segments"]},
        # flat bench_trend columns (drift-checked once present)
        "disagg_ttft_p95_ms": dis["ttft_p95_v_ms"],
        "kv_transfer_frac": kv_frac,
        "host_cpu_count": os.cpu_count(),
    }


def bench_serving_quant(seed=0):
    """Quantized serving plane trace (ROADMAP item 2; PERF.md §22):
    int8-KV pages with per-(page, head, row) absmax scales + per-channel
    int8 serving weights, measured against the f32 engine on four axes —
    all asserted/schema-gated by ``perf/check_obs.py --trace quant``:

      * **parity** — greedy exact-match rate and max teacher-forced logit
        drift on the standard parity scenarios
        (``serving.quant.parity_report``).  Gate: exact_match >= 0.99.
        The parity model is margin-engineered (embedding-dominated
        residual, tied LM head — the spec-decode trace's construction):
        argmax-under-perturbation on a raw random-weight model measures
        the noise floor of near-uniform logits, not serving quality;
        PERF.md §22 records the raw-model number for honesty.
      * **capacity** — concurrent users sustained at FIXED pool bytes:
        both arms get the same byte budget, the int8 arm simply fits
        ~3.6x more pages (page_bytes accounting includes the scales).
        Gate: peak concurrent active users >= 1.8x f32, zero lost.
      * **throughput** — the dequant tax: same workload, same page
        COUNT, paired rounds; gate best-paired int8/f32 tokens/s >= 0.95.
      * **resilience re-runs** — the failover drill (2-replica quantized
        fleet, seeded ``serve.crash``, full-KV snapshots shipping scales)
        and a mini elastic drill (quantized ``ElasticFleet`` on the
        virtual-clock diurnal trace) both hold zero-lost + bit-equal vs
        the uninterrupted QUANTIZED single engine — per-row scales make
        quantization write-order independent, so the engine's whole
        self-exactness matrix survives quantization; plus a pool-pressure
        drill asserting the degradation ladder still walks admit ->
        evict -> preempt in order with bit-identical outputs."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import Telemetry
    from paddle_tpu.resilience import inject
    from paddle_tpu.serving import (AutoscalePolicy, ElasticFleet,
                                    ReplicaFleet, VirtualClock,
                                    make_scenario, replay_fleet)
    from paddle_tpu.serving.quant import page_bytes, parity_report

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    page_size, horizon, t_bucket = 8, 4, 16
    # margin-engineered parity/serving model (see docstring + PERF.md §22)
    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1,
                                            key=jax.random.PRNGKey(7))
    bp = {k: (v * 0.15 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    params = (ep, bp, hp)
    rng = np.random.default_rng(seed)

    def sync_pages(eng):
        leaf = jax.tree_util.tree_leaves(eng._pages_k)[0]
        _sync(leaf.reshape(-1)[0].astype(jnp.float32))

    # ---- 1. parity harness (the subsystem's contract) -------------------
    parity = parity_report(params, cfg, kv_dtype="int8", quantize=8,
                           engine_kw=dict(attention_impl="auto" if on_tpu
                                          else "ref"))
    assert parity["exact_match"] >= 0.99, \
        f"quantized greedy exact-match {parity['exact_match']} < 0.99: " \
        f"{parity}"

    # ---- 2. capacity at FIXED pool bytes --------------------------------
    pb_f32 = page_bytes(cfg, page_size, dtype=dtype)
    pb_q = page_bytes(cfg, page_size, kv_dtype="int8")
    n_users = 12
    prompts = [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
               for t in rng.integers(12, 21, n_users)]
    max_new = 12
    per_user = max(
        (len(p) + max_new - 1 + page_size - 1) // page_size for p in prompts)
    pool_bytes = (3 * per_user + 1) * pb_f32       # ~3 users' worth of f32
    pages_f32 = pool_bytes // pb_f32
    pages_q = pool_bytes // pb_q

    def mk_engine(kv_dtype, num_pages, slots=n_users, telemetry=None,
                  max_pages=None, **kw):
        return ServingEngine(
            params, cfg, num_slots=slots, page_size=page_size,
            num_pages=int(num_pages),
            max_pages_per_seq=max_pages or per_user + 1,
            dtype=dtype, attention_impl="auto" if on_tpu else "ref",
            prompt_bucket=t_bucket, decode_horizon=horizon,
            kv_dtype=kv_dtype, quantize=8 if kv_dtype else None,
            telemetry=telemetry, **kw)

    def drive_capacity(kv_dtype, num_pages, telemetry=None):
        eng = mk_engine(kv_dtype, num_pages, telemetry=telemetry)
        # warm the executables outside the measured drive
        eng.submit(rng.integers(1, cfg.vocab_size,
                                (t_bucket,)).astype(np.int32),
                   max_new_tokens=horizon + 1)
        eng.run()
        eng.release_cache()
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        peak = 0
        steps = 0
        while eng._queue or eng.num_active or eng.inflight_depth:
            eng.step()
            peak = max(peak, eng.num_active)
            steps += 1
            assert steps < 10_000, "capacity drive wedged"
        done = {r: req for r in rids
                if (req := eng._finished.get(r)) is not None}
        assert len(done) == n_users, \
            f"capacity arm lost {n_users - len(done)} requests"
        return eng, peak, done

    eng_f32, users_f32, done_f32 = drive_capacity(None, pages_f32)
    tel_q = Telemetry()
    eng_q, users_q, done_q = drive_capacity("int8", pages_q,
                                            telemetry=tel_q)
    capacity_ratio = users_q / users_f32
    assert capacity_ratio >= 1.8, \
        f"int8 sustained {users_q} users vs f32 {users_f32} at " \
        f"{pool_bytes} pool bytes — ratio {capacity_ratio:.2f} < 1.8"
    eng_f32.check_invariants()
    eng_q.check_invariants()
    capacity = {
        "pool_bytes": int(pool_bytes),
        "page_bytes_f32": int(pb_f32),
        "page_bytes_int8": int(pb_q),
        "pages_f32": int(pages_f32),
        "pages_int8": int(pages_q),
        "n_users_offered": n_users,
        "users_f32": int(users_f32),
        "users_int8": int(users_q),
        "capacity_ratio": round(capacity_ratio, 3),
        "preemptions_f32": eng_f32.preemptions,
        "preemptions_int8": eng_q.preemptions,
        "completed_f32": len(done_f32),
        "completed_int8": len(done_q),
    }
    # the telemetry memory observatory must report the capacity win in
    # BYTES (pages x page_bytes for the active kv_dtype)
    mem_q = tel_q.memory_report(eng_q.stats())
    assert mem_q["last"]["page_bytes"] == pb_q, mem_q["last"]

    # ---- 3. throughput: the dequant tax (same page COUNT, paired) -------
    ample = (n_users + 2) * per_user

    def drive_tps(eng):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        done = eng.run()
        sync_pages(eng)
        dt = time.perf_counter() - t0
        outs = [list(done[r].generated) for r in rids]
        eng.release_cache()
        return n_users * max_new / dt, outs

    te_f32 = mk_engine(None, ample)
    te_q = mk_engine("int8", ample)
    for e in (te_f32, te_q):                # warm pass
        drive_tps(e)
    pair_ratios = []
    tps_f32_all, tps_q_all = [], []
    outs_q0 = None
    for _ in range(3):
        tps_f, _o = drive_tps(te_f32)
        tps_q, outs_q = drive_tps(te_q)
        if outs_q0 is None:
            outs_q0 = outs_q
        assert outs_q == outs_q0, "quantized outputs drifted across rounds"
        tps_f32_all.append(tps_f)
        tps_q_all.append(tps_q)
        pair_ratios.append(tps_q / tps_f)
    best = max(range(len(pair_ratios)), key=lambda i: pair_ratios[i])
    assert pair_ratios[best] >= 0.95, \
        f"int8 tokens/s best paired ratio {pair_ratios[best]:.3f} < 0.95 " \
        f"(f32 {tps_f32_all}, int8 {tps_q_all})"
    throughput = {
        "rounds": len(pair_ratios),
        "tokens_per_sec_f32": round(tps_f32_all[best], 1),
        "tokens_per_sec_int8": round(tps_q_all[best], 1),
        "best_paired_ratio": round(pair_ratios[best], 4),
        "pair_ratios": [round(r, 4) for r in pair_ratios],
        "median_ratio": round(sorted(pair_ratios)[len(pair_ratios) // 2], 4),
        "host_cpu_count": os.cpu_count(),
    }

    # ---- 4a. degradation ladder under pool pressure, quantized ----------
    # its own TIGHT geometry (page_size 4, horizon 2): growth must cross a
    # page boundary INSIDE the pressure window for the preempt rung to be
    # reachable — the same shape the resilience ladder drills use
    lp = [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
          for t in (10, 14, 9, 12)]

    def mk_ladder(telemetry=None):
        return ServingEngine(params, cfg, num_slots=2, page_size=4,
                             num_pages=40, max_pages_per_seq=16,
                             dtype=dtype,
                             attention_impl="auto" if on_tpu else "ref",
                             prompt_bucket=8, decode_horizon=2,
                             kv_dtype="int8", quantize=8,
                             telemetry=telemetry)

    l_ref = mk_ladder()
    ref_rids = [l_ref.submit(p, max_new_tokens=8) for p in lp]
    l_refs = [list(l_ref.run()[r].generated) for r in ref_rids]
    l_eng = mk_ladder(telemetry=Telemetry())
    l_rids = [l_eng.submit(p, max_new_tokens=8) for p in lp]
    with inject({"serve.pool_pressure": dict(action="trigger", after=1,
                                             count=4)}, seed=seed):
        for _ in range(8):
            l_eng.step()
    l_done = l_eng.run()
    assert [list(l_done[r].generated) for r in l_rids] == l_refs, \
        "pool-pressure ladder changed quantized greedy outputs"
    ev = [e["event"] for e in l_eng.telemetry.flight.events()]
    assert "evict" in ev and "preempt" in ev \
        and ev.index("evict") < ev.index("preempt"), \
        f"ladder order not preserved under quantized pages: {ev}"
    l_eng.check_invariants()
    ladder = {"order_preserved": True, "outputs_bitexact": True,
              "evictions": l_eng.cache_evictions,
              "preemptions": l_eng.preemptions}

    # ---- 4b. failover re-run with quantized pages -----------------------
    fo_prompts = [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
                  for t in rng.integers(8, 24, 8)]
    fo_new = [int(m) for m in rng.integers(8, 16, 8)]

    def factory():
        return mk_engine("int8", 96, slots=2, telemetry=Telemetry(),
                         max_pages=16, name="engine")

    fo_ref = factory()
    fr = [fo_ref.submit(p, max_new_tokens=m)
          for p, m in zip(fo_prompts, fo_new)]
    fo_done = fo_ref.run()
    fo_refs = [np.asarray(fo_done[r].output_ids) for r in fr]
    crash_at = int(rng.integers(5, 10))
    with tempfile.TemporaryDirectory() as snap_root:
        fleet = ReplicaFleet(factory, num_replicas=2,
                             snapshot_root=snap_root, snapshot_every=4,
                             snapshot_mode="full_kv")
        with inject({"serve.crash": dict(match={"engine": "r0"},
                                         at=crash_at)}, seed=seed) as plan:
            frids = [fleet.submit(p, max_new_tokens=m)
                     for p, m in zip(fo_prompts[:5], fo_new[:5])]
            fleet.run(max_rounds=4)
            frids += [fleet.submit(p, max_new_tokens=m)
                      for p, m in zip(fo_prompts[5:], fo_new[5:])]
            fdone = fleet.run()
    assert plan.fired("serve.crash") == 1, "the crash drill did not fire"
    assert len(fdone) == len(frids), \
        f"quantized failover lost {len(frids) - len(fdone)} requests"
    for frid, ref in zip(frids, fo_refs):
        np.testing.assert_array_equal(np.asarray(fdone[frid].output_ids),
                                      ref)
    fo_ev = [e["event"] for e in fleet.flight.events()]
    failover_q = {
        "lost_requests": 0,
        "outputs_bitexact": True,
        "recovered_from_snapshot": "restore" in fo_ev,
        "failovers": fleet.stats()["failovers"],
        "snapshot_mode": "full_kv (quantized pages + per-row scales ship "
                         "together)",
    }

    # ---- 4c. elastic re-run with quantized pages ------------------------
    sc = make_scenario("quant-elastic", seed=seed + 5, n_requests=24,
                       vocab=cfg.vocab_size, arrival="diurnal",
                       mean_interarrival_s=0.8, diurnal_period_s=24.0,
                       diurnal_amplitude=0.97, prompt_len=(5, 12),
                       max_new=(8, 14), shared_prefix_users=4,
                       system_prompt_len=16)
    el_ref = mk_engine("int8", 160, slots=2, max_pages=16)
    el_rids = [el_ref.submit(r.prompt, max_new_tokens=r.max_new_tokens)
               for r in sc.requests]
    el_done = el_ref.run()
    el_refs = {r.idx: list(el_done[rid].generated)
               for r, rid in zip(sc.requests, el_rids)}
    dt_round = 0.5
    vc = VirtualClock(dt_round)
    efleet = ElasticFleet(
        lambda: mk_engine("int8", 160, slots=2, telemetry=Telemetry(),
                          max_pages=16),
        policy=AutoscalePolicy(
            min_replicas=1, max_replicas=3, queue_growth=2.0,
            queue_min_depth=3.0, growth_window_s=2.0, growth_fire_frac=0.34,
            idle_per_replica=1.0, idle_window_s=2.5, min_samples=3,
            scale_cooldown_s=2.0, dt_per_round=dt_round),
        clock=vc)
    res = replay_fleet(efleet, sc, slo_ttft_s=3.0, virtual_clock=vc,
                       collect_tokens=True)
    lost = [rec["idx"] for rec in res["records"]
            if rec["rejected"] or rec["tokens"] == 0]
    assert not lost, f"quantized elastic lost/empty requests {lost}"
    for rec in res["records"]:
        assert rec["stream"] == el_refs[rec["idx"]], \
            f"quantized elastic request {rec['idx']} diverged"
    est = efleet.stats()
    assert est["scale_ups"] >= 1 and est["scale_downs"] >= 1, \
        f"quantized elastic never scaled: {est['scale_ups']} up / " \
        f"{est['scale_downs']} down"
    elastic_q = {
        "lost_requests": 0,
        "outputs_bitexact": True,
        "scale_ups": est["scale_ups"],
        "scale_downs": est["scale_downs"],
        "drain_migrations": est["drain_migrations"],
    }

    return {
        "trace": {"n_users": n_users, "max_new_tokens": max_new,
                  "page_size": page_size, "decode_horizon": horizon,
                  "kv_dtype": "int8", "weight_bits": 8, "seed": int(seed),
                  "model": "margin-engineered (blocks x0.15, tied LM head "
                           "x4 — PERF.md §22 methodology)"},
        "parity": parity,
        "capacity": capacity,
        "throughput": throughput,
        "ladder": ladder,
        "failover_q": failover_q,
        "elastic_q": elastic_q,
        # telemetry sections from the int8 CAPACITY engine: the memory
        # observatory must carry the bytes-denominated pool gauges
        "engine_stats": eng_q.stats(),
        "memory": mem_q,
        "metrics": tel_q.snapshot(eng_q.stats()),
    }


def bench_serving_frontend(seed=0):
    """Async front end + SLO-aware admission trace (ISSUE 11; PERF.md
    §18): the AsyncFrontend transport and the predictive-vs-depth
    admission A/B on the traffic harness's bursty + diurnal scenarios.

    Part 1 — transport exactness: a seeded scenario (concurrent streaming
    clients, ~30% of them disconnecting mid-decode) runs through
    ``AsyncFrontend`` over one engine and directly through
    ``ServingEngine.submit()`` on a twin; greedy outputs are ASSERTED
    bit-equal per request (abandoned clients: streamed prefix of the
    reference) and the frontend engine is asserted to leak ZERO pages
    after the cancels — before any number is reported.

    Part 2 — admission A/B: bursty and diurnal scenarios replay at ~3x
    offered load (arrivals paced in TOKEN time, so the same offered load
    reaches every machine) under the predictive controller and the
    depth-cap baseline, PAIRED per round.  The SLO deadline
    self-calibrates from the measured unloaded TTFT and step time to sit
    at a full depth queue's wait, so deeper queue-rot misses it while an
    uncongested request clears with ~15x headroom.  Gate (machine-
    aware, best-paired-ratio — this container's timing varies ~2x):
    predictive goodput-under-SLO >= depth-based at equal offered load;
    prediction error rides the artifact as `ttft_pred_err_s`
    (`perf/check_obs.py --trace frontend` schema-gates all of it)."""
    import asyncio
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import LlamaConfig, build_functional_llama
    from paddle_tpu.inference.paged import ServingEngine
    from paddle_tpu.observability import (BurnRateRule, FleetTelemetry,
                                          HealthSentinel, Telemetry,
                                          aggregate_alerts)
    from paddle_tpu.serving import (AdmissionController, AsyncFrontend,
                                    make_scenario, replay_engine)

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=384, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256)
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    slots, page_size, horizon, t_bucket = 4, 8, 4, 16
    n_async, n_ab, rounds = 10, 28, 3
    mean_new = 12

    ep, bp, hp, *_ = build_functional_llama(cfg, dtype=dtype, n_micro=1)
    params = (ep, bp, hp)

    def mk_engine():
        # sentinel-ON (ISSUE 13): the stock rule set watches every engine
        # in this trace; the A/B engine additionally gets a calibrated
        # TTFT burn-rate rule once the SLO deadline is measured below
        return ServingEngine(params, cfg, num_slots=slots,
                             page_size=page_size, num_pages=200,
                             max_pages_per_seq=8, dtype=dtype,
                             attention_impl="auto" if on_tpu else "ref",
                             prompt_bucket=t_bucket, decode_horizon=horizon,
                             telemetry=Telemetry(sentinel=HealthSentinel()))

    scen_kw = dict(vocab=cfg.vocab_size, prompt_len=(5, 14),
                   max_new=(8, 16), mean_interarrival_s=1.0)

    # ---- Part 1: AsyncFrontend bit-equality + cancels + leak check ------
    sc_async = make_scenario("async", seed=seed + 1, n_requests=n_async,
                             arrival="bursty", burst_every_s=3.0,
                             burst_size=4, abandon_frac=0.3,
                             abandon_range=(2, 6), **scen_kw)
    eng_ref = mk_engine()
    ref_rids = [eng_ref.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                for r in sc_async.requests]
    ref_done = eng_ref.run()
    refs = [list(ref_done[rid].generated) for rid in ref_rids]

    eng_front = mk_engine()

    async def run_async():
        streamed = {}
        async with AsyncFrontend(eng_front) as fe:
            async def client(r):
                s = await fe.submit(r.prompt,
                                    max_new_tokens=r.max_new_tokens)
                got = []
                async for tok in s:
                    got.append(tok)
                    if r.abandon_after is not None \
                            and len(got) >= r.abandon_after:
                        s.abandon()            # mid-decode disconnect
                        break
                streamed[r.idx] = got
            await asyncio.gather(*[client(r) for r in sc_async.requests])
            await fe.drain()
        return streamed

    streamed = asyncio.run(run_async())
    abandoned = 0
    for r in sc_async.requests:
        got, ref = streamed[r.idx], refs[r.idx]
        if r.abandon_after is None:
            assert got == ref, \
                f"frontend stream diverged from direct submit (req {r.idx})"
        else:
            abandoned += 1
            assert got == ref[:len(got)], \
                f"abandoned stream not a prefix of reference (req {r.idx})"
    eng_front.release_cache()
    leaked = eng_front.pool.num_pages - eng_front.pool.num_free
    assert leaked == 0, f"frontend engine leaked {leaked} pages"
    eng_front.check_invariants()
    # ISSUE 13: critical-path attribution over the transport-exactness
    # engine (bounded trace, full span coverage): every retired request
    # must decompose into exact disjoint segments — asserted BEFORE
    # reporting (abandoned clients never retire and are excluded)
    attribution = eng_front.telemetry.attribution_report()
    assert attribution["requests"] >= 1
    assert attribution["exact_requests"] == attribution["requests"], \
        f"attribution not exact: {attribution}"
    tail_report = eng_front.telemetry.tail.report()

    # ---- calibration: unloaded TTFT + step time on a warmed engine ------
    eng = mk_engine()
    rng = np.random.default_rng(seed)
    for _ in range(2):                     # warm prefill bucket + horizon
        eng.submit(rng.integers(1, cfg.vocab_size, (10,)).astype(np.int32),
                   max_new_tokens=mean_new)
        eng.run()
    # calibration on a CLEAN window: the warmup rounds above absorbed
    # every compile, and reset_window() drops their compile-inflated
    # phase/step observations — the rates measured here are warm rates
    eng.telemetry.reset_window()
    rid = eng.submit(rng.integers(1, cfg.vocab_size, (10,)).astype(np.int32),
                     max_new_tokens=mean_new)
    eng.run()
    ttft_unloaded = eng._finished[rid].ttft
    step_h = eng.telemetry.registry.histogram("engine.step_host_s")
    step_s = step_h.percentiles()[50] if step_h.count else 0.01
    # measured warm prefill tokens/s — handed to the controllers as their
    # cold-window prior (reset_window() empties the live-rate histograms
    # right before each A/B replay, so the first admissions of every
    # round predict from these priors)
    from paddle_tpu.serving import admission_view
    prefill_rate = admission_view(eng, min_samples=1).prefill_rate_tps
    ctrl_kw = dict(default_step_s=step_s,
                   default_prefill_rate_tps=prefill_rate)
    # a request at the BACK of a full depth queue waits ~depth_cap/slots
    # slot-frees of ~mean_new decode tokens each (the same per-slot cost
    # model TTFTPredictor uses); put the deadline right at that wait, so
    # an uncongested request clears it with ~15x headroom while burst
    # spillover and deeper queue-rot land past it on any host
    depth_cap = 2 * slots
    cap_wait = (depth_cap / slots) * mean_new * (step_s / horizon)
    slo_ttft = max(3.0 * ttft_unloaded, ttft_unloaded + cap_wait)
    # the A/B engine's sentinel gets the calibrated deadline: the TTFT
    # burn-rate detector (fast/slow dual window) watches the same SLO the
    # admission controllers are judged on
    eng.telemetry.sentinel.add_rule(BurnRateRule(
        "ttft_slo_burn", slo_ttft_s=slo_ttft, severity="page"))
    # offered load ~3x capacity in token time: under sustained load the
    # engine retires ~1 request per mean_new GENERATED tokens (S slots
    # each finish every mean_new of their own tokens, and all S generate
    # concurrently — capacity per generated token is S-independent), so
    # one arrival per load_tps tokens oversubscribes by mean_new/load_tps
    overload = 3.0
    load_tps = mean_new / overload

    # ---- Part 2: predictive-vs-depth A/B on bursty + diurnal ------------
    scenarios = {}
    for name, arr_kw in (
            ("bursty", dict(arrival="bursty", burst_every_s=6.0,
                            burst_size=10, burst_spread_s=0.5)),
            ("diurnal", dict(arrival="diurnal", diurnal_period_s=14.0,
                             diurnal_amplitude=0.95))):
        sc = make_scenario(name, seed=seed + 11, n_requests=n_ab,
                           abandon_frac=0.1, abandon_range=(2, 6),
                           **arr_kw, **scen_kw)
        pred_runs, depth_runs, ratios = [], [], []
        fleet_snaps = []
        for _ in range(rounds):
            eng.release_cache()
            eng.telemetry.reset_window()
            depth_runs.append(replay_engine(
                eng, sc,
                AdmissionController(policy="depth",
                                    max_queue_depth=depth_cap, **ctrl_kw),
                load_tps=load_tps, slo_ttft_s=slo_ttft))
            eng.release_cache()
            eng.telemetry.reset_window()
            ctrl = AdmissionController(
                policy="predictive", slo_ttft_s=slo_ttft, **ctrl_kw)
            pred_runs.append(replay_engine(
                eng, sc, ctrl,
                load_tps=load_tps, slo_ttft_s=slo_ttft))
            # fleet-aggregation snapshot captured IN-ROUND, so the merged
            # engine histograms and the frontend admission counters in
            # one snapshot describe the SAME round's window (the engine
            # telemetry resets at the next round's start)
            fleet_snaps.append(FleetTelemetry(
                {"engine": eng.telemetry}, frontend=ctrl.metrics)
                .snapshot())
            gp = pred_runs[-1]["report"]["goodput_under_slo"]
            gd = depth_runs[-1]["report"]["goodput_under_slo"]
            # depth goodput 0: predictive serving ANYTHING on time wins
            # outright (2.0); BOTH zero is a degenerate round that must
            # FAIL the gate (0.0), never alias to parity
            ratios.append(gp / gd if gd else (2.0 if gp > 0 else 0.0))
        best = max(range(rounds), key=lambda r: ratios[r])
        pr, dr = pred_runs[best], depth_runs[best]
        fleet_block = fleet_snaps[best]
        ttfts = [r["ttft_s"] for r in pr["records"]
                 if r["ttft_s"] is not None]
        scenarios[name] = {
            "n_requests": n_ab,
            "offered_load_factor": overload,
            **_ttft_report(ttfts, slo_ttft),
            "slo_report": pr["report"],
            "admission": pr["admission"],
            "admission_depth_baseline": dr["admission"],
            "ab": {
                "rounds": rounds,
                "goodput_pred": pr["report"]["goodput_under_slo"],
                "goodput_depth": dr["report"]["goodput_under_slo"],
                "goodput_pred_all": [p["report"]["goodput_under_slo"]
                                     for p in pred_runs],
                "goodput_depth_all": [d["report"]["goodput_under_slo"]
                                      for d in depth_runs],
                "pair_ratios": [round(x, 4) for x in ratios],
                "best_paired_ratio": round(ratios[best], 4),
            },
            "tokens_per_sec": round(
                sum(r["tokens"] for r in pr["records"])
                / pr["window_s"], 1) if pr["window_s"] else None,
        }
    return {
        "outputs_bit_exact": True,        # asserted above
        "leaked_pages": 0,                # asserted above
        # ISSUE 13: exact per-request latency decomposition (asserted
        # above), the tail-outlier capture summary, and the aggregated
        # health-sentinel view from the A/B engine (queue/burn detectors
        # observed the overloaded rounds; counts are reported, not gated
        # — calm/pressure determinism is pinned in tests/test_health.py)
        "attribution": attribution,
        "tail": tail_report,
        "alerts": aggregate_alerts({"engine": eng.telemetry.sentinel}),
        # fleet-wide aggregation (ISSUE 12; schema-gated): engine
        # telemetry + predictive-controller registries merged, captured
        # in-round from the LAST scenario's best paired round — both
        # sides of the snapshot describe one measurement window
        "fleet": fleet_block,
        "host_cpu_count": os.cpu_count(),
        "async_harness": {
            "n_requests": n_async,
            "abandoned_mid_decode": abandoned,
            "arrival": "bursty",
            "note": "greedy streams bit-equal direct submit; abandons are "
                    "prefixes and freed every page",
        },
        "calibration": {
            "ttft_unloaded_ms": round(ttft_unloaded * 1e3, 2),
            "step_host_s_p50": round(step_s, 6),
            "prefill_rate_tps_measured": round(prefill_rate, 1),
            "slo_ttft_ms": round(slo_ttft * 1e3, 2),
            "load_tokens_per_scenario_s": round(load_tps, 3),
            "depth_cap": depth_cap,
            "arrival_pacing": "token-time (machine-independent offered "
                              "load; same trick as the serving trace)",
        },
        "scenarios": scenarios,
        "engine_stats": eng.stats(),
    }


def main():
    import sys
    import jax
    if not any(d.platform == "tpu" for d in jax.devices()):
        # the measurement path never falls back to the CPU: a number from
        # a CPU run must not appear under a device metric's name
        sys.exit(f"bench.py: no TPU among jax.devices() "
                 f"({jax.devices()[0].platform}) — main() measures on the "
                 f"chip only (serving traces: --trace ...)")
    _setup_compile_cache()
    t_start = time.perf_counter()
    res = bench_llama()
    extras = {}
    secondary = (("vit_l16_images_per_sec", bench_vit_l16, 250),
                 ("resnet50_images_per_sec", bench_resnet50, 250),
                 ("llama_271M_seq8192_tokens_per_sec",
                  bench_llama_long_context, 250),
                 ("ernie_base_mlm", bench_ernie_mlm, 250),
                 ("sd15_unet_images_per_sec", bench_sd_unet, 450),
                 ("llama_271M_decode", bench_llama_decode, 250),
                 ("serving", bench_serving, 250),
                 ("serving_shared_prefix", bench_serving_shared_prefix, 250),
                 ("serving_spec_decode", bench_serving_spec_decode, 250),
                 ("serving_frontend", bench_serving_frontend, 250),
                 ("serving_failover", bench_serving_failover, 250),
                 ("serving_elastic", bench_serving_elastic, 250),
                 ("serving_quant", bench_serving_quant, 450))
    if len(jax.devices()) >= 4:
        # the disagg A/B needs 2 disjoint mp=2 submeshes; standalone runs
        # get forced-host devices via --trace disagg, but main() takes
        # whatever the host exposes
        secondary += (("serving_disagg", bench_serving_disagg, 450),)
    import signal

    def _alarm(_sig, _frm):
        raise TimeoutError("secondary bench exceeded its time slice")

    # priming mode (perf/prime_cache.py): no budget gate, no alarms — the
    # whole point is to let every cold compile finish into the cache
    no_caps = os.environ.get("BENCH_NO_CAPS") == "1"
    for name, fn, cap in secondary:
        if not no_caps and time.perf_counter() - t_start > 1000:
            extras[name] = "skipped: bench time budget"
            continue
        try:
            jax.clear_caches()  # release the previous bench's HBM
            prev = signal.signal(signal.SIGALRM, _alarm)
            signal.alarm(0 if no_caps else cap)
            try:                # hard cap per extra (a cold compile can
                extras[name] = fn()   # exceed any soft budget)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, prev)
        except Exception as e:  # noqa: BLE001 — one failed extra must not
            # hide the others' numbers; it is recorded, and fails the run
            # through the exit code below
            extras[name] = f"error: {type(e).__name__}: {e}"[:200]

    out = {
        "metric": f"llama_{res['n_params'] // 1_000_000}M_train_tokens_per_sec_per_chip",
        "value": res["tokens_per_sec"],
        "unit": "tokens/s/chip",
        "vs_baseline": (round(res["tokens_per_sec"] / R2_BASELINE_TPS, 4)
                        if res["on_tpu"] else None),
        "baseline_note": "ratio vs 36285.8 tok/s, a number from a deleted "
                         "record of an earlier installation (not measured "
                         "on this code)",
        "mfu": res["mfu"],
        "model_flops_per_token_gflops": res["model_flops_per_token"],
        "chip_peak_tflops_bf16": res["chip_peak_tflops_bf16"],
        "device_kind": res["device_kind"],
        "loss": res["loss"],
    }
    out.update(extras)
    print(json.dumps(out))
    failed = [k for k, v in extras.items()
              if isinstance(v, str) and v.startswith("error:")]
    if failed:
        sys.exit(f"bench.py: phases failed: {', '.join(failed)}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace",
                    choices=["shared-prefix", "serving", "spec-decode",
                             "failover", "frontend", "elastic", "quant",
                             "disagg"],
                    default=None,
                    help="run ONE serving trace and print its JSON line "
                         "(shared-prefix: prefix-cache hit-rate / "
                         "prefill-tokens-saved / TTFT; serving: the mixed-"
                         "length continuous-batching trace; spec-decode: "
                         "self-speculative decoding vs speculation off; "
                         "failover: replica fleet with an injected "
                         "mid-trace crash — zero lost requests + bit-equal "
                         "outputs asserted, recovery time reported; "
                         "frontend: AsyncFrontend transport exactness + "
                         "the predictive-vs-depth admission A/B on bursty "
                         "and diurnal traffic, goodput-under-SLO reported; "
                         "elastic: sentinel-driven autoscaling + prefix-"
                         "affinity routing on a diurnal shared-prefix "
                         "trace — zero-loss drains, bit-equal outputs, "
                         "goodput-per-replica-hour vs fixed-N fleets; "
                         "quant: the int8-KV + int8-weight serving plane "
                         "— greedy exact-match parity vs f32, concurrent "
                         "users at fixed pool bytes, dequant-tax tokens/s "
                         "A/B, and the failover/elastic drills re-run "
                         "with quantized pages; "
                         "disagg: disaggregated prefill/decode on "
                         "disjoint mp=2 submeshes at a fixed 4 chips — "
                         "prefill-heavy virtual-clock trace, rank-local "
                         "KV page handoff, TTFT p95 win vs the "
                         "colocated-TP fleet, bit-exactness asserted)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump the metrics dict to PATH as a JSON "
                         "artifact (BENCH_r0x-style)")
    ap.add_argument("--seed", type=int, default=None,
                    help="seed for trace generation (default: each trace's "
                         "own fixed seed, so unseeded runs reproduce the "
                         "published numbers)")
    ap.add_argument("--perfetto", metavar="PATH", default=None,
                    help="failover trace only: also write the stitched "
                         "cross-component Perfetto trace (frontend/router/"
                         "replica tracks + per-request flow events) to "
                         "PATH — load it at https://ui.perfetto.dev")
    ap.add_argument("--proc", action="store_true",
                    help="failover trace only: run the CROSS-PROCESS "
                         "drill (real worker processes, real SIGKILL "
                         "mid-decode, zero-loss recovery over the RPC "
                         "wire — ISSUE 17)")
    ap.add_argument("--tp", type=int, default=None, metavar="N",
                    help="serving trace only: add the tensor-parallel arm "
                         "— shard one engine over an mp mesh of N devices "
                         "(CPU hosts get N forced-host virtual devices) "
                         "and report the `tp` block: greedy bit-exactness "
                         "vs the single-chip engine, the per-rank "
                         "collective profile (dist.collective_s / "
                         "max_rank_skew_s), decode_sync_frac attribution, "
                         "and the quantized-AllReduce parity gate")
    args = ap.parse_args()
    if args.trace is None and (args.json or args.seed is not None):
        ap.error("--json/--seed only apply to a serving trace; "
                 "pass --trace "
                 "{shared-prefix,serving,spec-decode,failover,frontend}")
    if args.perfetto is not None and args.trace != "failover":
        ap.error("--perfetto applies to --trace failover only")
    if args.proc and args.trace != "failover":
        ap.error("--proc applies to --trace failover only")
    if args.proc and args.perfetto is not None:
        ap.error("--perfetto is not wired for the --proc drill")
    if args.tp is not None:
        if args.trace != "serving":
            ap.error("--tp applies to --trace serving only")
        if args.tp < 2:
            ap.error("--tp wants N >= 2 (N=1 is the single-chip engine)")
    n_forced = args.tp if args.tp is not None \
        else (4 if args.trace == "disagg" else None)
    if n_forced is not None:
        # BEFORE any jax import: a CPU host needs N virtual devices for
        # the mp mesh(es) (inert on a real multi-chip host — the flag
        # only affects the host platform).  The disagg trace wants 4: two
        # disjoint mp=2 submeshes.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={n_forced}"
            ).strip()
    if args.trace is not None:
        _setup_compile_cache()
        fn = {"shared-prefix": bench_serving_shared_prefix,
              "serving": bench_serving,
              "spec-decode": bench_serving_spec_decode,
              "failover": bench_serving_failover,
              "frontend": bench_serving_frontend,
              "elastic": bench_serving_elastic,
              "quant": bench_serving_quant,
              "disagg": bench_serving_disagg}[args.trace]
        if args.proc:
            fn = bench_serving_failover_proc
        kw = {}
        if args.seed is not None:
            kw["seed"] = args.seed
        if args.perfetto is not None:
            kw["perfetto"] = args.perfetto
        if args.tp is not None:
            kw["tp"] = args.tp
        res = fn(**kw)
        metric = f"trace_{args.trace.replace('-', '_')}"
        if args.proc:
            metric += "_proc"
        out = {"metric": metric, **res}
        print(json.dumps(out))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(out, f, indent=2)
    else:
        main()
