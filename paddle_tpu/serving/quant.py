"""Quantized serving plane (ROADMAP item 2, single-chip half): int8/fp8 KV
pages, quantized serving weights, and the bit-drift parity harness.

PagePool capacity is the admission bottleneck of the whole serving stack —
the entire degradation ladder (admit -> queue -> reject -> evict-cache ->
preempt) exists because pages run out, so halving page bytes is a direct
~2x on concurrent users per chip.  This module is the numeric core the
quantized page store shares across every layer that touches it:

  * :func:`quantize_kv` / :func:`dequantize_kv` — the ONE symmetric-absmax
    KV codec (int8 grid, or fp8 e4m3 storage where the jax build has the
    dtype).  Scales are per (page, kv head, token slot): one f32 absmax
    per head per token row of a page.  That granularity is deliberate —
    it makes quantization WRITE-ORDER INDEPENDENT (a token row quantizes
    the same whether it arrived via dense prefill, a chunk, a decode step,
    a speculative verify scatter, or a preemption re-prefill), which is
    what lets the quantized engine keep every bit-exactness invariant the
    f32 engine holds against ITSELF: cache on/off, chunked prefill,
    preemption + re-prefill, COW, snapshot/restore, overlap, and the
    whole fleet failover matrix.  A coarser per-page scalar would need
    requantization as the running absmax grows, and requantization error
    depends on write order — every one of those invariants would die.
  * :func:`kv_spec` — kv_dtype name -> (storage dtype, qmax); the
    per-dtype registry `models/llama.build_llama_paged_decode` and the
    Pallas kernel agree on.
  * :func:`page_bytes` — bytes per KV page (both K and V, all layers,
    scales included) for a geometry/dtype: the telemetry
    `mem.pool_*_bytes` gauges and the fixed-pool-bytes capacity test
    both size pools through this one function.
  * :func:`quantize_params` — per-channel int8 weight quantization for
    serving params (through `quantization.quantize_weight(axis=...)`):
    matmul weights snap to the int8 grid per output channel and are
    stored DEQUANTIZED in the compute dtype (this backend has no native
    int8 matmul — the grid snap is the accuracy-honest part; native int8
    GEMM is the TPU follow-up).  Norm weights stay f32 (standard
    practice: they are tiny and scale-sensitive).
  * :func:`parity_report` — the subsystem's CONTRACT: greedy exact-match
    rate and max teacher-forced logit drift of a quantized engine vs the
    f32 engine on the standard parity scenarios.  Exact match (not
    bit-exactness) is the quantized gate by design: quantization is a
    lossy code, so the question is whether greedy DECISIONS survive it
    (PERF.md §22 has the methodology).

EQuARX-style quantized AllReduce (arxiv 2506.17615) reuses exactly this
per-page scale machinery once TP decode (ROADMAP item 1) lands.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["KV_DTYPES", "kv_spec", "quantize_kv", "dequantize_kv",
           "page_bytes", "quantize_params", "parity_scenarios",
           "parity_report", "logit_drift"]


def _jnp():
    import jax.numpy as jnp
    return jnp


# kv_dtype name -> (storage dtype name, qmax).  qmax is the grid half-range
# the absmax maps onto: 127 for the symmetric int8 grid (the -128 code is
# never emitted, keeping the code symmetric), 448 = the e4m3 max finite —
# scaling absmax onto it uses the whole fp8 dynamic range without ever
# rounding into inf/nan.
KV_DTYPES = {"int8": ("int8", 127.0), "fp8": ("float8_e4m3fn", 448.0)}


def kv_spec(kv_dtype):
    """``kv_dtype`` name -> (storage jnp dtype, qmax).  Raises a clear
    ValueError for unknown names and for ``fp8`` on a jax build without
    the ``float8_e4m3fn`` storage dtype (gate, don't crash mid-trace)."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r} (expected one of "
            f"{sorted(KV_DTYPES)}, or None for the f32/bf16 page store)")
    jnp = _jnp()
    name, qmax = KV_DTYPES[kv_dtype]
    dt = getattr(jnp, name, None)
    if dt is None:
        raise ValueError(
            f"kv_dtype {kv_dtype!r} needs the jnp.{name} storage dtype, "
            f"which this jax build lacks — use kv_dtype='int8'")
    return jnp.dtype(dt), qmax


def quantize_kv(x, *, qmax, dtype):
    """Symmetric absmax quantization of K/V rows: ``x [..., D]`` (any float
    dtype) -> ``(q [..., D] storage-dtype, scale [...] f32)`` with one
    scale per leading-index row (per token, per head).  ``qmax``/``dtype``
    are keyword-only STATICS (from :func:`kv_spec`) so the branch below is
    never traced.  Zero rows round-trip to exact zeros."""
    jnp = _jnp()
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    y = xf / scale[..., None]
    if jnp.issubdtype(dtype, jnp.integer):
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(dtype)
    else:
        # fp8 storage: the cast IS the rounding (|y| <= qmax = the e4m3
        # max finite by construction, so the cast never overflows)
        q = y.astype(dtype)
    return q, scale


def dequantize_kv(q, scale):
    """``(q [..., D], scale [...])`` -> f32 values.  The ONE dequant
    expression — every consumer (the Pallas kernel, its jnp ref, the
    chunk/verify gathers, the dense-prefill local fake-quant) routes
    through the same two ops, so identical stored rows dequantize to
    identical f32 values on every attention path."""
    jnp = _jnp()
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def page_bytes(config, page_size: int, kv_dtype=None, dtype=None) -> int:
    """Bytes ONE page of KV cache costs (K + V across all layers, per-page
    scales included for quantized dtypes).  This is the unit the telemetry
    memory observatory reports pool occupancy in and the unit the
    fixed-pool-bytes capacity test holds constant across arms."""
    jnp = _jnp()
    L = config.num_hidden_layers
    hkv = config.num_key_value_heads
    d = config.hidden_size // config.num_attention_heads
    if kv_dtype is None:
        item = jnp.dtype(dtype if dtype is not None else jnp.float32).itemsize
        return 2 * L * hkv * page_size * d * item
    storage, _ = kv_spec(kv_dtype)
    data = 2 * L * hkv * page_size * d * storage.itemsize
    scales = 2 * L * hkv * page_size * 4          # one f32 per head per row
    return data + scales


# ---------------------------------------------------------------------------
# Serving weight quantization (per-channel, through quantization/)
# ---------------------------------------------------------------------------
def _quant_leaf(w, bits, reduce_axis):
    from ..quantization import dequantize_weight, quantize_weight
    q, scale = quantize_weight(w, bits=bits, axis=reduce_axis)
    return dequantize_weight(q, scale, dtype=w.dtype)


def quantize_params(params, bits: int = 8):
    """Snap the (embed, block, head) serving pytrees onto the per-channel
    int grid: matmul weights quantize with one absmax scale per OUTPUT
    channel (reduction over the contraction axis — the granularity the
    attention projections need; a per-tensor scale lets one hot channel
    flatten every other head's resolution), embeddings per ROW.  1-D norm
    gains (`ln1`/`ln2`/`ln_f`) pass through untouched.  Values come back
    DEQUANTIZED in the input dtype: this backend's matmul consumes
    f32/bf16, so the grid snap is what changes numerics — exactly what
    the parity harness must see."""
    ep, bp, hp = params
    ep = dict(ep, tok=_quant_leaf(ep["tok"], bits, -1))
    bp = {k: (v if k.startswith("ln") else _quant_leaf(v, bits, -2))
          for k, v in bp.items()}
    hp = dict(hp, lm=_quant_leaf(hp["lm"], bits, -2))
    return ep, bp, hp


# ---------------------------------------------------------------------------
# Parity harness — the subsystem's contract
# ---------------------------------------------------------------------------
def parity_scenarios(vocab: int, seed: int = 0, page_size: int = 8):
    """The standard parity scenario set: seeded prompts covering the same
    shapes every serving exactness suite sweeps — short, page-boundary
    (len % page_size == 0 and == page_size - 1), long/multi-page, and a
    shared-prefix pair (the prefix-cache hit path).  Returns a list of
    ``(prompt ndarray, max_new_tokens)``."""
    rng = np.random.default_rng(seed)
    lens = [3, page_size, page_size - 1, 2 * page_size,
            3 * page_size + 2, 2 * page_size + 1]
    out = []
    for t in lens:
        out.append((rng.integers(1, vocab, (int(t),)).astype(np.int32), 16))
    shared = rng.integers(1, vocab, (2 * page_size,)).astype(np.int32)
    for t in (3, page_size - 2):
        tail = rng.integers(1, vocab, (int(t),)).astype(np.int32)
        out.append((np.concatenate([shared, tail]), 16))
    return out


def _run_engine(factory, scenarios):
    outs = []
    eng = factory()
    rids = [eng.submit(p, max_new_tokens=m) for p, m in scenarios]
    done = eng.run()
    for r in rids:
        outs.append([int(t) for t in done[r].generated])
    return outs, eng


def logit_drift(params_ref, params_q, config, prompts, *, kv_dtype,
                page_size: int = 8, steps: int = 8, dtype=None,
                ref_build_kw=None, q_build_kw=None):
    """Max |logits_q - logits_ref| over a TEACHER-FORCED greedy decode:
    both page stores replay the REFERENCE engine's token trajectory, so
    the drift number measures the quantization error of each step's
    logits in isolation (a free-running comparison would conflate one
    early argmax flip with everything after it).  Returns (max_drift,
    per-step max drifts).

    ``ref_build_kw`` / ``q_build_kw``: extra build_llama_paged_decode
    kwargs per arm — how tests/test_tp_serving.py drifts the quantized
    AllReduce against the f32-collective build (both arms
    ``mesh=<mesh>``, the q arm additionally ``quantized_allreduce=True``,
    with ``kv_dtype=None`` so page quantization stays out of the
    measurement)."""
    import jax.numpy as jnp
    from ..models.llama import build_llama_paged_decode

    per = max(math.ceil((len(p) + steps) / page_size) for p in prompts)
    n_pages = per + 1
    drifts = []
    builds = {}
    for tag, prm, kvd, bkw in (("ref", params_ref, None, ref_build_kw),
                               ("q", params_q, kv_dtype, q_build_kw)):
        builds[tag] = build_llama_paged_decode(
            config, page_size=page_size, num_pages=n_pages, dtype=dtype,
            attention_impl="ref", kv_dtype=kvd, **(bkw or {}))
    for prompt in prompts:
        T = len(prompt)
        ids = jnp.asarray(np.asarray(prompt, np.int32)[None])
        row = np.arange(per, dtype=np.int32)
        state = {}
        for tag in ("ref", "q"):
            fam = builds[tag]
            prm = params_ref if tag == "ref" else params_q
            state[tag] = fam.prefill(prm, ids, jnp.asarray(T, jnp.int32),
                                     jnp.asarray(row), 0, fam.init_cache())
        step_drift = [float(jnp.max(jnp.abs(state["q"][0]
                                            - state["ref"][0])))]
        # teacher forcing: the reference argmax feeds BOTH stores
        tok = int(np.asarray(jnp.argmax(state["ref"][0])))
        for i in range(steps - 1):
            pos = T + i
            for tag in ("ref", "q"):
                prm = params_ref if tag == "ref" else params_q
                state[tag] = builds[tag].decode_step(
                    prm, jnp.asarray([tok], jnp.int32),
                    jnp.asarray([pos], jnp.int32),
                    jnp.asarray(row[None]), state[tag][1],
                    jnp.asarray([True]))
            step_drift.append(float(jnp.max(jnp.abs(
                state["q"][0] - state["ref"][0]))))
            tok = int(np.asarray(jnp.argmax(state["ref"][0][0])))
        drifts.append(step_drift)
    flat = [d for row_ in drifts for d in row_]
    return max(flat), drifts


def parity_report(params, config, *, kv_dtype="int8", quantize=8,
                  scenarios=None, engine_kw=None, drift_steps=8,
                  drift_prompts=2, ref_engine_kw=None, q_engine_kw=None,
                  ref_build_kw=None, q_build_kw=None):
    """Greedy exact-match rate + max logit drift of the quantized serving
    plane vs the f32 engine on the standard parity scenarios.

    Builds two engines from the SAME params/config — the f32 reference
    and one with ``kv_dtype`` pages (+ per-channel ``quantize``-bit
    weights when ``quantize`` is set) — runs every scenario greedily on
    both, and reports:

      * ``exact_match`` — fraction of requests whose FULL greedy output
        matches the f32 engine token-for-token (the gated number);
      * ``token_match`` — mean matched-prefix fraction over tokens (the
        diagnostic: how deep into a sequence the first divergence sits);
      * ``max_logit_drift`` — max |Δlogits| over a teacher-forced decode
        of the first ``drift_prompts`` scenarios (the raw numeric error
        the argmax survived).

    ``ref_engine_kw`` / ``q_engine_kw`` merge per-arm ON TOP of
    ``engine_kw`` — this is how tests/test_tp_serving.py reuses the harness
    for quantized-vs-f32 COLLECTIVES instead of quantized-vs-f32 pages:
    both arms ``mesh=<mesh>``, the q arm ``quantized_allreduce=True``,
    with ``kv_dtype=None, quantize=None`` so the only difference under
    measurement is the per-layer AllReduce grid.  ``ref_build_kw`` /
    ``q_build_kw`` forward to :func:`logit_drift` the same way.

    Deterministic for a given params/config/scenario seed."""
    from ..inference.paged import ServingEngine

    kw = dict(num_slots=4, page_size=8, attention_impl="ref",
              prompt_bucket=8, decode_horizon=4)
    kw.update(engine_kw or {})
    if scenarios is None:
        # scenario lengths are built AROUND the engine's page size (the
        # page-boundary cases are the point of the set)
        scenarios = parity_scenarios(config.vocab_size,
                                     page_size=kw["page_size"])
    need = max(math.ceil((len(p) + m) / kw["page_size"]) + 1
               for p, m in scenarios)
    kw.setdefault("max_pages_per_seq", need)
    kw.setdefault("num_pages", need * (len(scenarios) + kw["num_slots"]))

    params_q = quantize_params(params, bits=int(quantize)) if quantize \
        else params

    ref_kw = dict(kw, **(ref_engine_kw or {}))
    q_kw = dict(kw, **(q_engine_kw or {}))
    ref_outs, ref_eng = _run_engine(
        lambda: ServingEngine(params, config, **ref_kw), scenarios)
    q_outs, q_eng = _run_engine(
        lambda: ServingEngine(params_q, config, kv_dtype=kv_dtype, **q_kw),
        scenarios)

    matches = [a == b for a, b in zip(ref_outs, q_outs)]
    tok_fracs = []
    for a, b in zip(ref_outs, q_outs):
        n = max(len(a), 1)
        m = 0
        while m < min(len(a), len(b)) and a[m] == b[m]:
            m += 1
        tok_fracs.append(m / n)
    if drift_prompts > 0:
        max_drift, _ = logit_drift(
            params, params_q, config,
            [p for p, _m in scenarios[:drift_prompts]], kv_dtype=kv_dtype,
            page_size=kw["page_size"], steps=drift_steps,
            ref_build_kw=ref_build_kw, q_build_kw=q_build_kw)
    else:
        max_drift = 0.0        # drift pass skipped (cheap smoke mode)
    ref_eng.check_invariants()
    q_eng.check_invariants()
    return {
        "kv_dtype": kv_dtype,
        "weight_bits": int(quantize) if quantize else None,
        "scenarios": len(scenarios),
        "exact_match": round(sum(matches) / len(matches), 4),
        "token_match": round(float(np.mean(tok_fracs)), 4),
        "max_logit_drift": round(max_drift, 6),
        "mismatched": [i for i, ok in enumerate(matches) if not ok],
    }
