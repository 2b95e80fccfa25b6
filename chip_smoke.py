#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two things this repo ships, through the entry points a user calls,
at the widths of ``llama_config_7b()`` (H=4096, 32 heads, D=128, I=11008,
V=32000, bf16), cut by DEPTH only, with random weights made from ``--seed``:

  default (one chip, one process)
    device  the device JAX reports, the compile-cache directory, and one
            complex64 array fetched to the host
    kernel  ``ragged_paged_attention`` against ``ragged_paged_attention_ref``
            at the decode / prefill-chunk / speculative-verify shapes
    serve   ``ServingEngine`` with its TPU defaults (attention_impl="auto",
            no interpret) over mixed-length prompts — dense prefill, chunked
            prefill and the decode horizon all run — then the same prompts
            through an attention_impl="ref" engine on the same weights
    train   the donated jitted train step (``build_functional_llama`` +
            ``AdamW.apply_gradients_functional``) with the Pallas
            flash-attention and rms_norm kernels in the executable, S=2048

  --multichip (four chips; runs ONLY this)
    the TP=4 engine (``ServingEngine(mesh=build_mesh({"mp": 4}))``) and the
    one-chip engine it is compared with, same weights and prompts — in bf16
    (what is deployed) and in float32 (where the repo's TP contract, greedy
    tokens equal with f32 collectives, must hold to the token)

One JSON object per phase on stdout; the LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
No timing here is a benchmark: seconds are printed so a reader can see where
a run spent its 1200 s, nothing else.

There is no CPU branch and no ``except``: run without a TPU it exits non-zero
before any phase, and a failing phase ends the run with its traceback.  The
phases are plain functions of (config, sizes, attention impl) so that
tests/test_chip_smoke.py can rehearse them at a tiny size on the CPU mesh
with the kernels in interpret mode — the steering lives in that test.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

# Sizes per device kind (jax's ``device_kind``); a kind not listed is an
# error, never a default.  Widths are llama_config_7b()'s; ``layers`` is the
# depth cut, chosen with perf/chip_fit.py from ``memory_analysis()`` of the
# executables compiled for the described chip (numbers in CHANGES.md PR 22):
# the largest depth that leaves the page pool (serve) or the Adam state and
# activations (train) inside the chip's HBM with margin.
SIZES = {
    "TPU v5 lite": {
        "serve": dict(layers=12, num_slots=8, page_size=64,
                      max_pages_per_seq=32, prefill_chunk=128,
                      prompt_bucket=32, decode_horizon=8,
                      prompt_lens=(40, 100, 300, 77, 520, 128, 200, 33),
                      max_new_tokens=24, compare_tokens=4),
        "train": dict(layers=4, batch=2, seq=2048, steps=4),
        # two arms, each the TP engine and the one-chip engine it is
        # compared with, at a depth ONE chip holds.  bf16 at the default
        # matmul precision is what gets deployed: there the two programs'
        # logits differ at the 1e-2 level on the chip (XLA rounds to bf16 at
        # different points of differently-partitioned programs — measured,
        # PERF.md PR 22), greedy tokens part at near-ties, and only gross
        # disagreement fails.  The repo's TP contract (tests/
        # test_tp_serving.py: greedy tokens EQUAL with f32 collectives) was
        # stated on a CPU mesh in float32; on the chip it binds in float32
        # at matmul precision "highest" (logits then agree to ~4e-6), and
        # in that arm every token must match.
        "multichip": dict(tp=4, num_slots=8, page_size=64,
                          max_pages_per_seq=32, prefill_chunk=128,
                          prompt_bucket=32, decode_horizon=8,
                          prompt_lens=(40, 300, 77, 200), max_new_tokens=16,
                          arms=(dict(dtype="bfloat16", layers=8,
                                     min_agreement=0.25),
                                dict(dtype="float32", layers=4,
                                     matmul_precision="highest",
                                     min_agreement=1.0))),
        # --hybrid: the recurrent family (Mamba-2 + attention + LatentMoE)
        # at the widths and the cut of the benchmark configuration named
        # here, a few slots: dense prefill, chunked prefill with the state
        # carried, and the decode horizon all run
        "hybrid": dict(config="nemotron-3-super-serve-1of4", num_slots=8,
                       page_size=64, max_pages_per_seq=16, prefill_chunk=256,
                       prompt_bucket=128, decode_horizon=8,
                       prompt_lens=(100, 300, 520, 77, 640),
                       max_new_tokens=16, compare_tokens=4,
                       # the cell's decode batch and prefill chunk: the row
                       # bounds its grouped products run at
                       product_tokens=(64, 1024)),
        # --latent: the latent-attention family (MLA + SwiGLU experts) at the
        # widths of the benchmark configuration named here, three layers
        # (the dense one and two expert layers), a few slots
        "latent": dict(config="kimi-vl-a3b-serve-1of4", layers=3,
                       num_slots=8, page_size=64, max_pages_per_seq=32,
                       prefill_chunk=256, prompt_bucket=128,
                       decode_horizon=8,
                       prompt_lens=(100, 300, 520, 77, 1200),
                       max_new_tokens=16, compare_tokens=4,
                       product_tokens=(64, 512)),
        # --sambay: the SambaY family (Mamba-1 + window / full differential
        # attention + GMU over ONE K/V store) at the widths of the benchmark
        # configuration named here, 8 layers (every kind of layer), a few
        # slots; prompts longer than the window and than two chunks
        "sambay": dict(config="phi-4-mini-flash-serve-1chip", layers=8,
                       num_slots=4, page_size=64, max_pages_per_seq=32,
                       prefill_chunk=640, prompt_bucket=128,
                       decode_horizon=8, prompt_lens=(100, 600, 1300, 460),
                       max_new_tokens=72, compare_tokens=4),
    },
}
VERIFY_Q = 5            # speculative verify segment: K+1 at the engine's K=4
KERNEL_TOL = 2e-2       # |kernel - ref| on f32 outputs of bf16 q/k/v (below)
PRODUCT_TOL = 2e-4      # |grouped_matmul - ragged_dot|, f32 sums of bf16 products
KERNEL_VS_REF_FLOOR = 0.75      # serve: kernel engine vs ref engine, bf16


def check(cond, msg):
    """A phase's pass/fail test (not ``assert``: survives ``python -O``)."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def emit(phase, **facts):
    """One JSON line per phase — printed BEFORE any check on those facts
    that can fail, so a failing run still says what it saw."""
    print(json.dumps({"phase": phase, **facts}), flush=True)


def cut_config(layers):
    from paddle_tpu.models.llama import llama_config_7b
    return dataclasses.replace(llama_config_7b(), num_hidden_layers=layers)


def build_params(cfg, seed, dtype="bfloat16"):
    import jax
    from paddle_tpu.models.llama import build_functional_llama
    return build_functional_llama(cfg, key=jax.random.PRNGKey(seed),
                                  dtype=dtype)[:3]


def count_params(tree):
    import jax
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(tree))


def peak_bytes(devices):
    """Per-device ``peak_bytes_in_use`` (None where the backend keeps no
    stats, as the CPU backend of the rehearsal does not)."""
    out = []
    for d in devices:
        st = d.memory_stats()
        out.append(None if st is None else int(st["peak_bytes_in_use"]))
    return out


def executable_bytes(compiled):
    """What ``memory_analysis()`` says one compiled program needs."""
    mem = compiled.memory_analysis()
    return {"arguments": int(mem.argument_size_in_bytes),
            "temporaries": int(mem.temp_size_in_bytes),
            "aliased": int(mem.alias_size_in_bytes)}


def make_prompts(cfg, lens, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (int(t),)).astype(np.int32)
            for t in lens]


# ---------------------------------------------------------------------------
# kernel: ragged_paged_attention vs its jnp reference at the serving shapes
# ---------------------------------------------------------------------------
def kernel_parity_phase(cfg, sizes, *, interpret=False, seed=0):
    """decode (q_len 1 per slot), one prefill chunk, speculative verify —
    the three segment shapes every serving dispatch is a case of — at the
    config's head geometry, bf16 q and pages, f32 outputs.  The kernel and
    the reference both accumulate in f32 from the same bf16 inputs; what
    differs is the order of the online softmax and the precision the
    backend gives an f32 matmul, so the bound is a bf16-scale one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)

    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // nh
    ps, width = sizes["page_size"], sizes["max_pages_per_seq"]
    slots, chunk = sizes["num_slots"], sizes["prefill_chunk"]
    n_pages = slots * width
    rng = np.random.default_rng(seed)
    kp, vp = (jnp.asarray(rng.normal(0, 1, (nkv, n_pages, ps, d)),
                          jnp.bfloat16) for _ in range(2))
    rows = {}
    for name, s, qmax in (("decode", slots, 1), ("chunk", 1, chunk),
                          ("verify", slots, VERIFY_Q)):
        q = jnp.asarray(rng.normal(0, 1, (s, qmax, nh, d)), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(n_pages)[:s * width]
                            .reshape(s, width), jnp.int32)
        q_len = rng.integers(1, qmax + 1, (s,))
        q_len[0] = qmax                      # one full segment ...
        if s > 1:
            q_len[-1] = 0                    # ... and one idle slot
        q_start = rng.integers(0, width * ps - qmax, (s,))
        q_start[0] = width * ps - qmax       # ... that reaches the last page
        args = (q, kp, vp, table, jnp.asarray(q_start, jnp.int32),
                jnp.asarray(q_len, jnp.int32),
                jnp.asarray(np.where(q_len > 0, q_start + q_len, 0),
                            jnp.int32))
        got = jax.jit(lambda *a: ragged_paged_attention(
            *a, interpret=interpret, out_dtype=jnp.float32))(*args)
        want = jax.jit(lambda *a: ragged_paged_attention_ref(
            *a, out_dtype=jnp.float32))(*args)
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == (s, qmax, nh, d), f"{name}: shape {got.shape}")
        check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
        pad = np.arange(qmax)[None, :] >= q_len[:, None]
        check((got[pad] == 0).all(), f"{name}: padded rows not exactly zero")
        err = float(np.abs(got - want).max())
        check(err <= KERNEL_TOL, f"{name}: |kernel - ref| = {err} > "
                                 f"{KERNEL_TOL}")
        rows[name] = {"q": list(q.shape), "max_abs_err": err,
                      "ref_abs_max": float(np.abs(want).max())}
    return {"compared": "ragged_paged_attention vs ragged_paged_attention_"
                        "ref, bf16 q/pages, f32 out",
            "heads": [nh, nkv], "head_dim": d, "page_size": ps,
            "table_width": width, "tolerance_abs": KERNEL_TOL, "cases": rows}


# ---------------------------------------------------------------------------
# serve: ServingEngine through submit()/run()
# ---------------------------------------------------------------------------
def run_engine(params, cfg, sizes, prompts, max_new_tokens, *,
               attention_impl="auto", interpret=False, mesh=None):
    """One engine over the prompts, in the params' dtype; returns
    (outputs, facts, engine)."""
    from paddle_tpu.inference.paged import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(
        params, cfg, num_slots=sizes["num_slots"],
        page_size=sizes["page_size"],
        max_pages_per_seq=sizes["max_pages_per_seq"],
        dtype=params[0]["tok"].dtype,
        attention_impl=attention_impl, interpret=interpret,
        prompt_bucket=sizes["prompt_bucket"],
        decode_horizon=sizes["decode_horizon"],
        prefill_chunk=sizes["prefill_chunk"], telemetry=True, mesh=mesh)
    rids = [eng.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    done = eng.run()
    outs = [[int(t) for t in done[r].generated] for r in rids]
    seconds = time.perf_counter() - t0
    for i, o in enumerate(outs):
        check(len(o) == max_new_tokens,
              f"request {i}: {len(o)} of {max_new_tokens} tokens")
        check(all(0 <= t < cfg.vocab_size for t in o),
              f"request {i}: token outside the vocabulary")
    eng.check_invariants()
    st = eng.stats()
    comp = eng.telemetry.compile_report()
    facts = {
        "attention_impl": attention_impl, "tp_degree": st["tp_degree"],
        "dtype": str(params[0]["tok"].dtype),
        "requests": len(prompts),
        "prompt_lens": [int(len(p)) for p in prompts],
        "tokens_generated": st["tokens_generated"],
        "prefill_tokens": st["prefill_tokens_executed"],
        "decode_dispatches": st["decode_steps"],
        "executables": st["jit_cache_misses"],
        "compile_seconds": comp["compile_s_total"],
        "seconds_with_compile": round(seconds, 3),
        "pool_pages": eng.pool.num_pages,
        "pool_tokens": eng.pool.num_pages * eng.page_size,
        "page_bytes_per_rank": int(eng.page_bytes),
    }
    return outs, facts, eng


def token_agreement(a, b):
    """Greedy outputs of two engines on the same prompts: where each pair
    first breaks (None = equal) and the share of tokens in the common
    prefixes.  A first break is where the comparison ends for that request:
    after it the two engines decode different contexts."""
    breaks, agree, total = [], 0, 0
    for x, y in zip(a, b):
        n = min(len(x), len(y))
        first = next((i for i in range(n) if x[i] != y[i]), None)
        breaks.append(first)
        agree += n if first is None else first
        total += n
    return {"tokens_equal": all(f is None for f in breaks),
            "first_break": breaks, "agreement": round(agree / total, 4)}


def serve_phase(cfg, sizes, *, attention_impl="auto", interpret=False,
                seed=0, report=emit):
    import jax

    params = build_params(cfg, seed)
    prompts = make_prompts(cfg, sizes["prompt_lens"], seed + 1)
    chunk = sizes["prefill_chunk"]
    check(any(len(p) > chunk for p in prompts)
          and any(len(p) <= chunk for p in prompts),
          "prompts must straddle the prefill chunk")
    outs, facts, eng = run_engine(params, cfg, sizes, prompts,
                                  sizes["max_new_tokens"],
                                  attention_impl=attention_impl,
                                  interpret=interpret)
    ran = facts["executables"]
    check(ran.get("prefill", 0) > 0 and ran.get("prefill_chunk", 0) > 0
          and ran.get("decode_step", 0) > 0,
          f"dense prefill, chunked prefill and decode must all run: {ran}")
    compiled = eng.decode_horizon_compiled()
    facts.update(
        depth=cfg.num_hidden_layers, parameters=count_params(params),
        decode_has_tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        decode_executable_bytes=executable_bytes(compiled),
        peak_bytes_in_use=peak_bytes(jax.devices()[:1])[0])
    report("serve", **facts)
    del eng, compiled
    gc.collect()
    # the same prompts through the jnp reference attention, same weights:
    # dense-prefill first tokens never touch the kernel, every later token
    # (and every token of a chunked prompt) does
    n_cmp = sizes["compare_tokens"]
    ref_outs, ref_facts, ref_eng = run_engine(params, cfg, sizes, prompts,
                                              n_cmp, attention_impl="ref")
    del ref_eng
    versus = {
        "compared": f"first {n_cmp} greedy tokens of every request, "
                    f"attention_impl={attention_impl!r} vs 'ref' engine",
        "tolerance": f"agreement >= {KERNEL_VS_REF_FLOOR} (a bf16 near-tie "
                     f"may flip a token; a break ends that request's "
                     f"comparison)",
        **token_agreement([o[:n_cmp] for o in outs], ref_outs),
        "ref_compile_seconds": ref_facts["compile_seconds"]}
    report("serve_vs_ref", **versus)
    check(versus["agreement"] >= KERNEL_VS_REF_FLOOR,
          f"kernel engine vs ref engine: {versus}")
    return {**facts, "vs_ref_engine": versus}


def hybrid_config(name):
    """(NemotronHConfig, the configuration file) of a benchmark
    configuration of the recurrent family, as its driver reads it."""
    from benchmark.drivers import serve_nemotron_h as drv
    from benchmark.run import load_json
    conf = load_json(os.path.dirname(os.path.abspath(__file__)),
                     "benchmark", "configs", name + ".json")
    return drv.model_config(conf), conf


def grouped_product_phase(cfg, tokens, *, shapes=None, interpret=False,
                          seed=0):
    """The experts' grouped product at a decode batch's and a prefill run's
    row bounds (``tokens`` names the two batch sizes), the Pallas kernel and
    ``jax.lax.ragged_dot`` called directly on ONE set of operands, a third
    of the held experts empty: the kernel has lowered and run on this
    device, whatever the engine below would choose.  ``shapes``: the
    [K, N] of an expert's matrices (None: a LatentMoE expert's two)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.incubate.distributed.models.moe.dropless import row_bounds
    from paddle_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, grouped_matmul_ref, tiles)

    held, k = cfg.held()[1], cfg.num_experts_per_tok
    rng = np.random.default_rng(seed)
    seen = {}
    for name, t in zip(("decode", "prefill"), tokens):
        bound = row_bounds(t, k, held, cfg.n_routed_experts)[0]
        rows = rng.multinomial(bound * 3 // 4, rng.dirichlet(np.ones(held)))
        rows[rng.choice(held, held // 3, replace=False)] = 0
        counted = int(rows.sum())
        for shape in shapes or (
                (cfg.moe_latent_size, cfg.moe_intermediate_size),
                (cfg.moe_intermediate_size, cfg.moe_latent_size)):
            tiling = tiles(bound, *shape, held)
            check(tiling is not None,
                  f"{name}: no kernel tiling for {bound} rows x {shape}")
            xs = jnp.asarray(rng.normal(0, 1, (bound, shape[0])),
                             jnp.bfloat16)
            w = jnp.asarray(rng.normal(0, shape[0] ** -0.5, (held, *shape)),
                            jnp.bfloat16)
            args = (xs, w, jnp.asarray(rows, jnp.int32))
            got = np.asarray(jax.jit(lambda *a: grouped_matmul(
                *a, role=name, interpret=interpret,
                out_dtype=jnp.float32))(*args))[:counted]
            want = np.asarray(jax.jit(lambda *a: grouped_matmul_ref(
                *a, out_dtype=jnp.float32))(*args))[:counted]
            err = float(np.abs(got - want).max())
            seen[f"{name}.{shape[0]}x{shape[1]}"] = dict(
                rows_bound=bound, rows_counted=counted,
                experts_touched=int((rows > 0).sum()),
                tiles=list(tiling), max_abs_err=err)
            check(np.isfinite(got).all() and err <= PRODUCT_TOL,
                  f"grouped product {name} {shape}: |kernel - ragged_dot| "
                  f"{err} over {PRODUCT_TOL}")
    return seen


def hybrid_phase(cfg, sizes, *, attention_impl="auto", interpret=False,
                 dtype="bfloat16", seed=0, report=emit):
    """A family with recurrent state through the same engine: its three
    executables run (dense prefill, a prefill chunk with the state carried,
    the decode horizon), no routed row is dropped, and the kernel engine's
    greedy tokens agree with the reference-attention engine's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.nemotron_h import build_functional_nemotron_h
    params = jax.block_until_ready(jax.jit(
        lambda k: build_functional_nemotron_h(cfg, k, jnp.dtype(dtype)))(
            jax.random.PRNGKey(seed)))
    prompts = make_prompts(cfg, sizes["prompt_lens"], seed + 1)
    chunk = sizes["prefill_chunk"]
    check(any(len(p) > chunk for p in prompts)
          and any(len(p) <= chunk for p in prompts),
          "prompts must straddle the prefill chunk")
    outs, facts, eng = run_engine(params, cfg, sizes, prompts,
                                  sizes["max_new_tokens"],
                                  attention_impl=attention_impl,
                                  interpret=interpret)
    ran, st = facts["executables"], eng.stats()
    check(ran.get("prefill", 0) > 0 and ran.get("prefill_chunk", 0) > 0
          and ran.get("decode_step", 0) > 0,
          f"dense prefill, chunked prefill and decode must all run: {ran}")
    check(eng.family.recurrent and eng.cache is None,
          "a recurrent family runs without a prefix cache")
    check(st["moe_rows_dropped"] == 0 and st["moe_pairs_held"] > 0,
          f"routed rows: {st['moe_pairs_held']} held, "
          f"{st['moe_rows_dropped']} dropped")
    check(st["ssm_slot_resets"] == len(prompts),
          f"{st['ssm_slot_resets']} slot resets for {len(prompts)} prompts")
    compiled = eng.decode_horizon_compiled()
    facts.update(
        family=eng.family.name, depth=cfg.num_hidden_layers,
        parameters=count_params(params),
        moe_pairs_held=st["moe_pairs_held"],
        ssm_state_bytes=st["ssm_state_bytes"],
        decode_has_tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        decode_executable_bytes=executable_bytes(compiled),
        peak_bytes_in_use=peak_bytes(jax.devices()[:1])[0])
    report("hybrid", **facts)
    del eng, compiled
    gc.collect()
    n_cmp = sizes["compare_tokens"]
    ref_outs, _, ref_eng = run_engine(params, cfg, sizes, prompts, n_cmp,
                                      attention_impl="ref")
    del ref_eng
    versus = {"compared": f"first {n_cmp} greedy tokens of every request, "
                          f"attention_impl={attention_impl!r} vs 'ref'",
              **token_agreement([o[:n_cmp] for o in outs], ref_outs)}
    report("hybrid_vs_ref", **versus)
    check(versus["agreement"] >= KERNEL_VS_REF_FLOOR,
          f"kernel engine vs ref engine: {versus}")
    return {**facts, "vs_ref_engine": versus}


def latent_config(name, layers):
    """(MlaMoeConfig cut to ``layers``, the configuration file) of a
    benchmark configuration of the latent-attention family."""
    from benchmark.drivers import serve_mla_moe as drv
    from benchmark.run import load_json
    conf = load_json(os.path.dirname(os.path.abspath(__file__)),
                     "benchmark", "configs", name + ".json")
    return drv.model_config(conf, num_hidden_layers=layers), conf


def latent_kernel_phase(cfg, sizes, *, interpret=False, seed=0):
    """The latent-page attention kernel against its plain form at the
    config's widths: decode (one query a slot x every head) and a run's
    segments (`models/mla_moe.SEGMENT` queries each), bf16 queries and rows,
    f32 outputs, ragged lengths, an idle slot, a segment that reaches the
    table's last page."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.mla_moe import SEGMENT
    from paddle_tpu.ops.pallas.paged_attention import (
        mla_paged_attention, mla_paged_attention_ref)

    nh, dl = cfg.num_attention_heads, cfg.kv_lora_rank
    width = -(-cfg.latent_row // 128) * 128
    ps, table_w, slots = sizes["page_size"], sizes["max_pages_per_seq"], \
        sizes["num_slots"]
    n_pages, layers = slots * table_w, 2
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(0, 1, (layers, 1, n_pages, ps, width)),
                       jnp.bfloat16)
    kw = dict(dv=dl, sm_scale=(cfg.qk_nope_head_dim
                               + cfg.qk_rope_head_dim) ** -0.5)
    rows = {}
    for name, s, qmax in (("decode", slots, 1), ("chunk", 4, SEGMENT)):
        # queries as the model makes them: unit scale over the row's REAL
        # columns, zeros in the store's padding
        q = rng.normal(0, 1, (s, qmax, nh, width)) * 0.3
        q[..., cfg.latent_row:] = 0
        q = jnp.asarray(q, jnp.bfloat16)
        table = jnp.asarray(rng.permutation(n_pages)[:s * table_w]
                            .reshape(s, table_w), jnp.int32)
        q_len = rng.integers(1, qmax + 1, (s,))
        q_len[0] = qmax
        q_len[-1] = 0
        q_start = rng.integers(0, table_w * ps - qmax, (s,))
        q_start[0] = table_w * ps - qmax
        args = (q, pool, table, jnp.asarray(q_start, jnp.int32),
                jnp.asarray(q_len, jnp.int32),
                jnp.asarray(np.where(q_len > 0, q_start + q_len, 0),
                            jnp.int32))
        got = np.asarray(jax.jit(lambda *a: mla_paged_attention(
            *a, layer=jnp.int32(1), role=name, interpret=interpret,
            out_dtype=jnp.float32, **kw))(*args))
        want = np.asarray(jax.jit(lambda *a: mla_paged_attention_ref(
            *a, layer=1, out_dtype=jnp.float32, **kw))(*args))
        check(got.shape == (s, qmax, nh, dl), f"{name}: shape {got.shape}")
        check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
        pad = np.arange(qmax)[None, :] >= q_len[:, None]
        check((got[pad] == 0).all(), f"{name}: padded rows not exactly zero")
        err = float(np.abs(got - want).max())
        check(err <= KERNEL_TOL, f"{name}: |kernel - plain| = {err} > "
                                 f"{KERNEL_TOL}")
        rows[name] = {"q": list(q.shape), "max_abs_err": err,
                      "ref_abs_max": float(np.abs(want).max())}
    return {"compared": "mla_paged_attention vs mla_paged_attention_ref, "
                        "bf16 q/rows, f32 out",
            "heads": nh, "row": [cfg.latent_row, width], "page_size": ps,
            "table_width": table_w, "tolerance_abs": KERNEL_TOL,
            "cases": rows}


def latent_phase(cfg, sizes, *, attention_impl="auto", interpret=False,
                 dtype="bfloat16", seed=0, report=emit):
    """The latent-attention family through the same engine: dense prefill,
    prefill chunks that read a prefix back from the latent pages and the
    decode horizon all run, a repeated prompt is served from the prefix
    cache, no routed row is dropped, and the kernel engine's greedy tokens
    agree with the plain-attention engine's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.mla_moe import build_functional_mla_moe
    params = jax.block_until_ready(jax.jit(
        lambda k: build_functional_mla_moe(cfg, k, jnp.dtype(dtype)))(
            jax.random.PRNGKey(seed)))
    prompts = make_prompts(cfg, sizes["prompt_lens"], seed + 1)
    chunk = sizes["prefill_chunk"]
    check(any(len(p) > 2 * chunk for p in prompts)
          and any(len(p) <= chunk for p in prompts),
          "prompts must straddle the prefill chunk, one over several chunks")
    outs, facts, eng = run_engine(params, cfg, sizes, prompts,
                                  sizes["max_new_tokens"],
                                  attention_impl=attention_impl,
                                  interpret=interpret)
    ran, st = facts["executables"], eng.stats()
    check(ran.get("prefill", 0) > 0 and ran.get("prefill_chunk", 0) > 0
          and ran.get("decode_step", 0) > 0,
          f"dense prefill, chunked prefill and decode must all run: {ran}")
    check(eng.family.page_leaves == ("latent",) and eng.cache is not None,
          "one latent page store, with a prefix cache")
    check(st["moe_rows_dropped"] == 0 and st["moe_pairs_held"] > 0,
          f"routed rows: {st['moe_pairs_held']} held, "
          f"{st['moe_rows_dropped']} dropped")
    # the longest prompt again: its whole pages come out of the cache
    rid = eng.submit(prompts[-1], max_new_tokens=sizes["max_new_tokens"])
    again = [int(t) for t in eng.run()[rid].generated]
    hit = eng.stats()["cached_prefix_tokens"] - st["cached_prefix_tokens"]
    check(hit >= len(prompts[-1]) // sizes["page_size"] * sizes["page_size"]
          - sizes["page_size"], f"prefix cache served {hit} tokens")
    same = token_agreement([again], [outs[-1]])
    compiled = eng.decode_horizon_compiled()
    facts.update(
        family=eng.family.name, depth=cfg.num_hidden_layers,
        parameters=count_params(params),
        moe_pairs_held=st["moe_pairs_held"],
        latent_rows_written=st["latent_rows_written"],
        prefix_tokens_from_cache=hit, prefix_hit_vs_cold=same,
        decode_has_tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        decode_executable_bytes=executable_bytes(compiled),
        peak_bytes_in_use=peak_bytes(jax.devices()[:1])[0])
    report("latent", **facts)
    check(same["agreement"] >= KERNEL_VS_REF_FLOOR,
          f"a prefix hit against the cold run: {same}")
    del eng, compiled
    gc.collect()
    n_cmp = sizes["compare_tokens"]
    ref_outs, _, ref_eng = run_engine(params, cfg, sizes, prompts, n_cmp,
                                      attention_impl="ref")
    del ref_eng
    versus = {"compared": f"first {n_cmp} greedy tokens of every request, "
                          f"attention_impl={attention_impl!r} vs 'ref'",
              **token_agreement([o[:n_cmp] for o in outs], ref_outs)}
    report("latent_vs_ref", **versus)
    check(versus["agreement"] >= KERNEL_VS_REF_FLOOR,
          f"kernel engine vs ref engine: {versus}")
    return {**facts, "vs_ref_engine": versus}


def sambay_config(name, layers):
    """(SambaYConfig cut to ``layers``, the configuration file) of a
    benchmark configuration of the SambaY family."""
    from benchmark.drivers import serve_sambay as drv
    from benchmark.run import load_json
    conf = load_json(os.path.dirname(os.path.abspath(__file__)),
                     "benchmark", "configs", name + ".json")
    return drv.model_config(conf, num_hidden_layers=layers), \
        {**conf, "num_hidden_layers": layers}


def sambay_phase(cfg, conf, sizes, *, attention_impl="auto", interpret=False,
                 dtype="bfloat16", seed=0, report=emit, limits=None):
    """The SambaY family through the same engine: dense prefill, prefill
    chunks (window, state and tail carried; the last alone enters the second
    half) and the decode horizon all run, past a wrap of the window's ring;
    every request's tokens, SSM states and window rows are held to the plain
    reference's full forward (`benchmark/reference_sambay.py`, float32), and
    the kernel engine's greedy tokens agree with the plain-attention
    engine's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference_sambay as reference
    from paddle_tpu.models.sambay import build_functional_sambay
    limits = limits or {"logit": reference.SERVE_LOGIT_DELTA,
                        "state": reference.SERVE_STATE_RTOL,
                        "window": reference.SERVE_WINDOW_RTOL}
    params = jax.block_until_ready(jax.jit(
        lambda k: build_functional_sambay(cfg, k, jnp.dtype(dtype)))(
            jax.random.PRNGKey(seed)))
    prompts = make_prompts(cfg, sizes["prompt_lens"], seed + 1)
    chunk, W = sizes["prefill_chunk"], cfg.sliding_window
    check(any(len(p) > 2 * chunk for p in prompts)
          and any(W < len(p) <= chunk for p in prompts),
          "prompts must straddle the prefill chunk, one over two chunks, "
          "and a dense prefill must be longer than the window")
    from paddle_tpu.inference.paged import ServingEngine
    eng = ServingEngine(
        params, cfg, num_slots=sizes["num_slots"],
        page_size=sizes["page_size"],
        max_pages_per_seq=sizes["max_pages_per_seq"],
        dtype=params[0]["tok"].dtype, attention_impl=attention_impl,
        interpret=interpret, prompt_bucket=sizes["prompt_bucket"],
        decode_horizon=sizes["decode_horizon"], prefill_chunk=chunk)
    rids = [eng.submit(p, max_new_tokens=sizes["max_new_tokens"])
            for p in prompts]
    states = {}
    while not all(eng.lookup(r).finish_time for r in rids):
        eng.step()
        for r in rids:          # the slot keeps its state until it is reused
            if r not in states and eng.lookup(r).finish_time:
                states[r] = eng.recurrent_state(r)
    eng.check_invariants()
    outs = [[int(t) for t in eng.lookup(r).generated] for r in rids]
    st = eng.stats()
    ran = st["jit_cache_misses"]
    check(ran.get("prefill", 0) > 0 and ran.get("prefill_chunk", 0) > 1
          and ran.get("decode_step", 0) > 0,
          f"dense prefill, both chunk executables and decode must run: {ran}")
    check(st["prefill_tokens_cross_decoder"] == len(prompts),
          f"{st['prefill_tokens_cross_decoder']} tokens entered the second "
          f"half of the model for {len(prompts)} prompts")
    gaps, state_err, window_err = [], [], []
    pad = max(len(p) for p in prompts) + sizes["max_new_tokens"]
    for p, o, r in zip(prompts, outs, rids):
        want = reference.check_generation(params, conf, p, o, pad_to=pad)
        gaps += want["gaps"]
        state_err += reference.relative_errors(list(states[r]["ssm"]),
                                               want["states"])
        rows = want["window_positions"] % W
        for j, kv in enumerate(want["window"]):
            window_err += reference.relative_errors(
                [states[r]["window_k"][j][rows],
                 states[r]["window_v"][j][rows]], kv)
    compiled = eng.decode_horizon_compiled()
    facts = {
        "family": eng.family.name, "depth": cfg.num_hidden_layers,
        "parameters": count_params(params), "dtype": str(jnp.dtype(dtype)),
        "prompt_lens": [int(len(p)) for p in prompts],
        "executables": ran, "tokens_generated": st["tokens_generated"],
        "compared": "every request's greedy tokens under the float32 "
                    "reference's logits, its SSM states and window rows",
        "worst_logit_gap": max(gaps), "logit_delta": limits["logit"],
        "worst_state_error": max(state_err), "state_rtol": limits["state"],
        "worst_window_error": max(window_err),
        "window_rtol": limits["window"],
        "shared_kv_tokens_attended_decode":
            st["shared_kv_tokens_attended_decode"],
        "prefill_tokens_cross_decoder": st["prefill_tokens_cross_decoder"],
        "decode_has_tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "decode_executable_bytes": executable_bytes(compiled),
        "peak_bytes_in_use": peak_bytes(jax.devices()[:1])[0]}
    report("sambay", **facts)
    check(facts["worst_logit_gap"] <= limits["logit"]
          and facts["worst_state_error"] <= limits["state"]
          and facts["worst_window_error"] <= limits["window"],
          f"the engine against the reference: {facts}")
    del eng, compiled
    gc.collect()
    n_cmp = sizes["compare_tokens"]
    ref_outs, _, ref_eng = run_engine(params, cfg, sizes, prompts, n_cmp,
                                      attention_impl="ref")
    del ref_eng
    versus = {"compared": f"first {n_cmp} greedy tokens of every request, "
                          f"attention_impl={attention_impl!r} vs 'ref'",
              **token_agreement([o[:n_cmp] for o in outs], ref_outs)}
    report("sambay_vs_ref", **versus)
    check(versus["agreement"] >= KERNEL_VS_REF_FLOOR,
          f"kernel engine vs ref engine: {versus}")
    return {**facts, "vs_ref_engine": versus}


# ---------------------------------------------------------------------------
# train: the donated jitted train step
# ---------------------------------------------------------------------------
def train_kernels():
    """The registry's attention and norm for the functional train block —
    which on a TPU must be the Pallas ones (the perf contract of the
    train path; tests/test_chip_compile.py compiles them for the chip)."""
    from paddle_tpu.core.dispatch import get_kernel
    names = ("flash_attention_causal", "rms_norm")
    impls = {n: get_kernel(n) for n in names}
    for n, k in impls.items():
        check(k is not None
              and (k.__module__ or "").startswith("paddle_tpu.ops.pallas"),
              f"{n} did not resolve to its Pallas implementation: {k}")
    return {n: f"{k.__module__}.{k.__name__}" for n, k in impls.items()}


def build_train_step(cfg, seed=0):
    """(init_state, step): ``init_state()`` -> (ep, bp, hp, eo, bo, ho), a
    pure function so ``jax.eval_shape`` can size it without a device;
    ``step(*state, batch)`` -> (*state', loss), to be jitted with the six
    state arguments donated."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optimizer
    from paddle_tpu.models.llama import build_functional_llama
    from paddle_tpu.parallel.pipeline import _flatten, _unflatten

    opt = optimizer.AdamW(learning_rate=1e-4, parameters=[])
    lr = jnp.asarray(1e-4, jnp.float32)
    _, _, _, ea, ba, hl = build_functional_llama(
        cfg, dtype=jnp.bfloat16, n_micro=1, head_chunks=8, init_params=False)

    def init_state():
        ep, bp, hp = build_functional_llama(
            cfg, key=jax.random.PRNGKey(seed), dtype=jnp.bfloat16)[:3]
        return (ep, bp, hp) + tuple(opt.init_opt_state(_flatten(p))
                                    for p in (ep, bp, hp))

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

    return init_state, step


def train_phase(cfg, sizes, *, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    init_state, step = build_train_step(cfg, seed)
    state = init_state()
    rng = np.random.default_rng(seed + 2)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (sizes["batch"], sizes["seq"])), jnp.int32)
    batch = (ids, ids)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=tuple(range(6))) \
        .lower(*state, batch).compile()
    compile_s = time.perf_counter() - t0
    losses = []
    for _ in range(sizes["steps"]):
        *state, loss = compiled(*state, batch)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {
        "depth": cfg.num_hidden_layers, "batch": sizes["batch"],
        "seq": sizes["seq"], "steps": sizes["steps"],
        "parameters": count_params(state[:3]), "losses": losses,
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "compile_seconds": round(compile_s, 3),
        "train_executable_bytes": executable_bytes(compiled),
        "peak_bytes_in_use": peak_bytes(jax.devices()[:1])[0],
    }


# ---------------------------------------------------------------------------
# --multichip: the TP engine and the one-chip engine it is compared with
# ---------------------------------------------------------------------------
def shard_facts(arr, axis, n):
    """Where an array really sits: the devices of its addressable shards
    and their shape.  Sharded over ``axis`` on ``n`` devices means n
    distinct devices each holding 1/n of that axis."""
    shards = arr.addressable_shards
    devs = sorted({s.device.id for s in shards})
    want = list(arr.shape)
    want[axis] //= n
    check(len(devs) == n, f"array lives on devices {devs}, wanted {n}")
    check(all(list(s.data.shape) == want for s in shards),
          f"shard shapes {[s.data.shape for s in shards]} != {want}")
    return {"global": list(arr.shape), "shard": want, "devices": devs}


def multichip_phase(make_cfg, sizes, devices, *, attention_impl="auto",
                    interpret=False, seed=0, report=emit):
    """Each arm of ``sizes["arms"]``: the TP engine on a ``{"mp": tp}`` mesh
    of ``devices``, where its state really sits, then the one-chip engine on
    the same weights and prompts, then where their greedy tokens first part
    (``make_cfg(layers)`` -> config)."""
    from paddle_tpu.distributed.topology import build_mesh

    tp = sizes["tp"]
    check(len(devices) >= tp, f"{len(devices)} devices, need {tp}")
    mesh = build_mesh({"mp": tp}, devices=devices[:tp])
    arms = {}
    for arm in sizes["arms"]:
        arms[arm["dtype"]] = _multichip_arm(
            make_cfg(arm["layers"]), sizes, arm, mesh, devices[:tp],
            attention_impl, interpret, seed, report)
    return arms


def _multichip_arm(cfg, sizes, arm, mesh, devices, attention_impl, interpret,
                   seed, report):
    import jax

    tp = len(devices)
    tag = f"multichip/{arm['dtype']}"
    # both engines of an arm trace under the arm's matmul precision (None =
    # the backend's default)
    with jax.default_matmul_precision(arm.get("matmul_precision")):
        params = build_params(cfg, seed, arm["dtype"])
        prompts = make_prompts(cfg, sizes["prompt_lens"], seed + 1)
        tp_outs, tp_facts, eng = run_engine(
            params, cfg, sizes, prompts, sizes["max_new_tokens"],
            attention_impl=attention_impl, interpret=interpret, mesh=mesh)
        text = eng.decode_horizon_compiled().as_text()
        tp_facts.update(
            matmul_precision=arm.get("matmul_precision") or "default",
            depth=cfg.num_hidden_layers, parameters=count_params(params),
            mesh={"axes": dict(mesh.shape),
                  "devices": [d.id for d in mesh.devices.flat]},
            placed={"pages_k": shard_facts(eng._pages_k, 1, tp),
                    "wq": shard_facts(eng.params[1]["wq"], 2, tp),
                    "wgate": shard_facts(eng.params[1]["wgate"], 2, tp),
                    "wdown": shard_facts(eng.params[1]["wdown"], 1, tp)},
            decode_has_tpu_custom_call="tpu_custom_call" in text,
            decode_has_all_reduce="all-reduce" in text,
            peak_bytes_in_use_per_device=peak_bytes(devices))
        report(f"{tag}/tp_engine", **tp_facts)
        check(tp_facts["tp_degree"] == tp and tp_facts["decode_has_all_reduce"],
              f"not a TP={tp} engine with a per-layer all-reduce")
        del eng
        gc.collect()
        one_outs, one_facts, one = run_engine(
            params, cfg, sizes, prompts, sizes["max_new_tokens"],
            attention_impl=attention_impl, interpret=interpret)
        report(f"{tag}/one_chip_engine", **one_facts)
        del one, params
        gc.collect()
        versus = {
            "compared": f"greedy tokens, TP={tp} vs one chip, same weights "
                        f"and prompts, f32 collectives",
            "tolerance": f"agreement >= {arm['min_agreement']} "
                         f"(1.0 = the repo's TP contract: tokens equal)",
            **token_agreement(tp_outs, one_outs)}
        report(f"{tag}/tp_vs_one_chip", **versus)
        check(versus["agreement"] >= arm["min_agreement"],
              f"{tag}: TP={tp} engine vs one-chip engine: {versus}")
    return {"tp_engine": tp_facts, "one_chip_engine": one_facts,
            "tp_vs_one_chip": versus}


# ---------------------------------------------------------------------------
def device_phase(cache_dir):
    """What JAX sees, where compiles are cached, and one complex64 array
    fetched whole (Tensor.numpy() has no complex special case any more)."""
    import jax
    import numpy as np
    import paddle_tpu as paddle

    z = np.asarray([1 + 2j, -3.5 + 0.25j, 0j], np.complex64)
    t = paddle.to_tensor(z)
    back = (t * t).numpy()
    check(back.dtype == np.complex64 and np.allclose(back, z * z),
          f"complex64 round trip: {back}")
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "jax": jax.__version__,
            "compile_cache_dir": cache_dir,
            "complex64_fetch": [str(c) for c in back]}


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the TP=4 engine and its one-chip "
                         "comparison (needs four chips)")
    ap.add_argument("--hybrid", action="store_true",
                    help="run ONLY the recurrent family's engine (Mamba-2 + "
                         "attention + LatentMoE at the benchmark "
                         "configuration's widths; one chip)")
    ap.add_argument("--latent", action="store_true",
                    help="run ONLY the latent-attention family: its kernel "
                         "against the plain form, the SwiGLU experts' "
                         "grouped product, its engine (one chip)")
    ap.add_argument("--sambay", action="store_true",
                    help="run ONLY the SambaY family's engine (Mamba-1 + "
                         "differential attention + GMU over one K/V store) "
                         "against the plain reference (one chip)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if dev.device_kind not in SIZES:
        raise KeyError(f"no sizes on record for device kind "
                       f"{dev.device_kind!r} (known: {sorted(SIZES)})")
    sizes = SIZES[dev.device_kind]

    from paddle_tpu.core.device import setup_compile_cache
    cache_dir = setup_compile_cache()
    entries_before = cache_entries(cache_dir)
    emit("device", **device_phase(cache_dir),
         compile_cache_entries_before=entries_before)

    if args.multichip:
        arms = multichip_phase(cut_config, sizes["multichip"],
                               jax.devices(), seed=args.seed)
        check(all(a["tp_engine"]["decode_has_tpu_custom_call"]
                  for a in arms.values()),
              "no tpu_custom_call in a TP decode executable: the Pallas "
              "kernel did not run under shard_map")
    elif args.hybrid:
        hy = sizes["hybrid"]
        cfg = hybrid_config(hy["config"])[0]
        emit("grouped_product", **grouped_product_phase(
            cfg, hy["product_tokens"], seed=args.seed))
        hybrid = hybrid_phase(cfg, hy, seed=args.seed)
        check(hybrid["decode_has_tpu_custom_call"],
              "no tpu_custom_call in the hybrid decode executable")
    elif args.latent:
        la = sizes["latent"]
        cfg = latent_config(la["config"], la["layers"])[0]
        emit("latent_kernel", **latent_kernel_phase(cfg, la, seed=args.seed))
        h, f = cfg.hidden_size, cfg.moe_intermediate_size
        emit("grouped_product", **grouped_product_phase(
            cfg, la["product_tokens"], shapes=((h, f), (f, h)),
            seed=args.seed))
        latent = latent_phase(cfg, la, seed=args.seed)
        check(latent["decode_has_tpu_custom_call"],
              "no tpu_custom_call in the latent decode executable")
    elif args.sambay:
        sa = sizes["sambay"]
        got = sambay_phase(*sambay_config(sa["config"], sa["layers"]), sa,
                           seed=args.seed)
        check(got["decode_has_tpu_custom_call"],
              "no tpu_custom_call in the sambay decode executable")
    else:
        sv = sizes["serve"]
        cfg = cut_config(sv["layers"])
        emit("kernel", **kernel_parity_phase(cfg, sv, seed=args.seed))
        serve = serve_phase(cfg, sv, seed=args.seed)
        check(serve["decode_has_tpu_custom_call"],
              "no tpu_custom_call in the decode executable: the Pallas "
              "kernel did not run")
        jax.clear_caches()          # the serve phase's executables ...
        gc.collect()                # ... and buffers, before the trainer
        kernels = train_kernels()
        tr = sizes["train"]
        train = train_phase(cut_config(tr["layers"]), tr, seed=args.seed)
        emit("train", kernels=kernels, **train)
        check(train["tpu_custom_calls"] > 0,
              "no tpu_custom_call in the train executable")

    emit("compile_cache", dir=cache_dir, entries_before=entries_before,
         entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
