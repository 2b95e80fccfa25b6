#!/usr/bin/env python3
"""The ragged paged-attention kernel ALONE on the chip, at `serve_chat_c16`'s
shapes (PERF.md section 5's kernel table; ISSUE 30 step 0).

    chiprun -- python3 perf/ragged_kernel_probe.py [--root DIR] [--tag NAME]
        [--heads 8,4,1] [--cases decode.chat,chunk.512]

One process, one pool `bf16[16, 8, 513, 64, 128]` a side (the cell's), page
tables as `ServingEngine` writes them (a slot's pages anywhere in the pool,
dead entries 0).  Each case is one jitted program of CALLS kernel calls, the
layer index traced and cycling as the model's layer loop hands it over, each
call's queries depending on the last call's output.  Two readings a case:
host clock over the program per call (ends in `block_until_ready`; includes
the query relayout around the kernel) and, from one profiled run, the device
seconds of the kernel's own events per call (`trace_reduce`, matched by the
`kernel_metadata` label).  `--root` names another checkout of this repo (the
parent's, unpacked by `git archive`) whose kernel is timed instead; `.nobody`
cases run the kernel with `_attend_page` emptied: what the grid steps and the
page DMAs cost without the math.  Every case prints one JSON line; all of
them go to `chiprun_out/ragged_kernel_probe.<tag>.json`.  No CPU fallback.
"""
import argparse
import inspect
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SLOTS, HQ, HKV, D, PAGE, TABLE, LAYERS = 16, 32, 8, 128, 64, 32, 16
CALLS = 128                        # one dispatch: 16 layers x horizon 8
LABEL = r'kernel_metadata=\{\s*"kernel":"ragged_paged_attention"'


def chat_kv_len(rng, n):
    """Context of a `chat_c16` slot at a random instant of its reply: prompt
    64-1024 log-uniform, output 32-128 uniform, progress uniform."""
    prompt = np.exp(rng.uniform(math.log(64), math.log(1024), n))
    out = rng.uniform(32, 128, n)
    return (prompt + rng.uniform(0, 1, n) * out).astype(np.int32)


def cases(rng):
    """name -> (slots, q_len, kv_len [slots])"""
    chat = chat_kv_len(rng, SLOTS)
    full = np.full(SLOTS, TABLE * PAGE, np.int32)
    out = {"decode.chat": (SLOTS, 1, chat),
           "decode.full": (SLOTS, 1, full),
           "decode.dead": (SLOTS, 1, np.ones(SLOTS, np.int32)),
           "decode.full.nobody": (SLOTS, 1, full),
           "decode.chat.nobody": (SLOTS, 1, chat),
           "verify.chat": (SLOTS, 5, chat + 5)}
    for kv in (512, 1024, 2048):
        out[f"chunk.{kv}"] = (1, 512, np.array([kv], np.int32))
    return out


def page_tables(rng, kv_len):
    """[S, TABLE]: each slot's live columns name distinct pages 1..512 in
    random order, dead columns 0 (`ServingEngine`'s `np.zeros`)."""
    perm = rng.permutation(np.arange(1, SLOTS * TABLE + 1))
    pt = np.zeros((len(kv_len), TABLE), np.int32)
    for s, n in enumerate(kv_len):
        live = -(-int(n) // PAGE)
        pt[s, :live] = perm[s * TABLE:s * TABLE + live]
    return pt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--heads", default="",
                    help="comma list of kv heads a step to force (the "
                         "change's private keyword); empty = the kernel's "
                         "own choice")
    ap.add_argument("--cases", default="")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=30)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    sys.path.insert(1, os.path.dirname(HERE))          # benchmark/ reader

    import jax
    import jax.numpy as jnp
    from benchmark import peaks, trace_reduce
    from paddle_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs the chip, found {dev.platform}"}))
        return 2
    hbm_bytes_per_s = peaks.lookup(dev.device_kind)["hbm_bytes_per_s"]
    forced = [int(h) for h in args.heads.split(",") if h] or [None]
    if forced != [None] and "_heads" not in inspect.signature(
            pa.ragged_paged_attention).parameters:
        print(json.dumps({"error": "this kernel takes no _heads"}))
        return 2

    rng = np.random.default_rng(args.seed)
    pool_shape = (LAYERS, HKV, SLOTS * TABLE + 1, PAGE, D)
    fill = jax.jit(lambda k: jax.random.normal(k, pool_shape, jnp.bfloat16))
    kpool, vpool = (fill(jax.random.PRNGKey(args.seed + i)) for i in (0, 1))
    attend = pa._attend_page
    results = []
    for name, (slots, q_len, kv_len) in cases(rng).items():
        if args.cases and name not in args.cases.split(","):
            continue
        pt = jnp.asarray(page_tables(rng, kv_len))
        kl = jnp.asarray(kv_len)
        ql = jnp.full((slots,), q_len, jnp.int32)
        qs = kl - q_len
        q0 = jax.random.normal(jax.random.PRNGKey(7),
                               (slots, q_len, HQ, D), jnp.bfloat16)
        role = name.split(".")[0]
        pa._attend_page = (lambda *a, **k: None) if name.endswith(".nobody") \
            else attend
        n_calls = CALLS // 8 if role == "chunk" else CALLS
        for heads in forced:
            kw = {} if heads is None else {"_heads": heads}

            @jax.jit
            def program(q, kp, vp):
                def call(i, q):
                    o = pa.ragged_paged_attention(
                        q, kp, vp, pt, qs, ql, kl, role=role,
                        layer=i % LAYERS, **kw)
                    return q + o * jnp.bfloat16(1e-3)
                return jax.lax.fori_loop(0, n_calls, call, q)

            row = {"tag": args.tag, "case": name, "heads": heads,
                   "slots": slots, "q_len": q_len, "calls": n_calls,
                   "kv_tokens": int(kv_len.sum()),
                   "live_pages": int((-(-kv_len // PAGE)).sum())}
            try:
                jax.block_until_ready(program(q0, kpool, vpool))
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    jax.block_until_ready(program(q0, kpool, vpool))
                host_ms = (time.perf_counter() - t0) / args.reps * 1e3
                row["host_ms_per_call"] = host_ms / n_calls
                logdir = tempfile.mkdtemp(prefix="ragged_probe_")
                jax.profiler.start_trace(logdir)
                jax.block_until_ready(program(q0, kpool, vpool))
                jax.profiler.stop_trace()
                planes = trace_reduce.load(logdir)
                shutil.rmtree(logdir, ignore_errors=True)
                ops = planes[sorted(planes)[0]][trace_reduce.OPS]
                kern_ms = trace_reduce.matching_ns(ops, LABEL) / 1e6
                bytes_ = row["kv_tokens"] * 2 * HKV * D * 2
                row.update(
                    kernel_ms_per_call=kern_ms / n_calls,
                    # memory-bound side: the live K/V bytes of one call
                    roofline_pct=None if name.endswith(".nobody") else
                    100 * bytes_ / hbm_bytes_per_s / (kern_ms / n_calls / 1e3))
            except Exception as e:                 # a blocking Mosaic refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)
            results.append(row)
    pa._attend_page = attend
    dest = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"ragged_kernel_probe.{args.tag}.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
