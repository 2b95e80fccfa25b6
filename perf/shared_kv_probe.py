#!/usr/bin/env python3
"""The ragged paged-attention kernel ALONE on the chip at
`serve_longreason_c64`'s two decode shapes: 64 slots, one query a slot, 40
query heads of 128 (the zero-padded halves of differential attention) over 10
K/V rows of 128, ONE pool of one layer.

  store  contexts as the cell's traffic leaves them (prompt 2-8 k log-uniform
         + part of an answer of 256-1,024), a table of 9,344 tokens a slot;
  ring   a window layer's ring: 512 rows a slot, every one live, its pages in
         order.

    chiprun -- python3 perf/shared_kv_probe.py [--pages 64,128,256]
        [--cases store,ring] [--root DIR] [--tag NAME] [--seed N]

A case a shape and page size: one jitted program of CALLS kernel calls (each
call's queries depend on the last call's output), the host clock over it per
call (ends in `block_until_ready`), the same over the kernel's loop steps
(one a slot and live page: every head pair of the page in it) and the bytes
of live K/V a call over that time as a share of the published HBM bandwidth.
`--root` names another checkout of this repo (the parent's, unpacked by `git
archive`) whose kernel is timed instead.  One JSON line a case; all of them
in `chiprun_out/shared_kv_probe.<tag>.json`.  No CPU fallback.
"""
import argparse
import json
import math
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SLOTS, HQ, HKV, D, CONTEXT, WINDOW = 64, 40, 10, 128, 9344, 512
CALLS = 64                         # one dispatch: 8 reading layers x horizon 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", default="64,128,256")
    ap.add_argument("--cases", default="store,ring")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    sys.path.insert(1, os.path.dirname(HERE))          # benchmark/ peaks
    import jax
    import jax.numpy as jnp
    from benchmark import peaks
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("shared_kv_probe: no TPU; nothing was run", file=sys.stderr)
        return 2
    peak = peaks.lookup(dev.device_kind)["hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)
    prompt = np.exp(rng.uniform(math.log(2048), math.log(8192), SLOTS))
    context = {"store": CONTEXT, "ring": WINDOW}
    kv_lens = {"store": (prompt + rng.uniform(0, 1, SLOTS)
                         * rng.uniform(256, 1024, SLOTS)).astype(np.int32),
               "ring": np.full(SLOTS, WINDOW, np.int32)}
    rows = []
    for case in args.cases.split(","):
        kv_len = kv_lens[case]
        for ps in (int(p) for p in args.pages.split(",")):
            table_w = context[case] // ps
            n_pages = SLOTS * table_w
            pool = jnp.asarray(rng.normal(0, 1, (1, HKV, n_pages + 1, ps, D)),
                               jnp.bfloat16)
            order = rng.permutation(n_pages) if case == "store" \
                else np.arange(n_pages)
            table = jnp.asarray(order.reshape(SLOTS, table_w), jnp.int32)
            q = jnp.asarray(rng.normal(0, 1, (SLOTS, 1, HQ, D)) * 0.3,
                            jnp.bfloat16)
            lens = jnp.asarray(kv_len)
            kind = "cross" if case == "store" else "window"

            @jax.jit
            def program(q, k, v, table, lens):
                def call(_, q):
                    o = ragged_paged_attention(
                        q, k, v, table, lens - 1, jnp.ones_like(lens), lens,
                        sm_scale=0.125, out_dtype=jnp.float32, role="decode",
                        kind=kind, layer=jnp.int32(0))
                    return (q + 1e-3 * o).astype(q.dtype)
                return jax.lax.fori_loop(0, CALLS, call, q)

            jax.block_until_ready(program(q, pool, pool, table, lens))
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(program(q, pool, pool, table, lens))
            per_call = (time.perf_counter() - t0) / 3 / CALLS
            live = int(kv_len.sum()) * 2 * HKV * D * 2
            loop_steps = int((-(-kv_len // ps)).sum())
            row = {"tag": args.tag, "case": case, "page_size": ps,
                   "table_width": table_w,
                   "mean_context": float(kv_len.mean()),
                   "ms_per_call": 1e3 * per_call,
                   "loop_steps": loop_steps,
                   "us_per_loop_step": 1e6 * per_call / loop_steps,
                   "live_kv_bytes": live,
                   "roofline_pct": 100 * live / per_call / peak,
                   "device": dev.device_kind}
            print(json.dumps(row), flush=True)
            rows.append(row)
            del pool
    dest = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"shared_kv_probe.{args.tag}.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
