#!/usr/bin/env python3
"""How exact the ragged paged-attention kernel is ON THE CHIP: its output at
a decode shape (8 slots of 200-768 live tokens, 40 query rows of 128 over 10
K/V rows, page 128) against a float64 softmax over the same bf16 values.

    chiprun -- python3 perf/ragged_numerics_probe.py [--root DIR] [--tag NAME]

Interpret mode on the CPU computes an f32 `dot_general` in f32; Mosaic on
the chip runs it as ONE bf16 pass unless told "highest" (PERF.md section 6,
PR 40), so only a chip run says what a kernel's products round to.  Two
arms, the values given as bf16 and widened to f32 outside the call; beside
each the error ONE bf16 piece of the probabilities would leave (an
estimate: the kernel rounds per page, this per row).  PR 40 read, parent |
change: bf16 4.84e-4 | 1.05e-6, f32 4.84e-4 | 4.84e-4, one piece 4.84e-4.
One JSON line; `--root` names another checkout whose kernel is read
instead.  No CPU fallback.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SLOTS, HQ, HKV, D, PAGE, TABLE = 8, 40, 10, 128, 128, 6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("ragged_numerics_probe: no TPU; nothing was run",
              file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    pool_shape = (1, HKV, SLOTS * TABLE + 1, PAGE, D)
    q = jnp.asarray(rng.normal(0, 0.5, (SLOTS, 1, HQ, D)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(0, 1, pool_shape), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(0, 1, pool_shape), jnp.bfloat16)
    table = jnp.asarray(np.arange(SLOTS * TABLE).reshape(SLOTS, TABLE),
                        jnp.int32)
    lens = jnp.asarray(rng.integers(200, TABLE * PAGE, SLOTS), jnp.int32)

    @jax.jit
    def call(q, k, v):
        return ragged_paged_attention(
            q, k, v, table, lens - 1, jnp.ones_like(lens), lens,
            sm_scale=0.125, out_dtype=jnp.float32, role="decode",
            layer=jnp.int32(0))

    q64, k64, v64 = (np.asarray(a, np.float64) for a in (q, kp, vp))
    exact = np.zeros((SLOTS, HQ, D))
    one_piece = 0.0
    for s in range(SLOTS):
        n = int(lens[s])
        rows = slice(s * TABLE, (s + 1) * TABLE)
        k = k64[0][:, rows].reshape(HKV, TABLE * PAGE, D)[:, :n]
        v = v64[0][:, rows].reshape(HKV, TABLE * PAGE, D)[:, :n]
        for h in range(HQ):
            scores = k[h // (HQ // HKV)] @ q64[s, 0, h] * 0.125
            p = np.exp(scores - scores.max())
            exact[s, h] = p @ v[h // (HQ // HKV)] / p.sum()
            rounded = np.asarray(jnp.asarray(p, jnp.bfloat16), np.float64)
            one_piece = max(one_piece, np.abs(
                rounded @ v[h // (HQ // HKV)] / p.sum() - exact[s, h]).max())
    row = {"tag": args.tag, "device": dev.device_kind,
           "one_bf16_piece_of_p": one_piece}
    widen = lambda a: a.astype(jnp.float32)
    for arm, operands in (("bf16", (q, kp, vp)),
                          ("f32", (widen(q), widen(kp), widen(vp)))):
        out = np.asarray(call(*operands), np.float64)[:, 0]
        row[f"max_abs_err.{arm}"] = float(np.abs(out - exact).max())
    print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
