#!/usr/bin/env python3
"""The LatentMoE grouped products ALONE on the chip, at `serve_reason_c64`'s
three shapes: `jax.lax.ragged_dot` (XLA's lowering) beside the Pallas kernel
(`paddle_tpu/ops/pallas/grouped_matmul.py`).  PERF.md section 5's table and
the kernel's tile constants come from here (ISSUE 34).

    chiprun -- python3 perf/grouped_matmul_probe.py [--sweep] [--seed N]
        [--tag NAME] [--cases decode,chunk,chunk.tier2]

One process, one layer's expert matrices `bf16[128, 1024, 2688]` and
`bf16[128, 2688, 1024]`.  A case is a row bound and a load: `decode` 704 rows
for 64 tokens, `chunk` 11,264 for 1,024 tokens, `chunk.tier2` 22,528 for the
same tokens at 2.2 times the share (the second row bound is taken when the
counted rows pass the first).  The load is drawn from `--seed` as the cell's
router draws it: every token takes its top 22 of 512 experts by a fixed
popularity plus noise (sigma 0.7: ~105 of the 128 held experts get a row of a
decode batch, the most loaded ~5 times the mean), the 128 held ones counted.
Each (case, implementation) is one jitted program of PAIRS expert calls, up
product, relu^2, down product, each call's rows depending on the last call's
output.  Read per product from one profiled run: device ms a call of the
events carrying `TRACE_LABEL`, split by their result's width; the touched
experts' weight bytes over that time against the HBM peak; the kernel's
(row tile, group) visits over the touched experts.  The first call of every
kernel program is compared with `ragged_dot`'s on the counted rows.
`--sweep` runs the kernel over row tiles and weight-tile widths instead of
its own choice.  Every row prints as one JSON line; all of them go to
`chiprun_out/grouped_matmul_probe.<tag>.json`.  No CPU fallback.
"""
import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
HELD, EXPERTS, TOP_K, LATENT, INTER = 128, 512, 22, 1024, 2688
SIGMA = 0.7
# name -> (row bound, tokens, share of the rows over the cell's, role, pairs)
CASES = {"decode": (704, 64, 1.0, "decode", 20),
         "chunk": (11264, 1024, 1.0, "prefill", 4),
         "chunk.tier2": (22528, 1024, 2.2, "prefill", 4)}
SWEEP = {"decode": ([16, 32, 64, 128], [(896, 512), (2688, 1024)]),
         "chunk": ([64, 128, 256], [(896, 512), (2688, 1024)]),
         "chunk.tier2": ([128, 256], [(896, 512)])}
_RESULT = re.compile(r"= \w+\[\d+,(\d+)\]")


def held_rows(rng, tokens, share):
    """int32 [HELD]: the rows each held expert gets from ``tokens`` tokens."""
    noisy = rng.gumbel(size=(tokens, EXPERTS)) \
        + SIGMA * rng.standard_normal(EXPERTS)
    sel = np.argsort(-noisy, axis=1)[:, :TOP_K]
    rows = np.bincount(sel[sel < HELD], minlength=HELD)
    return np.round(rows * share).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--cases", default="")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(HERE))

    import jax
    import jax.numpy as jnp
    from benchmark import peaks, trace_reduce
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": f"needs the chip, found {dev.platform}"}))
        return 2
    hbm_bytes_per_s = peaks.lookup(dev.device_kind)["hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)
    normal = lambda i, shape, scale: jax.jit(
        lambda k: (jax.random.normal(k, shape, jnp.float32) * scale)
        .astype(jnp.bfloat16))(jax.random.PRNGKey(args.seed + i))
    w_up = normal(0, (HELD, LATENT, INTER), LATENT ** -0.5)
    w_down = normal(1, (HELD, INTER, LATENT), INTER ** -0.5)

    def products(impl, role):
        """impl None: ragged_dot; else (tm, (tn of up, tn of down)), None for
        the kernel's own choice."""
        if impl is None:
            return [gm.grouped_matmul_ref] * 2
        tm, (tn_up, tn_down) = impl
        return [lambda xs, w, rows, tn=tn: gm.grouped_matmul(
            xs, w, rows, tm=tm, tn=tn, role=role) for tn in (tn_up, tn_down)]

    results = []
    for name, (bound, tokens, share, role, pairs) in CASES.items():
        if args.cases and name not in args.cases.split(","):
            continue
        rows_np = held_rows(rng, tokens, share)
        assert rows_np.sum() <= bound, (name, int(rows_np.sum()))
        rows = jnp.asarray(rows_np)
        counted, touched = int(rows_np.sum()), int((rows_np > 0).sum())
        x0 = normal(2, (bound, LATENT), 1.0)
        own = gm.tiles(bound, LATENT, INTER, HELD), \
            gm.tiles(bound, INTER, LATENT, HELD)
        impls = [None, (None, (None, None))]
        if args.sweep:
            tms, tns = SWEEP[name]
            impls = [None] + [(tm, tn) for tm in tms for tn in tns]
        expected = None
        for impl in impls:
            up, down = products(impl, role)

            # the matrices are ARGUMENTS: closed over they would be 1.4 GB
            # of constants in every executable, minutes a compile
            def pair(x, wu, wd, rows):
                h = up(x, wu, rows)
                return down(jnp.square(jax.nn.relu(h)), wd, rows)

            @jax.jit
            def program(x, wu, wd, rows):
                return jax.lax.fori_loop(
                    0, pairs, lambda i, x: x + pair(x, wu, wd, rows)
                    * jnp.bfloat16(1e-3), x)

            operands = (x0, w_up, w_down, rows)

            row = {"tag": args.tag, "case": name, "rows_bound": bound,
                   "rows_counted": counted, "experts_touched": touched,
                   "load_max_over_mean": float(
                       rows_np.max() * HELD / max(counted, 1)),
                   "impl": "ragged_dot" if impl is None else "kernel"}
            if impl is not None:
                tm = impl[0] or own[0][0]
                row.update(tm=tm, tn_up=impl[1][0] or own[0][1],
                           tn_down=impl[1][1] or own[1][1])
                visits = int(gm.weight_visits(rows, bound, tm))
                row.update(visits=visits,
                           visits_over_touched=visits / max(touched, 1))
            try:
                first = np.asarray(jax.jit(pair)(*operands)[:counted],
                                   np.float32)
                if impl is None:
                    expected = first
                else:
                    row["max_abs_diff_vs_ragged_dot"] = float(
                        np.abs(first - expected).max())
                    row["ragged_dot_abs_max"] = float(np.abs(expected).max())
                jax.block_until_ready(program(*operands))
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    jax.block_until_ready(program(*operands))
                row["host_ms_per_pair"] = (time.perf_counter() - t0) \
                    / args.reps / pairs * 1e3
                logdir = tempfile.mkdtemp(prefix="gmm_probe_")
                jax.profiler.start_trace(logdir)
                jax.block_until_ready(program(*operands))
                jax.profiler.stop_trace()
                planes = trace_reduce.load(logdir)
                shutil.rmtree(logdir, ignore_errors=True)
                ops = [e for e in planes[sorted(planes)[0]][trace_reduce.OPS]
                       if gm.TRACE_LABEL in e[0]]
                row["labelled_events"] = len(ops)
                for which, width in (("up", INTER), ("down", LATENT)):
                    mine = [e for e in ops
                            if _RESULT.search(e[0]).group(1) == str(width)]
                    ms = trace_reduce.union_ns(mine) / 1e6 / pairs
                    weight_bytes = touched * LATENT * INTER * 2
                    row[f"{which}_ms"] = ms
                    row[f"{which}_weight_roofline_pct"] = \
                        100 * weight_bytes / hbm_bytes_per_s / (ms / 1e3)
                row["pair_ms"] = row["up_ms"] + row["down_ms"]
                row["pair_weight_gb_per_s"] = \
                    2 * touched * LATENT * INTER * 2 / row["pair_ms"] / 1e6
            except Exception as e:             # a tiling Mosaic refuses
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)
            results.append(row)
    dest = os.path.join(os.path.dirname(HERE), "chiprun_out")
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, f"grouped_matmul_probe.{args.tag}.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "seed": args.seed,
                   "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
