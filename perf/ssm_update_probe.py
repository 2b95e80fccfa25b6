#!/usr/bin/env python3
"""A Mamba layer's one-token state update ALONE on the chip, at
`serve_reason_c64`'s shape (64 slots x 128 heads x [64, 128] float32 = 268 MB
a layer): `ops/ssm.ssm_decode_update` on the layouts the cache has held, beside
what a read-once-write-once pass CAN reach here.  PERF.md section 5's table
comes from here (ISSUE 36).

    chiprun -- python3 perf/ssm_update_probe.py [--seed N] [--tag NAME]
        [--hb 32,128] [--state float32|bfloat16] [--out DIR]

One process.  A row is one jitted program of CALLS updates in a loop, the
state donated and carried, each call's `x` moved by the last call's `y`:
  plain           the layer's state its own leaf `[S, heads, P, N]` (the cache
                  since PR 36): XLA makes ONE fusion with both results
  plain.stacked   a slice of a stacked leaf `[5, S, heads, P, N]`, written
                  back with `.at[j].set` (the cache before PR 36): an update
                  in place and a second pass that reads the state for `y`
  pallas.copy     a Pallas call that moves blocks `[1, hb, P, N]` of the state
                  through VMEM and back to the same buffer, and nothing else:
                  the ceiling of ANY one-pass update on this chip
  pallas.fused    the same call doing the update and the reduce on the
                  resident tile (the kernel ISSUE 36 asked for; it lost to
                  `plain` and is kept here, not in the package)
Read per row: host ms a call over REPS runs of the program (median and
least), the bytes a call REQUIRES (the state read once and written once)
over that time against the published HBM peak, and the first call's `y` and
new state against the plain form's.  Every row prints as one JSON line; all
go to `chiprun_out/ssm_update_probe.<tag>.json`.

`--rehearse` is the CPU rehearsal of the same control flow at a tiny shape
with the Pallas calls in interpret mode: it checks the rows against each
other and reports NO time (a time comes from the chip; without the flag a
run that finds no TPU exits 2).
"""
import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# slots, heads, groups, P, N, layers of the stacked form
CELL = (64, 128, 8, 64, 128, 5)
TINY = (3, 8, 2, 8, 128, 2)
CALLS, REPS = 20, 5


def pallas_update(h, x, dt, a, b, c, *, hb, fused, interpret=False):
    """`ssm_decode_update`'s operands and results through ONE Pallas call over
    blocks of ``hb`` heads of the state, written back where they lay.
    ``fused``: the update and `y = h' C` on the resident tile, in the plain
    form's float32 arithmetic; else the state is only copied (y zeros).  What
    varies along a tile's sublanes (`dt x`, a number a (head, p)) comes in
    with the block's heads on the lanes, the decay rides scalar prefetch."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, heads, p = x.shape
    groups, n = b.shape[1:]
    rep, nb, f32 = heads // groups, heads // hb, jnp.float32
    assert heads % hb == 0 and (hb % rep == 0 or rep % hb == 0)

    def kernel(decay_ref, col_ref, b_ref, c_ref, h_ref, y_ref, out_ref):
        if not fused:
            out_ref[...] = h_ref[...]
            y_ref[...] = jnp.zeros_like(y_ref)
            return
        slot, j = pl.program_id(0), pl.program_id(1)
        for i in range(hb):                  # static: a head's lane is i
            g = (j * hb) // rep + i // rep
            new = h_ref[0, i].astype(f32) * decay_ref[slot, j * hb + i] \
                + col_ref[0, 0, :, i:i + 1] * b_ref[0, pl.ds(g, 1), :]
            out_ref[0, i] = new.astype(out_ref.dtype)
            y_ref[0, 0, :, i:i + 1] = jnp.sum(
                new * c_ref[0, pl.ds(g, 1), :], axis=1, keepdims=True)

    dt = dt.astype(f32)
    col = (dt[:, :, None] * x.astype(f32)).reshape(s, nb, hb, p) \
        .swapaxes(2, 3)                                      # [S, nb, P, hb]
    at_slot = lambda s, j, decay: (s, 0, 0)
    at_block = lambda s, j, decay: (s, j, 0, 0)
    y, new = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s, nb),
            in_specs=[pl.BlockSpec((1, 1, p, hb), at_block),
                      pl.BlockSpec((1, groups, n), at_slot),
                      pl.BlockSpec((1, groups, n), at_slot),
                      pl.BlockSpec((1, hb, p, n), at_block)],
            out_specs=[pl.BlockSpec((1, 1, p, hb), at_block),
                       pl.BlockSpec((1, hb, p, n), at_block)]),
        out_shape=[jax.ShapeDtypeStruct((s, nb, p, hb), f32),
                   jax.ShapeDtypeStruct(h.shape, h.dtype)],
        input_output_aliases={4: 1},         # the state IS the new state
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jnp.exp(dt * a.astype(f32)), col, b.astype(f32), c.astype(f32), h)
    return y.swapaxes(2, 3).reshape(s, heads, p).astype(x.dtype), new


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--hb", default="")
    ap.add_argument("--state", default="float32")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(os.path.dirname(HERE),
                                                  "chiprun_out"))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(HERE))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import peaks
    from paddle_tpu.ops.ssm import ssm_decode_update

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"needs the chip, found {dev.platform}"}))
        return 2
    slots, heads, groups, p, n, layers = TINY if args.rehearse else CELL
    calls = 2 if args.rehearse else CALLS
    state_dt = jnp.dtype(args.state)
    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed % (1 << 31)), 6)
    normal = lambda k, shape: jax.random.normal(k, shape, f32)
    x = normal(keys[1], (slots, heads, p)).astype(bf16)
    dt = jax.nn.softplus(normal(keys[2], (slots, heads)))
    a = -jnp.exp(normal(keys[3], (heads,)) * 0.5)
    b = normal(keys[4], (slots, groups, n)).astype(bf16)
    c = normal(keys[5], (slots, groups, n)).astype(bf16)
    # the same state every row; the stacked form holds it in every layer
    fresh = jax.jit(lambda lead: jnp.broadcast_to(
        normal(keys[0], (slots, heads, p, n)).astype(state_dt),
        lead + (slots, heads, p, n)), static_argnums=0)
    state_bytes = slots * heads * p * n * state_dt.itemsize

    def looped(update):
        """update(h, x) -> (y, h) -> the program of `calls` of them."""
        def program(h, x):
            def body(_, carry):
                h, x = carry
                y, h = update(h, x)
                return h, x + y * bf16(1e-3)
            return jax.lax.fori_loop(0, calls, body, (h, x))
        return jax.jit(program, donate_argnums=(0,))

    def stacked(hs, x):
        y, h = ssm_decode_update(hs[layers - 1], x, dt, a, b, c)
        return y, hs.at[layers - 1].set(h)

    rows = [("plain", None, (),
             lambda h, x: ssm_decode_update(h, x, dt, a, b, c)),
            ("plain.stacked", None, (layers,), stacked)]
    for hb in [int(v) for v in args.hb.split(",") if v] \
            or sorted({min(heads, 32), heads}):
        for fused in (False, True):
            rows.append((
                "pallas.fused" if fused else "pallas.copy", hb, (),
                lambda h, x, hb=hb, fused=fused: pallas_update(
                    h, x, dt, a, b, c, hb=hb, fused=fused,
                    interpret=args.rehearse)))

    results, expected = [], None
    for impl, hb, lead, update in rows:
        row = {"tag": args.tag, "impl": impl, "state": state_dt.name,
               "shape": [slots, heads, groups, p, n], "calls": calls,
               "device": dev.device_kind}
        if hb:
            row["hb"] = hb
        try:
            y, h = jax.jit(update)(fresh(lead), x)
            first = (np.asarray(y, np.float32),
                     np.asarray(h if not lead else h[-1], np.float32))
            del y, h
            if impl == "plain":
                expected = first
            if impl != "pallas.copy":        # a copy computes nothing
                row["y_max_abs_diff_vs_plain"] = float(
                    np.abs(first[0] - expected[0]).max())
                row["state_max_abs_diff_vs_plain"] = float(
                    np.abs(first[1] - expected[1]).max())
            program = looped(update)
            h = fresh(lead)
            h, _ = jax.block_until_ready(program(h, x))       # compiles
            if not args.rehearse:
                took = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    h, _ = jax.block_until_ready(program(h, x))
                    took.append((time.perf_counter() - t0) / calls)
                ms = statistics.median(took) * 1e3
                row.update(
                    host_ms_per_call=ms, host_ms_per_call_min=min(took) * 1e3,
                    required_mb_per_call=2 * state_bytes / 1e6,
                    roofline_pct=100 * 2 * state_bytes / peaks.lookup(
                        dev.device_kind)["hbm_bytes_per_s"] / (ms / 1e3))
            del h
        except Exception as e:               # a block Mosaic refuses
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
        results.append(row)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"ssm_update_probe.{args.tag}.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "seed": args.seed,
                   "rehearsal": args.rehearse, "rows": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
