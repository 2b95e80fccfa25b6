"""Size chip_smoke.py's executables for a chip that is described, not attached.

On-chip-measurement guide §2, rehearsal 3, for WHOLE programs: compile the
engine's four paged executables (dense prefill, prefill chunk, the decode
horizon, speculative verify), the donated train step and the TP=4 decode
horizon at ``llama_config_7b()`` widths for ``v5e:2x2`` from shapes alone
(``jax.eval_shape``; nothing runs, no array exists), and print what
``memory_analysis()`` says each needs on a device.  This is how the depth
cuts in ``chip_smoke.SIZES`` were chosen — the largest depth whose programs
stay inside the chip's 15.75 GiB with margin — and how to choose them again
when an executable changes.  The compiler refuses here what it would refuse
on the chip (a kernel it cannot lower, a program that does not fit), at no
chip time.  A compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python perf/chip_fit.py                 # the SIZES table
    JAX_PLATFORMS=cpu python perf/chip_fit.py serve:14 train:3 tp:8 one:4:float32:highest
    JAX_PLATFORMS=cpu python perf/chip_fit.py hybrid:0      # serve_reason_c64
    JAX_PLATFORMS=cpu python perf/chip_fit.py latent:0      # serve_longdoc_c64
    JAX_PLATFORMS=cpu python perf/chip_fit.py sambay:0      # serve_longreason_c64
    JAX_PLATFORMS=cpu python perf/chip_fit.py --dump <dir> hybrid:0

Arguments are ``phase:layers[:dtype[:matmul_precision]]`` overrides (phases:
serve, train, and tp / one — the --multichip engines on four devices / one;
``hybrid`` — the recurrent family's three executables at the benchmark
configuration ``nemotron-3-super-serve-1of4``, ``latent`` — the
latent-attention family's at ``kimi-vl-a3b-serve-1of4``, ``sambay`` —
`models/sambay.py`'s at ``phi-4-mini-flash-serve-1chip``, with the chunk that
is not a prompt's last as a fourth program; their depth is the file's: the layers argument is ignored).  ``--dump <dir>`` writes every compiled
program's text there: a device trace's event names ARE those instructions.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,  # noqa: E402
                          SingleDeviceSharding)

import chip_smoke  # noqa: E402

KIND = "TPU v5 lite"
GIB = float(1 << 30)
DUMP_DIR = None             # --dump: where `report` writes compiled texts


def report(name, compiled, t0):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: compile {time.time() - t0:.1f}s  "
          f"args {m.argument_size_in_bytes / GIB:.2f}  "
          f"temp {m.temp_size_in_bytes / GIB:.2f}  "
          f"out-alias {(m.output_size_in_bytes - m.alias_size_in_bytes) / GIB:.2f}  "
          f"=> {need / GIB:.2f} GiB/device  "
          f"tpu_custom_call x{text.count('tpu_custom_call')}  "
          f"all-reduce {'all-reduce' in text}", flush=True)
    if DUMP_DIR:
        with open(os.path.join(DUMP_DIR, "_".join(name.split()) + ".hlo.txt"),
                  "w") as f:
            f.write(text)


def placed_on(sharding):
    """tree of arrays/shapes -> the same shapes placed on ``sharding``."""
    return lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def step_programs(fam, params, cache, rep, *, slots, table, horizon, chunk,
                  bucket, packed=True):
    """{name: (jitted fn, abstract args)}: the three model calls of an
    engine step as `ServingEngine` jits them (`inference/paged.
    make_step_calls`: one packed int32 argument a call, the key split
    inside, the cache — argument 1 — donated).  ``packed=False``: the same
    calls with every field an ARGUMENT of its own, as the engine launched
    them before it packed (the horizon's fields are concatenated in the
    program, which XLA folds against the unpack's slices) — what
    `tests/test_chip_compile.py` holds the packed forms against."""
    from paddle_tpu.inference.paged import make_step_calls
    from paddle_tpu.models.llama import split_call_key
    S, P = slots, table
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    decode, prefill_sample, chunk_fn, _ = make_step_calls(fam, P)
    names = (f"decode horizon K={horizon}", f"prefill chunk C={chunk}",
             f"dense prefill T={bucket}")
    if packed:
        def decode_horizon(*a):
            return decode(*a, K=horizon, greedy=True)

        def prefill_chunk(*a):
            return chunk_fn(*a, C=chunk)

        return dict(zip(names, (
            (jax.jit(decode_horizon, donate_argnums=(1,)),
             (params, cache, key, i32((6 + P) * S), f32(2 * S))),
            (jax.jit(prefill_chunk, donate_argnums=(1,)),
             (params, cache, i32(3 + P + chunk))),
            (jax.jit(lambda *a: prefill_sample(*a, greedy=True),
                     donate_argnums=(1,)),
             (params, cache, key, i32(4 + P + bucket))))))

    def decode_horizon(params, toks, lengths, tables, cache, active, key,
                       temps, top_ps, remaining, eos_ids):
        return decode(params, cache, key, jnp.concatenate([
            toks, lengths, remaining, eos_ids, active, jnp.zeros_like(toks),
            tables.ravel()]), jnp.concatenate([temps, top_ps]), K=horizon,
            greedy=True)

    def dense_prefill(params, ids, true_len, row, slot, cache, key):
        logits, cache = fam.prefill(params, ids, true_len, row, slot, cache)
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                split_call_key(key)[0], cache)

    return dict(zip(names, (
        (jax.jit(decode_horizon, donate_argnums=(4,)),
         (params, i32(S), i32(S), i32(S, P), cache, i32(S), key, f32(S),
          f32(S), i32(S), i32(S))),
        (jax.jit(fam.prefill_chunk, donate_argnums=(6,)),
         (params, i32(1, chunk), i32(), i32(), i32(P), i32(), cache)),
        (jax.jit(dense_prefill, donate_argnums=(5,)),
         (params, i32(1, bucket), i32(), i32(P), i32(), cache, key)))))


def paged_programs(cfg, sizes, place_params, place_pages, rep, mesh=None,
                   dtype="bfloat16", packed=True):
    """Lower the engine's executables the way ServingEngine jits them."""
    from paddle_tpu.models.llama import (build_functional_llama,
                                         build_llama_paged_decode)
    S, P = sizes["num_slots"], sizes["max_pages_per_seq"]
    params = place_params(jax.eval_shape(
        lambda: build_functional_llama(cfg, dtype=dtype)[:3]))
    fam = build_llama_paged_decode(cfg, page_size=sizes["page_size"],
                                   num_pages=S * P, dtype=dtype,
                                   attention_impl="pallas", mesh=mesh)
    cache = place_pages(jax.eval_shape(fam.init_cache))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=rep)
    bucket = max(t for t in sizes["prompt_lens"]
                 if t <= sizes["prefill_chunk"])
    bucket = -(-bucket // sizes["prompt_bucket"]) * sizes["prompt_bucket"]
    return {
        **step_programs(fam, params, cache, rep, slots=S, table=P,
                        horizon=sizes["decode_horizon"],
                        chunk=sizes["prefill_chunk"], bucket=bucket,
                        packed=packed),
        f"verify Q={chip_smoke.VERIFY_Q}": (
            jax.jit(fam.verify_step, donate_argnums=(4,)),
            (params, i32(S, chip_smoke.VERIFY_Q), i32(S), i32(S, P), cache,
             i32(S))),
    }


def family_programs(conf, drv, build, place, rep, dtype="bfloat16",
                    packed=True):
    """Lower a family's three executables the way ServingEngine jits them,
    at the sizes of a benchmark configuration file ``conf`` (``drv``: the
    cell's driver module, ``build``: the family's weights from a key)."""
    cfg, e = drv.model_config(conf), conf["engine"]
    S, P = e["num_slots"], e["max_pages_per_seq"]
    params = place(jax.eval_shape(lambda: build(cfg, dtype=dtype)))
    fam = cfg.paged_family(page_size=e["page_size"], num_pages=S * P,
                           num_slots=S, max_pages_per_seq=P, dtype=dtype,
                           attention_impl="pallas")
    cache = place(jax.eval_shape(fam.init_cache))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    return {
        "weights from a seed": (
            jax.jit(lambda k: build(cfg, k, dtype)), (key,)),
        **step_programs(fam, params, cache, rep, slots=S, table=P,
                        horizon=e["decode_horizon"],
                        chunk=e["prefill_chunk"], bucket=e["prefill_chunk"],
                        packed=packed),
    }, cache


def hybrid_programs(conf, place, rep, dtype="bfloat16", **kw):
    """`family_programs` of the recurrent family (`models/nemotron_h.py`)."""
    from benchmark.drivers import serve_nemotron_h as drv
    from paddle_tpu.models.nemotron_h import build_functional_nemotron_h
    return family_programs(conf, drv, build_functional_nemotron_h, place,
                           rep, dtype, **kw)


def latent_programs(conf, place, rep, dtype="bfloat16", **kw):
    """`family_programs` of the latent-attention family
    (`models/mla_moe.py`)."""
    from benchmark.drivers import serve_mla_moe as drv
    from paddle_tpu.models.mla_moe import build_functional_mla_moe
    return family_programs(conf, drv, build_functional_mla_moe, place, rep,
                           dtype, **kw)


def sambay_programs(conf, place, rep, dtype="bfloat16", **kw):
    """`family_programs` of the SambaY family (`models/sambay.py`), and the
    chunk executable of a chunk that is NOT its prompt's last (the first
    half of the layers and the K/V layer alone)."""
    from benchmark.drivers import serve_sambay as drv
    from paddle_tpu.inference.paged import make_step_calls
    from paddle_tpu.models.sambay import build_functional_sambay
    programs, cache = family_programs(conf, drv, build_functional_sambay,
                                      place, rep, dtype, **kw)
    e = conf["engine"]
    name = f"prefill chunk C={e['prefill_chunk']}"
    fn, args = programs[name]
    fam = drv.model_config(conf).paged_family(
        page_size=e["page_size"],
        num_pages=e["num_slots"] * e["max_pages_per_seq"],
        num_slots=e["num_slots"], max_pages_per_seq=e["max_pages_per_seq"],
        dtype=dtype, attention_impl="pallas")
    chunk_fn = make_step_calls(fam, e["max_pages_per_seq"])[2]

    def prefill_chunk(*a):
        return chunk_fn(*a, C=e["prefill_chunk"], last=False)

    programs[name + " not last"] = (
        jax.jit(prefill_chunk, donate_argnums=(1,)), args)
    return programs, cache


def fit_family(topo, layers, dtype, phase="hybrid"):
    from benchmark import run as bench_run
    name, programs_of = {
        "hybrid": ("nemotron-3-super-serve-1of4", hybrid_programs),
        "latent": ("kimi-vl-a3b-serve-1of4", latent_programs),
        "sambay": ("phi-4-mini-flash-serve-1chip", sambay_programs)}[phase]
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    conf = bench_run.load_json(root, "benchmark", "configs", name + ".json")
    one = SingleDeviceSharding(topo.devices[0])
    programs, _ = programs_of(conf, placed_on(one), one, dtype=dtype)
    for name, (fn, args) in programs.items():
        t0 = time.time()
        report(f"{phase} {dtype} {name}", fn.lower(*args).compile(), t0)


def fit_one_chip(topo, layers, dtype, phase="serve"):
    sizes = chip_smoke.SIZES[KIND]["serve" if phase == "serve"
                                  else "multichip"]
    one = SingleDeviceSharding(topo.devices[0])
    place = placed_on(one)
    cfg = chip_smoke.cut_config(layers)
    for name, (fn, args) in paged_programs(cfg, sizes, place, place, one,
                                           dtype=dtype).items():
        t0 = time.time()
        report(f"{phase} {dtype} L={layers} {name}",
               fn.lower(*args).compile(), t0)


def fit_tp(topo, layers, dtype):
    from paddle_tpu.models.llama import (llama_paged_page_spec,
                                         llama_paged_param_specs)
    sizes = chip_smoke.SIZES[KIND]["multichip"]
    mesh = Mesh(topo.devices, ("mp",))
    ns = lambda spec: NamedSharding(mesh, spec)

    def place_params(tree):
        return jax.tree_util.tree_map(
            lambda spec, a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=ns(spec)),
            llama_paged_param_specs("mp"), tree,
            is_leaf=lambda s: isinstance(s, PartitionSpec))

    cfg = chip_smoke.cut_config(layers)
    for name, (fn, args) in paged_programs(cfg, sizes, place_params,
                                           placed_on(ns(
                                               llama_paged_page_spec("mp"))),
                                           ns(PartitionSpec()),
                                           mesh=mesh, dtype=dtype).items():
        t0 = time.time()
        report(f"tp=4 {dtype} L={layers} {name}",
               fn.lower(*args).compile(), t0)


def fit_train(topo, layers, dtype):
    from paddle_tpu.ops.pallas import register_all
    register_all(force=True)       # what a TPU process registers by itself
    chip_smoke.train_kernels()
    sizes = chip_smoke.SIZES[KIND]["train"]
    one = SingleDeviceSharding(topo.devices[0])
    init_state, step = chip_smoke.build_train_step(
        chip_smoke.cut_config(layers))
    state = placed_on(one)(jax.eval_shape(init_state))
    ids = jax.ShapeDtypeStruct((sizes["batch"], sizes["seq"]), jnp.int32,
                               sharding=one)
    t0 = time.time()
    compiled = jax.jit(step, donate_argnums=tuple(range(6))) \
        .lower(*state, (ids, ids)).compile()
    report(f"train L={layers} B={sizes['batch']} S={sizes['seq']}",
           compiled, t0)


def main(argv):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sizes = chip_smoke.SIZES[KIND]
    if argv[:1] == ["--dump"]:
        global DUMP_DIR
        DUMP_DIR, argv = argv[1], argv[2:]
    todo = argv or [f"serve:{sizes['serve']['layers']}",
                    f"train:{sizes['train']['layers']}"] + [
        f"{phase}:{arm['layers']}:{arm['dtype']}:"
        f"{arm.get('matmul_precision') or ''}"
        for arm in sizes["multichip"]["arms"] for phase in ("tp", "one")]
    fits = {"serve": fit_one_chip, "train": fit_train, "tp": fit_tp,
            "hybrid": fit_family,
            "latent": lambda *a: fit_family(*a, phase="latent"),
            "sambay": lambda *a: fit_family(*a, phase="sambay"),
            "one": lambda *a: fit_one_chip(*a, phase="one")}
    for item in todo:
        phase, layers, dtype, precision = (item.split(":") + ["", ""])[:4]
        with jax.default_matmul_precision(precision or None):
            if precision:
                print(f"# matmul precision {precision}:")
            fits[phase](topo, int(layers), dtype or "bfloat16")


if __name__ == "__main__":
    main(sys.argv[1:])
