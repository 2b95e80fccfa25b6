"""Do the region labels (`paddle_tpu.profiler.device_span`) reach the TPU
compile's instructions, and do they leave the executables as they were?

Compiles the five benchmark cells' main executables for a DESCRIBED v5e
(on-chip-measurement guide, section 2; nothing runs) from the checkout at
``--root`` — both donated train steps as `benchmark/drivers/train*.py`
assemble them, and the decode horizon and the prefill chunk of the three
serving configurations at their files' sizes — and writes, per executable,
the compiled text and its SIGNATURE: the multiset of (opcode, fusion kind,
result shape) over every optimized instruction, the instructions inside
fused computations counted apart.  ``--compare a.json b.json`` says where
two signatures differ.  A label is compile-time text: with the labels the
signature must be the parent's.

    JAX_PLATFORMS=cpu python perf/region_fit.py --out /root/scratch/fit/change
    JAX_PLATFORMS=cpu python perf/region_fit.py --root <parent checkout> \
        --out /root/scratch/fit/parent [train_dense train_afmoe chat hybrid latent]
    python perf/region_fit.py --compare /root/scratch/fit/parent/signatures.json \
        /root/scratch/fit/change/signatures.json
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ITEMS = ("train_dense", "train_afmoe", "chat", "hybrid", "latent")
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<shape>\([^=]*?\)|\S+?)(?:\{[^ ]*)? "
    r"(?P<op>[a-z][\w\-]*)\(", re.M)
_LAYOUT = re.compile(r"\{[^{}]*\}")
REGION = re.compile(r'pt_region="([^"]+)"')


def one_line(text):
    """The compiled text with each instruction on ONE line: jax writes a
    kernel's `kernel_metadata` JSON with newlines inside the instruction."""
    return re.sub(r'\n("|\}[,}])', r"\1", text)


def _instructions(text):
    """(in a fused computation?, the line, its `_INSTR` match) of every
    instruction of a compiled text."""
    fused = False
    for line in one_line(text).splitlines():
        if line.startswith("ENTRY") or (line.startswith("%")
                                        and line.rstrip().endswith("{")):
            fused = line.startswith("%fused_computation")
            continue
        m = _INSTR.match(line)
        if m:
            yield fused, line, m


def signature(text):
    """{"entry": {key: count}, "fused": {key: count}}: key is ``opcode
    [kind] shape`` with layouts dropped (kind: a fusion's, a custom call's
    target); "fused" holds the instructions inside `%fused_computation*`
    bodies, "entry" every other one."""
    out = {"entry": collections.Counter(), "fused": collections.Counter()}
    kinds = {"fusion": r"kind=(k\w+)",
             "custom-call": r'custom_call_target="(\w+)"'}
    for fused, line, m in _instructions(text):
        kind = re.search(kinds[m["op"]], line) if m["op"] in kinds else None
        out["fused" if fused else "entry"][" ".join(filter(None, (
            m["op"], kind and kind[1], _LAYOUT.sub("", m["shape"]))))] += 1
    return {k: dict(sorted(v.items())) for k, v in out.items()}


# opcodes that only route values, and XLA's own prefetches into fast memory
# (which tensors it stages there moves with any change to a program)
ROUTING = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
           "reshape", "slice", "copy", "copy-start", "copy-done",
           "slice-start", "slice-done", "opt-barrier"}
_SMALL = re.compile(r"\b(?:s32|u32|pred)\[([\d,]*)\]")


def work_signature(text, small):
    """`signature` less what changing how a call's HOST STATE arrives may
    change: the instructions that only route values (`ROUTING`, and the
    `ConcatBitcast` that joins a sliced prefetch) and those whose every
    result is an integer or predicate array of at most ``small`` elements
    (slices of a packed buffer, the words of a key split).  What is left
    is the work: two programs that differ only in how their per-call
    fields arrive agree on it, entry and fused."""
    def routed(key):
        op, _, shape = key.partition(" ")
        if op in ROUTING or key.startswith("custom-call ConcatBitcast "):
            return True
        shapes = re.findall(r"\b[a-z]+\d*\[[\d,]*\]", shape)
        return bool(shapes) and all(
            (m := _SMALL.fullmatch(one)) is not None
            and math.prod(int(n) for n in m[1].split(",") if n) <= small
            for one in shapes)

    return {part: {k: v for k, v in kinds.items() if not routed(k)}
            for part, kinds in signature(text).items()}


def labelled(text):
    """{region: instructions outside fused computations that carry it},
    plus how many fusions carry one and how many do not."""
    regions, fusions, bare = collections.Counter(), 0, 0
    for fused, line, m in _instructions(text):
        if fused:
            continue
        r = REGION.search(line)
        if r:
            regions[r[1]] += 1
        if m["op"] == "fusion":
            fusions += 1
            bare += r is None
    return {"regions": dict(sorted(regions.items())), "fusions": fusions,
            "fusions_unlabelled": bare}


def programs(item, one):
    """{name: (jitted fn, abstract args)} of one cell's main executables."""
    import jax
    import jax.numpy as jnp
    import chip_fit
    from benchmark import run as bench_run
    root = bench_run.HERE + "/.."
    conf_of = lambda name: bench_run.load_json(root, "benchmark", "configs",
                                               name + ".json")
    traffic_of = lambda name: bench_run.load_json(root, "benchmark",
                                                  "traffic", name + ".json")
    place = chip_fit.placed_on(one)
    if item in ("train_dense", "train_afmoe"):
        from paddle_tpu.ops.pallas import register_all
        register_all(force=True)     # what a TPU process registers by itself
        if item == "train_dense":
            from benchmark.drivers import serve, train
            from paddle_tpu.models.llama import build_functional_llama
            conf, mix = conf_of("mistral-7b-train-1chip"), \
                traffic_of("pretrain_b2_s2048")
            cfg = serve.model_config(conf)
            init_opt, step = train.build_step(cfg, conf.get("step", {}))
            params = jax.eval_shape(lambda: build_functional_llama(
                cfg, dtype=jnp.bfloat16)[:3])
        else:
            from benchmark.drivers import train_afmoe
            from paddle_tpu.models.afmoe import build_functional_afmoe
            conf, mix = conf_of("trinity-mini-train-1of8"), \
                traffic_of("pretrain_b1_s8192")
            cfg, held = train_afmoe.model_config(conf)
            init_opt, step = train_afmoe.build_step(cfg, held,
                                                    conf.get("step", {}))
            params = jax.eval_shape(lambda: build_functional_afmoe(
                cfg, key=jax.random.PRNGKey(0), dtype=jnp.bfloat16,
                experts_held=held)[:3])
        state = place(tuple(params) + tuple(jax.eval_shape(init_opt,
                                                           *params)))
        ids = jax.ShapeDtypeStruct((int(mix["batch"]), int(mix["seq"])),
                                   jnp.int32, sharding=one)
        return {"train step": (jax.jit(step, donate_argnums=tuple(range(6))),
                               (*state, (ids, ids)))}
    if item == "chat":
        from benchmark.drivers import serve
        conf = conf_of("mistral-7b-serve-1chip")
        sizes = dict(conf["engine"], prompt_lens=[conf["engine"]
                                                  ["prefill_chunk"]])
        got = chip_fit.paged_programs(serve.model_config(conf), sizes, place,
                                      place, one)
    else:
        name, of = {"hybrid": ("nemotron-3-super-serve-1of4",
                               chip_fit.hybrid_programs),
                    "latent": ("kimi-vl-a3b-serve-1of4",
                               chip_fit.latent_programs)}[item]
        got, _ = of(conf_of(name), place, one)
    return {k: v for k, v in got.items()
            if k.startswith(("decode horizon", "prefill chunk"))}


def compare(a_path, b_path):
    a, b = (json.load(open(p)) for p in (a_path, b_path))
    same = True
    for name in sorted(set(a) ^ set(b)):
        print(f"{name}: only in {a_path if name in a else b_path}")
    for name in sorted(set(a) & set(b)):
        for part in ("entry", "fused"):
            x = collections.Counter(a.get(name, {}).get(part, {}))
            y = collections.Counter(b.get(name, {}).get(part, {}))
            only_a, only_b = x - y, y - x
            n = sum(x.values())
            if only_a or only_b:
                same = False
                print(f"{name} [{part}]: DIFFERS ({n} vs {sum(y.values())})")
                for k, v in sorted(only_a.items()):
                    print(f"   - {v} x {k}")
                for k, v in sorted(only_b.items()):
                    print(f"   + {v} x {k}")
            else:
                print(f"{name} [{part}]: the same {n} instructions, "
                      f"{len(x)} kinds")
    return 0 if same else 1


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    ap.add_argument("--out")
    ap.add_argument("--compare", nargs=2)
    ap.add_argument("items", nargs="*", default=list(ITEMS))
    args = ap.parse_args(argv)
    if args.compare:
        return compare(*args.compare)
    root = os.path.abspath(args.root)
    sys.path[:0] = [root, os.path.join(root, "perf")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    os.makedirs(args.out, exist_ok=True)
    sig_path = os.path.join(args.out, "signatures.json")
    sigs = json.load(open(sig_path)) if os.path.exists(sig_path) else {}
    for item in args.items:
        for name, (fn, a) in programs(item, one).items():
            t0 = time.time()
            text = fn.lower(*a).compile().as_text()
            key = f"{item} {name}"
            with open(os.path.join(args.out, "_".join(key.split())
                                   + ".hlo.txt"), "w") as f:
                f.write(text)
            sigs[key] = signature(text)
            print(json.dumps({"program": key, "compile_s":
                              round(time.time() - t0, 1),
                              **labelled(text)}), flush=True)
            with open(sig_path, "w") as f:
                json.dump(sigs, f, indent=0, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
