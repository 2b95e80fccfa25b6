"""The serving driver: one ``ServingEngine`` built from a configuration file,
driven by one thread with the load a traffic file describes — a closed loop
(``clients``: each caller sends its next request the instant its last reply
finished) or an open loop (``rate_rps``: requests sent on a schedule whatever
the engine does, each timed from the instant it was DUE).

The program is used only through what a user calls: ``LlamaConfig``,
``build_functional_llama`` (the weights), ``ServingEngine`` with ``submit`` /
``step`` / ``lookup`` / ``stats`` / ``check_invariants``, and the public
timestamps of ``Request``.  Every ``build_*`` function takes keyword
overrides so that benchmark/tests can rehearse the same path at a tiny size
on the CPU with the kernels interpreted; ``run.py`` passes none.
"""
import dataclasses
import sys
import time

import numpy as np

from benchmark import reference, trace_reduce
from benchmark.readers import percentile
from benchmark import traffic as traffic_gen

CHECK_TOKENS = 8            # greedy tokens each reference-check prompt makes


def say(msg):
    """A line for the reader of the log; never the last line of stdout."""
    print(msg, file=sys.stderr, flush=True)


def model_config(conf):
    """The program's config object from a configuration file's public
    ``config.json`` keys.  A key the shared Llama-shaped path cannot express
    is refused, not ignored."""
    from paddle_tpu.models.llama import LlamaConfig
    names = {f.name for f in dataclasses.fields(LlamaConfig)}
    cfg = dataclasses.replace(
        LlamaConfig(), **{k: v for k, v in conf.items()
                          if k in names})
    m = conf
    if m.get("sliding_window") is not None \
            or m.get("hidden_act", "silu") != "silu" \
            or m.get("head_dim", cfg.hidden_size // cfg.num_attention_heads) \
            != cfg.hidden_size // cfg.num_attention_heads:
        raise ValueError("configuration asks for a sliding window, another "
                         "activation or a head size the path does not have")
    return cfg


def build_params(cfg, seed, dtype):
    """The weights, made on the device in ONE jitted call from the seed, in
    the type they are served in."""
    import jax
    from paddle_tpu.models.llama import build_functional_llama
    make = jax.jit(lambda key: build_functional_llama(cfg, key=key,
                                                      dtype=dtype)[:3])
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def build_engine(params, cfg, conf, devices, **overrides):
    from paddle_tpu.inference.paged import ServingEngine
    mesh = None
    if conf.get("mesh"):
        from paddle_tpu.distributed.topology import build_mesh
        n = int(np.prod(list(conf["mesh"].values())))
        mesh = build_mesh(conf["mesh"], devices=devices[:n])
    return ServingEngine(params, cfg, dtype=params[0]["tok"].dtype, mesh=mesh,
                         **{**conf["engine"], **overrides})


class Load:
    """Submits what is due, steps the engine, collects what finished."""

    def __init__(self, eng, traffic, vocab_size, seed):
        self.eng = eng
        self.source = traffic_gen.requests(traffic, vocab_size, seed)
        self.clients = traffic.get("clients")
        self.open, self.done, self.refused = {}, [], 0
        self.lateness = []
        now = time.perf_counter()
        if self.clients:
            self.idle = [now] * int(self.clients)     # due times
        else:
            self.gaps = traffic_gen.arrival_gaps(traffic, seed)
            self.next_due = now + next(self.gaps)

    def submit(self, prompt, max_new, due):
        from paddle_tpu.inference.paged import (AdmissionRejected,
                                                PoolCapacityError)
        try:
            rid = self.eng.submit(prompt, max_new_tokens=max_new)
        except (AdmissionRejected, PoolCapacityError) as e:
            self.refused += 1
            say(f"refused: {e}")
            return
        self.open[rid] = {"rid": rid, "due": due, "want": max_new,
                          "prompt_len": len(prompt)}

    def step(self):
        now = time.perf_counter()
        if self.clients:
            while self.idle:
                self.submit(*next(self.source), self.idle.pop())
        else:
            while self.next_due <= now:
                self.lateness.append(now - self.next_due)
                self.submit(*next(self.source), self.next_due)
                self.next_due += next(self.gaps)
            if not self.open:               # nothing to serve until then
                time.sleep(max(self.next_due - time.perf_counter(), 0))
                return
        self.eng.step()
        for rid in list(self.open):
            r = self.eng.lookup(rid)
            if r.finish_time:
                self.done.append(self._close(self.open.pop(rid), r))
                if self.clients:
                    self.idle.append(r.finish_time)

    def _close(self, rec, r):
        n = len(r.generated)
        rec.update(
            submit=r.submit_time, admit=r.admit_time,
            first=r.first_token_time, finish=r.finish_time, tokens=n,
            ok=(not r.timed_out and n == rec["want"]
                and all(0 <= t < self.eng.config.vocab_size
                        for t in r.generated)),
            generated=r.generated,
            ttft_s=r.first_token_time - rec["due"],
            queue_s=r.admit_time - rec["due"],
            tpot_s=(r.finish_time - r.first_token_time) / (n - 1)
            if n > 1 else None)
        return rec

    def run_until(self, stop):
        while not stop():
            self.step()

    def first_tokens_between(self, t0, t1):
        """Requests whose FIRST token (which prefill makes, not decode) fell
        in [t0, t1], finished or not."""
        firsts = [r["first"] for r in self.done] + \
            [self.eng.lookup(rid).first_token_time for rid in self.open]
        return sum(1 for f in firsts if f and t0 <= f <= t1)


def warm_up_and_check(eng, params, cfg, conf, traffic, seed,
                      layer_order=None):
    """Run every executable shape the mix can produce once, together with
    the four reference-check prompts, then hold the engine's tokens to the
    plain reference.  Returns (ok, facts)."""
    eng_conf = conf["engine"]
    bucket, chunk = eng_conf["prompt_bucket"], eng_conf["prefill_chunk"]
    rng = np.random.default_rng(seed + 2)
    # two prompts through dense prefill, two through chunked prefill; the
    # lengths come from the seed but stay in one padding band each, so every
    # seed uses the same executables
    check_lens = [int(rng.integers(top - bucket + 1, top + 1)) for top in
                  (chunk // 2, chunk, chunk + bucket, chunk + chunk // 2)]
    lens = check_lens + traffic_gen.warmup_lengths(traffic, bucket)
    prompts = [rng.integers(1, cfg.vocab_size, (t,)).astype(np.int32)
               for t in lens]
    # K decode steps follow the first token: the horizon compiles here too
    rids = [eng.submit(p, max_new_tokens=CHECK_TOKENS) for p in prompts]
    done = eng.run()
    gaps = []
    pad = chunk + chunk // 2 + CHECK_TOKENS      # one shape for every seed
    for rid, prompt in zip(rids[:4], prompts[:4]):
        gaps += reference.generation_gaps(
            params, conf, prompt, done[rid].generated, pad_to=pad,
            layer_order=layer_order)
    worst = max(gaps)
    return worst <= reference.SERVE_LOGIT_DELTA, {
        "check_prompt_lens": check_lens, "check_positions": len(gaps),
        "worst_logit_gap": worst, "mean_logit_gap": float(np.mean(gaps)),
        "delta": reference.SERVE_LOGIT_DELTA, "warmup_prompt_lens": lens[4:]}


def measure(load, seconds, trace_seconds=0.0):
    """Drive the steady loop for ``seconds``; with ``trace_seconds`` the
    profiler records the LAST that many seconds of the window.  Returns the
    facts of the window (and the trace)."""
    eng = load.eng
    clock = time.perf_counter
    rec, st = None, None
    t0, s0 = clock(), eng.stats()
    while clock() < t0 + seconds:
        if trace_seconds and rec is None \
                and clock() >= t0 + seconds - trace_seconds:
            rec, st = trace_reduce.Recording(), eng.stats()
            rec.start()              # the engine is synchronous: device idle
        load.step()
    t1, s1 = clock(), eng.stats()
    trace = rec.stop(t1) if rec is not None else None
    in_window = [r for r in load.done if r["submit"] >= t0]
    good = [r for r in in_window if r["ok"]]
    facts = {
        "window_s": t1 - t0,
        "tokens_generated": s1["tokens_generated"] - s0["tokens_generated"],
        "decode_steps": s1["decode_steps"] - s0["decode_steps"],
        "prefill_tokens": s1["prefill_tokens_executed"]
        - s0["prefill_tokens_executed"],
        "first_tokens": load.first_tokens_between(t0, t1),
        "requests_finished": len(in_window),
        "in_flight_at_end": len(load.open),
        "compiled_in_window": sum(s1["jit_cache_misses"].values())
        - sum(s0["jit_cache_misses"].values()),
        "preemptions": s1["preemptions"] - s0["preemptions"],
    }
    facts["decode_tokens"] = facts["tokens_generated"] - facts["first_tokens"]
    if st is not None:
        facts["traced.decode_steps"] = s1["decode_steps"] - st["decode_steps"]
        facts["traced.prefill_tokens"] = s1["prefill_tokens_executed"] \
            - st["prefill_tokens_executed"]
    return facts, in_window, good, trace


def run(conf, traffic, seed, seconds, trace, t_start, devices, peak,
        layer_order=None, **overrides):
    """One run of one serve cell.  Returns the dict ``run.py`` prints from.
    ``layer_order`` hands the REFERENCE a wrong model (tests, PERF.md)."""
    import jax.numpy as jnp
    cfg = model_config(conf)
    say(f"imports and devices: {time.perf_counter() - t_start:.1f}s")
    params = build_params(cfg, seed, jnp.dtype(conf["torch_dtype"]))
    say(f"weights: {time.perf_counter() - t_start:.1f}s")
    eng = build_engine(params, cfg, conf, devices, **overrides)
    say(f"engine: {time.perf_counter() - t_start:.1f}s")
    ref_ok, check = warm_up_and_check(eng, params, cfg, conf, traffic, seed,
                                      layer_order)
    say(f"reference check: {check}")
    say(f"warm-up and check: {time.perf_counter() - t_start:.1f}s")
    load = Load(eng, traffic, cfg.vocab_size, seed)
    ramp = int(traffic.get("clients") or conf["engine"]["num_slots"])
    load.run_until(lambda: len(load.done) >= ramp)      # the ramp
    setup_s = time.perf_counter() - t_start
    say(f"ramp done, window starts: {setup_s:.1f}s")

    facts, in_window, good, tr = measure(
        load, seconds, float(traffic.get("trace_seconds", 8)) if trace else 0)
    try:
        eng.check_invariants()
        invariants = True
    except AssertionError as e:
        say(f"check_invariants failed: {e}")
        invariants = False
    say(f"window: {facts}")
    say(f"samples: {len(good)} requests behind the percentiles")
    if load.lateness:
        say(f"generator lateness: mean {np.mean(load.lateness):.4f}s "
            f"max {max(load.lateness):.4f}s over {len(load.lateness)}")
    e2e = {"setup_s": setup_s,
           "out_tok_s": facts["tokens_generated"] / facts["window_s"]}
    if len(good) >= 2:
        e2e["ttft_p90_ms"] = 1e3 * percentile([r["ttft_s"] for r in good], 90)
        e2e["tpot_p90_ms"] = 1e3 * percentile([r["tpot_s"] for r in good
                                         if r["tpot_s"] is not None], 90)
    facts.update(e2e)
    facts.update({k: v for k, v in conf["engine"].items()
                  if isinstance(v, (int, float))})
    facts["peak_flops"] = peak["flops_bf16"]
    return {
        "correct": bool(ref_ok and invariants
                        and facts["compiled_in_window"] == 0),
        "attempted": len(in_window) + load.refused,
        "failed": load.refused + sum(1 for r in in_window if not r["ok"]),
        "end_to_end": e2e, "facts": facts, "requests": good, "trace": tr,
        "check": check,
    }
