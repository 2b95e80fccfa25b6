"""One upload and one launch for every model call of an engine step.

A model call of `ServingEngine.step()` — a dense prefill, a prefill chunk,
the decode horizon — is ONE executable fed by ONE packed host->device array
(two where an admission changed the horizon's sampling row); the PRNG key is
split inside the executables.  Nothing else may reach the device from
`step()`: on the chip an upload that is an argument of its own costs the
device ~0.3 ms of waiting and a `jax.numpy` operation on the host — a key
split, a slice of its result, a zero fill, a merge — is an executable of its
own, ~0.5 ms (PERF.md section 6, PR 38).

The engine reaches jax through two handles, `_jnp` / `_jax`, and launches
through the jitted callables it keeps.  These tests put a counting proxy
over all of them after a warm-up and hold the counts against what the
engine says of itself: `stats()["step_launches"]` / `["step_uploads"]` and
the spans' `launches=` / `uploads=`.

The second half holds the KEY to what it always was.  Every executable that
draws — the horizon, the dense prefill with its fused first sample, the
single-logits sampler — takes the engine's key, splits it INSIDE
(`models/llama.split_call_key`: one split a call, greedy or not) and returns
the next key, which the engine rebinds as it rebinds the cache; before PR 38
the host split the key with executables of its own.  Where the split happens
must not show: the keys the calls receive are the sequential
`jax.random.split` chain from the seed, the subkey a call draws with is that
split's second row, and the sampled tokens of a mixed greedy / sampled run
are the literals RECORDED FROM THE PARENT COMMIT (`b93310d`, this container's
CPU) — through a snapshot and a restore too.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.paged import (ServingEngine, make_step_calls,
                                        pack_prefill)
from paddle_tpu.models.llama import (_sample_per_request, pack_decode_state,
                                     split_call_key)

# a handle's calls that make a host->device array ...
UPLOADS = {"jax.device_put", "jnp.asarray", "jnp.array"}
# ... and those that touch no device (anything else on a handle is an
# operation dispatched from the host: an executable of its own)
HOST_ONLY = ("jax.tree_util.", "jax.profiler.")
MODEL_CALLS = ("prefill_dense", "prefill_chunk", "decode_dispatch",
               "overlap_dispatch")


class Counted:
    """What crossed the proxies: ``uploads`` / ``eager`` (names of handle
    calls), ``execs`` (names of the jitted callables called), ``jits`` (new
    jitted callables made) and ``spans`` ((name, depth, stats) in the order
    they closed)."""

    def __init__(self):
        self.uploads, self.eager, self.execs, self.jits = [], [], [], []
        self.spans, self._open = [], []

    def annotation(self):
        counted = self

        class Annotation:
            """Stands in for `jax.profiler.TraceAnnotation`."""

            def __init__(self, name, **stats):
                self.name, self.stats = name, dict(stats)

            def set_metadata(self, **stats):
                self.stats.update(stats)

            def __enter__(self):
                counted._open.append(self)
                return self

            def __exit__(self, *exc):
                counted._open.pop()
                counted.spans.append((self.name.removeprefix("serve."),
                                      len(counted._open), self.stats))

        return Annotation


class Handle:
    """A module handle of the engine with every call on it counted."""

    def __init__(self, module, path, counted):
        self._module, self._path, self._counted = module, path, counted

    def __getattr__(self, name):
        value = getattr(self._module, name)
        path = f"{self._path}.{name}"
        if path == "jax.profiler.TraceAnnotation":
            return self._counted.annotation()
        if isinstance(value, types.ModuleType):
            return Handle(value, path, self._counted)
        if not callable(value) or isinstance(value, type):
            return value

        def call(*a, **kw):
            if path == "jax.jit":
                self._counted.jits.append(getattr(a[0], "__name__", "?"))
            elif path in UPLOADS:
                self._counted.uploads.append(path)
            elif not path.startswith(HOST_ONLY):
                self._counted.eager.append(path)
            return value(*a, **kw)

        return call


class Executable:
    """A jitted callable of the engine with every call counted."""

    def __init__(self, fn, name, counted):
        self._fn, self._name, self._counted = fn, name, counted

    def __call__(self, *a, **kw):
        self._counted.execs.append(self._name)
        return self._fn(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def count(eng):
    """Put the proxies over ``eng``'s handles and the jitted callables it
    has made so far (a warm engine makes no more)."""
    counted = Counted()
    eng._jax = Handle(jax, "jax", counted)
    eng._jnp = Handle(jnp, "jnp", counted)
    for name, table in (("horizon", eng._horizon_jit),
                        ("dense", eng._prefill_jit)):
        for k, fn in table.items():
            table[k] = Executable(fn, name, counted)
    for name, attr in (("chunk", "_chunk_jit"), ("sample", "_sample_jit"),
                       ("copy", "_copy_jit"), ("verify", "_verify_jit")):
        if getattr(eng, attr) is not None:
            setattr(eng, attr, Executable(getattr(eng, attr), name, counted))
    return counted


SEED = 7


@pytest.fixture(scope="module")
def models():
    from paddle_tpu.models.llama import (build_functional_llama,
                                         llama_config_tiny)
    from paddle_tpu.models.mla_moe import (build_functional_mla_moe,
                                           mla_moe_config_tiny)
    from paddle_tpu.models.nemotron_h import (build_functional_nemotron_h,
                                              nemotron_h_config_tiny)
    key = jax.random.PRNGKey(5)
    llama = llama_config_tiny(vocab=256, hidden=64, layers=2, heads=4,
                              seq=512)
    hybrid = nemotron_h_config_tiny(experts_held=(4, 8))
    latent = mla_moe_config_tiny(experts_held=(4, 8))
    return {
        "llama": (llama, build_functional_llama(
            llama, key=key, dtype=jnp.float32)[:3]),
        "nemotron_h": (hybrid, jax.jit(lambda k: build_functional_nemotron_h(
            hybrid, k, jnp.float32))(key)),
        "mla_moe": (latent, jax.jit(lambda k: build_functional_mla_moe(
            latent, k, jnp.float32))(key))}


def engine(model, **kw):
    cfg, params = model
    kw = {"num_slots": 3, "page_size": 4, "max_pages_per_seq": 16,
          "dtype": jnp.float32, "attention_impl": "ref", "prompt_bucket": 8,
          "prefill_chunk": 16, "decode_horizon": 4, "seed": SEED, **kw}
    return ServingEngine(params, cfg, **kw)


# (prompt length, tokens asked for): dense prefills of two buckets, chunked
# prefills of two and three chunks, more requests than slots
LENGTHS = [(5, 6), (21, 7), (12, 5), (37, 8), (7, 10), (18, 4)]


def submit(eng, cfg, seed, sampled=()):
    """The mix with fresh random prompts (another seed: no prefix hit, so
    no copy-on-write); the requests in ``sampled`` draw from a nucleus."""
    rng = np.random.default_rng(seed)
    return [eng.submit(rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32),
                       max_new_tokens=m,
                       **({"temperature": 0.8, "top_p": 0.9}
                          if i in sampled else {}))
            for i, (n, m) in enumerate(LENGTHS)]


def warm_and_count(model, sampled=(), **kw):
    """An engine that has compiled every shape of the mix, the proxies over
    it, and its `stats()` as the counted run starts."""
    eng = engine(model, **kw)
    submit(eng, model[0], seed=1, sampled=sampled)
    eng.run()
    counted = count(eng)
    return eng, counted, eng.stats()


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
@pytest.mark.parametrize("family", ["llama", "nemotron_h", "mla_moe"])
def test_a_model_call_is_one_launch_and_one_upload(models, family, overlap):
    """k dense admissions, c chunks and h horizons launch exactly k + c + h
    executables and nothing else; every call makes ONE upload (greedy
    traffic never changes the sampling row); the engine's counters and its
    spans say the same."""
    model = models[family]
    eng, counted, before = warm_and_count(model, overlap=overlap)
    submit(eng, model[0], seed=2)
    done = eng.run()
    assert all(len(done[r].generated) == m
               for r, (_, m) in zip(sorted(done)[-len(LENGTHS):], LENGTHS))
    after = eng.stats()
    k, c, h = (counted.execs.count(name)
               for name in ("dense", "chunk", "horizon"))
    assert k >= 3 and c >= 5 and h >= 4, counted.execs
    # nothing but the model calls reached the device: no executable of
    # `jax.random.split`, of a key slice, of a zero fill or of a merge, no
    # sampler, no page copy, no executable made anew
    assert len(counted.execs) == k + c + h, counted.execs
    assert counted.eager == [] and counted.jits == []
    assert counted.uploads == ["jax.device_put"] * (k + c + h)
    assert after["decode_steps"] - before["decode_steps"] == h
    # ... the counters ...
    assert after["step_launches"] - before["step_launches"] == k + c + h
    assert after["step_uploads"] - before["step_uploads"] == k + c + h
    # ... and the spans: one launch and one upload under every model call's
    # span, and a step's span holds the sum of the calls under it
    calls = [(name, stats) for name, _, stats in counted.spans
             if name in MODEL_CALLS]
    assert len(calls) == k + c + h
    assert all(stats["launches"] == 1 and stats["uploads"] == 1
               for _, stats in calls), calls
    assert sum(name == "prefill_dense" for name, _ in calls) == k
    assert sum(name == "prefill_chunk" for name, _ in calls) == c
    assert all(name != ("decode_dispatch" if overlap else "overlap_dispatch")
               for name, _ in calls)
    steps = [stats.get("launches", 0) for name, depth, stats in counted.spans
             if name == "step" and depth == 0]
    assert sum(steps) == k + c + h
    # a step with k' admissions, c' chunks and one horizon: k' + c' + 1
    under = 0
    for name, depth, stats in counted.spans:
        if name in MODEL_CALLS:
            under += 1
        elif name == "step" and depth == 0:
            assert stats.get("launches", 0) == under
            assert stats.get("uploads", 0) == under
            under = 0


@pytest.mark.parametrize("family,overlap", [
    ("llama", False), ("llama", True), ("nemotron_h", True),
    ("mla_moe", False)])
def test_sampled_traffic_adds_the_sampler_and_a_row_and_nothing_else(
        models, family, overlap):
    """With requests that draw: a sampled first token after a CHUNKED
    prefill is the one further executable (the chunk serves every request,
    so it cannot sample), with one upload; a dispatch uploads the sampling
    row again only after an admission changed it — at most two uploads a
    call, always; still no executable of a split, a slice or a fill."""
    model = models[family]
    sampled = {0, 1, 3}             # a dense admission and two chunked ones
    eng, counted, before = warm_and_count(model, sampled=sampled,
                                          overlap=overlap)
    submit(eng, model[0], seed=2, sampled=sampled)
    eng.run()
    after = eng.stats()
    k, c, h, draws = (counted.execs.count(name)
                      for name in ("dense", "chunk", "horizon", "sample"))
    assert draws == 2 and len(counted.execs) == k + c + h + draws
    assert counted.eager == [] and counted.jits == []
    rows = len(counted.uploads) - (k + c + h + draws)
    # the row went up again after some admissions, never twice a dispatch
    assert 1 <= rows <= min(h, len(LENGTHS))
    assert after["step_launches"] - before["step_launches"] \
        == len(counted.execs)
    assert after["step_uploads"] - before["step_uploads"] \
        == len(counted.uploads)
    for name, _, stats in counted.spans:
        if name in ("decode_dispatch", "overlap_dispatch"):
            assert stats["launches"] == 1 and stats["uploads"] in (1, 2)
        elif name in ("prefill_dense", "prefill_chunk"):
            # (the sampler after a final chunk runs under no span of its
            # own: the step's span has it)
            assert stats["launches"] == 1 and stats["uploads"] == 1


def test_a_copy_on_write_and_a_verify_are_counted_as_what_they_launch(
        models):
    """The two executables of `step()` that are not model calls of a cell:
    a copy-on-write page copy (one launch, one packed ``src | dst``
    upload) and a speculative verify (one launch; it keeps an upload a
    field, four) — counted, so launches over model calls reads above 1
    exactly where they ran."""
    model = models["llama"]
    eng = engine(model, speculative=2)

    def two_turns(first):
        """A repetitive prompt (the n-gram index drafts from it), then a
        follow-up that embeds the first turn: it attaches the retired
        whole pages and, through a COPY, the partly filled last one."""
        r1 = eng.submit(np.tile(np.arange(first, first + 5, dtype=np.int32),
                                2), max_new_tokens=5)
        turn1 = eng.run()[r1].output_ids
        assert (len(turn1) - 1) % eng.page_size       # a partial last page
        eng.submit(np.concatenate([turn1, np.arange(40, 47, dtype=np.int32)]),
                   max_new_tokens=5)
        eng.run()

    two_turns(1)
    counted, before = count(eng), eng.stats()
    two_turns(11)
    after = eng.stats()
    assert after["cow_copies"] - before["cow_copies"] == 1
    verifies = after["verify_steps"] - before["verify_steps"]
    assert verifies >= 1 and counted.execs.count("verify") == verifies
    assert counted.execs.count("copy") == 1
    assert counted.eager == [] and counted.jits == []
    assert after["step_launches"] - before["step_launches"] \
        == len(counted.execs)
    assert after["step_uploads"] - before["step_uploads"] \
        == len(counted.uploads)
    assert [stats["uploads"] for name, _, stats in counted.spans
            if name == "verify_dispatch"] == [4] * verifies


# ---------------------------------------------------------------------------
# the key stream
# ---------------------------------------------------------------------------
# (prompt length, tokens asked for, temperature, top_p): greedy and sampled
# requests through dense and chunked prefill, more of them than slots
MIX = [(5, 6, 0.0, 1.0), (9, 9, 0.8, 0.9), (21, 7, 1.0, 0.95),
       (12, 5, 0.0, 1.0), (30, 8, 0.7, 1.0), (7, 10, 1.3, 0.8),
       (18, 4, 0.0, 1.0)]

# `generated` of the mix's requests at the parent commit, engine seed 7,
# prompts from `default_rng(3)`, weights from `PRNGKey(5)`, float32: the
# same with `overlap` off and on.  "restored": the same requests after
# three steps, a snapshot and a restore into a fresh engine, where that
# differs (the recurrent family snapshots "compact" and prefills again,
# which draws keys of its own).
PARENT = {
    "llama": {"tokens": [
        [14, 154, 154, 82, 14, 14],
        [117, 170, 141, 246, 112, 125, 135, 85, 97],
        [250, 182, 79, 105, 206, 160, 17], [61, 41, 81, 61, 41],
        [110, 209, 163, 190, 89, 104, 211, 120],
        [22, 80, 222, 223, 179, 13, 64, 159, 203, 64],
        [217, 121, 43, 121]]},
    "nemotron_h": {"tokens": [
        [67, 138, 46, 174, 153, 114],
        [117, 199, 141, 246, 112, 125, 135, 85, 97],
        [108, 182, 79, 87, 206, 160, 17], [219, 12, 76, 81, 219],
        [110, 77, 163, 190, 21, 104, 211, 120],
        [22, 80, 190, 114, 179, 13, 64, 20, 134, 47],
        [19, 137, 236, 102]],
        "restored": [
        [67, 138, 46, 174, 153, 114],
        [117, 199, 141, 246, 112, 125, 135, 85, 97],
        [108, 182, 79, 87, 206, 160, 17], [219, 12, 76, 81, 219],
        [11, 13, 64, 20, 134, 47, 70, 146],
        [22, 197, 116, 189, 138, 128, 211, 199, 60, 112],
        [19, 137, 236, 102]]},
    "mla_moe": {"tokens": [
        [234, 207, 86, 201, 226, 70],
        [117, 199, 84, 28, 112, 125, 135, 85, 97],
        [250, 182, 79, 87, 206, 114, 17], [174, 61, 33, 33, 33],
        [30, 209, 163, 190, 126, 104, 211, 120],
        [191, 80, 222, 223, 179, 91, 64, 20, 134, 64],
        [129, 129, 85, 225]]},
}
# the engine's key after the run, at the parent, in all six cases
PARENT_LAST_KEY = [1882804955, 1173222465]


def submit_mix(eng):
    rng = np.random.default_rng(3)
    return [eng.submit(
        rng.integers(1, eng.config.vocab_size, (n,)).astype(np.int32),
        max_new_tokens=m, temperature=t, top_p=p) for n, m, t, p in MIX]


def keys_received(eng):
    """Record the key every drawing call of ``eng`` is handed, in the order
    the calls are made (the horizon and the dense prefill through
    `_call_paged(keyed=True)`, the sampler through its jitted callable)."""
    seen = []
    call_paged = eng._call_paged

    def paged(fn, *a, keyed=False, **kw):
        if keyed:
            seen.append(a[2])            # (params, cache, key, ...)
        return call_paged(fn, *a, keyed=keyed, **kw)

    eng._call_paged = paged
    eng._sample_jit = eng._jit("sample", eng._sample_fn)
    sampler = eng._sample_jit

    def sample(logits, key, row):
        seen.append(key)
        return sampler(logits, key, row)

    eng._sample_jit = sample
    return seen


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
@pytest.mark.parametrize("family", list(PARENT))
def test_a_mixed_run_draws_the_parents_tokens_from_the_sequential_split(
        models, family, overlap):
    eng = engine(models[family], overlap=overlap)
    seen = keys_received(eng)
    rids = submit_mix(eng)
    done = eng.run()
    # the tokens, sampled ones included, are the parent's
    assert [list(map(int, done[r].generated)) for r in rids] \
        == PARENT[family]["tokens"]
    # every drawing call received the NEXT key of one sequential chain from
    # the seed — so call i drew with row 1 of the i-th split, as when the
    # host split the key in front of the call
    assert len(seen) >= 10
    key = jax.random.PRNGKey(SEED)
    for got in seen:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(key))
        key = jax.random.split(key)[0]
    np.testing.assert_array_equal(np.asarray(eng._key), np.asarray(key))
    assert np.asarray(eng._key).tolist() == PARENT_LAST_KEY
    assert eng.stats()["decode_steps"] < len(seen)   # greedy calls split too


@pytest.mark.parametrize("family,overlap", [
    ("llama", False), ("llama", True), ("nemotron_h", False),
    ("mla_moe", True)])
def test_a_snapshot_and_a_restore_continue_the_stream(models, family,
                                                      overlap):
    model = models[family]
    eng = engine(model, overlap=overlap)
    rids = submit_mix(eng)
    for _ in range(3):
        eng.step()
    state = eng.snapshot("compact" if eng.family.recurrent else "full_kv")
    # the key is in the snapshot as it stands after `quiesce()`: the next
    # key of the last call made, not one a dispatch in flight still holds
    np.testing.assert_array_equal(state["rng"], np.asarray(eng._key))
    fresh = engine(model, overlap=overlap)
    fresh.restore(state)
    np.testing.assert_array_equal(np.asarray(fresh._key), state["rng"])
    done = fresh.run()
    want = PARENT[family].get("restored", PARENT[family]["tokens"])
    assert [list(map(int, done[r].generated)) for r in rids] == want
    assert np.asarray(fresh._key).tolist() == PARENT_LAST_KEY


# ---------------------------------------------------------------------------
# the executables themselves: next key = row 0 of the split, the draw is
# made with row 1
# ---------------------------------------------------------------------------
S, P, PS = 3, 4, 4


@pytest.fixture(scope="module")
def calls(models):
    cfg, params = models["llama"]
    fam = cfg.paged_family(page_size=PS, num_pages=S * P, num_slots=S,
                           max_pages_per_seq=P, dtype=jnp.float32,
                           attention_impl="ref")
    return params, fam, make_step_calls(fam, P)


def test_split_call_key_is_the_hosts_split():
    key = jax.random.PRNGKey(11)
    nxt, sub = jax.jit(split_call_key)(key)
    host_next, host_sub = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(host_next))
    np.testing.assert_array_equal(np.asarray(sub), np.asarray(host_sub))


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_the_dense_prefill_splits_once_and_draws_with_the_subkey(calls,
                                                                 greedy):
    params, fam, (_, prefill_sample, _, _) = calls
    key = jax.random.PRNGKey(3)
    ids = np.zeros((8,), np.int32)
    ids[:6] = [5, 9, 2, 7, 7, 1]
    row = np.arange(P, dtype=np.int32)
    tok, nxt, _ = jax.jit(
        lambda *a: prefill_sample(*a, greedy=greedy))(
            params, fam.init_cache(), key,
            jnp.asarray(pack_prefill(ids, 6, 1, 0.9, 0.8, row)))
    host_next, sub = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(host_next))
    logits, _ = fam.prefill(params, jnp.asarray(ids)[None], jnp.int32(6),
                            jnp.asarray(row), jnp.int32(1), fam.init_cache())
    want = jnp.argmax(logits) if greedy else _sample_per_request(
        logits[None], sub, jnp.float32([0.9]), jnp.float32([0.8]))[0]
    assert int(tok) == int(want)


def test_the_sampler_splits_once_and_draws_with_the_subkey(calls):
    sample_logits = calls[2][3]
    key = jax.random.PRNGKey(4)
    logits = jax.random.normal(jax.random.PRNGKey(9), (256,))
    tok, nxt = jax.jit(sample_logits)(logits, key, jnp.float32([1.1, 0.7]))
    host_next, sub = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(host_next))
    assert int(tok) == int(_sample_per_request(
        logits[None], sub, jnp.float32([1.1]), jnp.float32([0.7]))[0])


def test_the_horizon_splits_once_and_its_steps_draw_from_the_subkey(calls):
    params, fam, (horizon, prefill_sample, _, _) = calls
    K = 3
    cache = fam.init_cache()
    tables = np.arange(S * P, dtype=np.int32).reshape(S, P)
    # one prompt in slot 1, so the steps attend something
    ids = np.zeros((8,), np.int32)
    ids[:6] = [5, 9, 2, 7, 7, 1]
    _, _, cache = jax.jit(lambda *a: prefill_sample(*a, greedy=True))(
        params, cache, jax.random.PRNGKey(0),
        jnp.asarray(pack_prefill(ids, 6, 1, 0.0, 1.0, tables[1])))
    toks = np.array([0, 17, 0], np.int32)
    lengths = np.array([0, 6, 0], np.int32)
    temps, top_ps = np.float32([0.0, 0.9, 0.0]), np.float32([1.0, 0.85, 1.0])
    key = jax.random.PRNGKey(21)
    ints = pack_decode_state(toks, lengths, np.full((S,), 8), np.full((S,), -1),
                             [0, 1, 0], np.zeros((S,)), tables)
    out, _, _, _, _, nxt, _ = jax.jit(
        lambda *a: horizon(*a, K=K, greedy=False))(
            params, jax.tree_util.tree_map(jnp.copy, cache), key,
            jnp.asarray(ints), jnp.concatenate([temps, top_ps]))
    host_next, loop_key = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(nxt), np.asarray(host_next))
    live = jnp.asarray([False, True, False])
    tok, length = jnp.asarray(toks), jnp.asarray(lengths)
    for t in range(K):
        logits, cache = fam.decode_step(params, tok, length,
                                        jnp.asarray(tables), cache, live)
        loop_key, sub = jax.random.split(loop_key)
        tok = _sample_per_request(logits, sub, jnp.asarray(temps),
                                  jnp.asarray(top_ps))
        length = length + live.astype(jnp.int32)
        assert int(out[1, t]) == int(tok[1])
