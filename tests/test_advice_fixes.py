"""Regression tests for the round-1 advisor findings:
1. GradScaler per-optimizer unscale state (no double-unscale).
2. TrainStep grad_accum is real gradient merge, equivalent to full batch.
3. Distributed checkpoint shard keys are rank-collision-free.
4. jit.save keeps dynamic InputSpec dims shape-polymorphic.
5. Pallas flash-attn causal mask is bottom-right aligned for s_q != s_k.
"""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import optimizer


def _mlp(seed=7):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = optimizer.SGD(learning_rate=1e-2, parameters=net.parameters())
    return net, opt


class TestGradScalerState:
    def _backward(self, net, sc, x, y):
        pred = net(paddle.to_tensor(x))
        loss = ((pred - paddle.to_tensor(y)) ** 2).mean()
        sc.scale(loss).backward()

    def test_double_unscale_raises(self):
        from paddle_tpu.amp import GradScaler
        net, opt = _mlp()
        sc = GradScaler(enable=True, init_loss_scaling=8.0)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 8)).astype("float32")
        y = rng.normal(size=(4, 1)).astype("float32")
        self._backward(net, sc, x, y)
        sc.unscale_(opt)
        with pytest.raises(RuntimeError):
            sc.unscale_(opt)

    def test_step_after_unscale_does_not_rescale(self):
        from paddle_tpu.amp import GradScaler
        net, opt = _mlp()
        sc = GradScaler(enable=True, init_loss_scaling=8.0)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 8)).astype("float32")
        y = rng.normal(size=(4, 1)).astype("float32")
        self._backward(net, sc, x, y)
        g0 = np.asarray(net.parameters()[0]._grad._value).copy()
        sc.unscale_(opt)
        g1 = np.asarray(net.parameters()[0]._grad._value)
        np.testing.assert_allclose(g1, g0 / 8.0, rtol=1e-6)
        sc.step(opt)  # must not unscale a second time
        sc.update()
        # the canonical pattern is usable again next iteration
        opt.clear_grad()
        self._backward(net, sc, x, y)
        sc.unscale_(opt)
        sc.step(opt)
        sc.update()

    def test_two_optimizers_one_update(self):
        """step(opt1) must not clear opt2's unscaled state (update() is the
        per-iteration reset, exactly one call)."""
        from paddle_tpu.amp import GradScaler
        paddle.seed(3)
        net1 = nn.Linear(8, 4)
        net2 = nn.Linear(8, 4)
        opt1 = optimizer.SGD(learning_rate=1e-2, parameters=net1.parameters())
        opt2 = optimizer.SGD(learning_rate=1e-2, parameters=net2.parameters())
        sc = GradScaler(enable=True, init_loss_scaling=16.0)
        x = paddle.to_tensor(np.ones((2, 8), "float32"))
        loss = net1(x).sum() + net2(x).sum()
        sc.scale(loss).backward()
        sc.unscale_(opt1)
        sc.unscale_(opt2)
        g2 = np.asarray(net2.parameters()[0]._grad._value).copy()
        sc.step(opt1)
        sc.step(opt2)  # must NOT divide net2's grads again
        g2_after = np.asarray(net2.parameters()[0]._grad._value)
        np.testing.assert_allclose(g2_after, g2, rtol=1e-7)
        sc.update()


class TestGradAccum:
    def test_accum_matches_full_batch(self):
        from paddle_tpu.parallel.train_step import compile_train_step

        def loss_fn(model, x, y):
            return ((model(x) - y) ** 2).mean()

        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 8)).astype("float32")
        y = rng.normal(size=(8, 1)).astype("float32")

        net1, opt1 = _mlp()
        s1 = compile_train_step(net1, opt1, loss_fn, donate=False)
        l1 = float(s1(x, y).numpy())
        net2, opt2 = _mlp()
        s2 = compile_train_step(net2, opt2, loss_fn, donate=False, grad_accum=4)
        l2 = float(s2(x, y).numpy())
        assert abs(l1 - l2) < 1e-5
        for k in s1.params:
            np.testing.assert_allclose(np.asarray(s1.params[k]),
                                       np.asarray(s2.params[k]),
                                       rtol=2e-5, atol=2e-6)

    def test_buffers_chain_across_microbatches(self):
        """BatchNorm running stats must receive one update per microbatch,
        chained, not just the last microbatch against the stale buffers."""
        from paddle_tpu.parallel.train_step import compile_train_step

        def loss_fn(model, x, y):
            return ((model(x) - y) ** 2).mean()

        def make():
            paddle.seed(11)
            return nn.Sequential(nn.Linear(8, 8), nn.BatchNorm1D(8),
                                 nn.Linear(8, 1))

        rng = np.random.default_rng(1)
        x = rng.normal(size=(8, 8)).astype("float32")
        y = rng.normal(size=(8, 1)).astype("float32")

        # sequential reference: 4 separate forward/backwards on microbatches
        net_ref = make()
        opt_ref = optimizer.SGD(learning_rate=0.0,
                                parameters=net_ref.parameters())
        s_ref = compile_train_step(net_ref, opt_ref, loss_fn, donate=False)
        for i in range(4):
            s_ref(x[i * 2:(i + 1) * 2], y[i * 2:(i + 1) * 2])

        net_acc = make()
        opt_acc = optimizer.SGD(learning_rate=0.0,
                                parameters=net_acc.parameters())
        s_acc = compile_train_step(net_acc, opt_acc, loss_fn, donate=False,
                                   grad_accum=4)
        s_acc(x, y)

        for k in s_ref.buffers:
            np.testing.assert_allclose(np.asarray(s_ref.buffers[k]),
                                       np.asarray(s_acc.buffers[k]),
                                       rtol=1e-5, atol=1e-6)

    def test_bad_divisor_raises(self):
        from paddle_tpu.parallel.train_step import compile_train_step
        net, opt = _mlp()
        s = compile_train_step(net, opt,
                               lambda m, x, y: ((m(x) - y) ** 2).mean(),
                               donate=False, grad_accum=3)
        x = np.zeros((8, 8), "float32")
        with pytest.raises(ValueError):
            s(x, np.zeros((8, 1), "float32"))


class TestDistCheckpointKeys:
    def test_sharded_roundtrip_extent_keys(self, tmp_path):
        """Shards saved under a dp×mp sharding reload exactly (extent-keyed,
        no rank-local sid collisions) and reshard onto a new layout."""
        from paddle_tpu.distributed.checkpoint import (save_state_dict,
                                                       load_state_dict)
        from paddle_tpu.distributed.topology import build_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = build_mesh({"dp": 2, "mp": 4})
        w = np.arange(64, dtype="float32").reshape(8, 8)
        b = np.arange(8, dtype="float32")
        wt = paddle.to_tensor(w)
        wt._set_value(jax.device_put(wt._value,
                                     NamedSharding(mesh, P("dp", "mp"))))
        bt = paddle.to_tensor(b)
        bt._set_value(jax.device_put(bt._value, NamedSharding(mesh, P("mp"))))
        sd = {"w": wt, "b": bt, "step": 3}
        save_state_dict(sd, str(tmp_path))

        # metadata must cover every extent exactly once per unique shard
        import json
        with open(tmp_path / "metadata.json") as f:
            meta = json.load(f)
        w_exts = {tuple(tuple(p) for p in s["index"])
                  for s in meta["tensors"]["w"]["shards"]}
        assert len(w_exts) == 8  # 2x4 distinct extents

        dst_mesh = build_mesh({"dp": 8})
        wt2 = paddle.to_tensor(np.zeros_like(w))
        wt2._set_value(jax.device_put(wt2._value,
                                      NamedSharding(dst_mesh, P("dp"))))
        bt2 = paddle.to_tensor(np.zeros_like(b))
        load_state_dict({"w": wt2, "b": bt2}, str(tmp_path))
        np.testing.assert_array_equal(np.asarray(wt2.numpy()), w)
        np.testing.assert_array_equal(np.asarray(bt2.numpy()), b)

    def test_resave_removes_stale_rank_files(self, tmp_path):
        """Re-saving into the same dir must not leave old rank files that a
        later load could mix in (single-process: any rank >= 1 is stale)."""
        from paddle_tpu.distributed.checkpoint import (save_state_dict,
                                                       load_state_dict)
        import pickle
        # plant a stale shard file claiming rank 3 wrote part of 'w'
        stale = {("w", ((0, 4), (0, 4))): np.full((4, 4), 99.0, "float32")}
        with open(tmp_path / "rank3.data", "wb") as f:
            pickle.dump(stale, f)
        with open(tmp_path / "rank3.meta.json", "w") as f:
            import json
            json.dump({"version": 2, "tensors": {"w": {
                "shape": [4, 4], "dtype": "float32",
                "shards": [{"index": [[0, 4], [0, 4]],
                            "file": "rank3.data"}]}}}, f)
        w = paddle.to_tensor(np.ones((4, 4), "float32"))
        save_state_dict({"w": w}, str(tmp_path))
        assert not (tmp_path / "rank3.data").exists()
        t = paddle.to_tensor(np.zeros((4, 4), "float32"))
        load_state_dict({"w": t}, str(tmp_path))
        np.testing.assert_array_equal(np.asarray(t.numpy()),
                                      np.ones((4, 4), "float32"))

    def test_missing_shard_detected(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import (save_state_dict,
                                                       load_state_dict)
        import json, os, pickle
        w = paddle.to_tensor(np.ones((4, 4), "float32"))
        save_state_dict({"w": w}, str(tmp_path))
        # corrupt: drop the shard payload but keep metadata
        with open(tmp_path / "rank0.data", "wb") as f:
            pickle.dump({}, f)
        with pytest.raises(RuntimeError, match="missing"):
            load_state_dict({"w": paddle.to_tensor(np.zeros((4, 4), "float32"))},
                            str(tmp_path))


class TestPolymorphicExport:
    def test_dynamic_batch_dim(self, tmp_path):
        from paddle_tpu import jit
        from paddle_tpu.static.input_spec import InputSpec
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 4), nn.ReLU(), nn.Linear(4, 2))
        path = str(tmp_path / "m")
        jit.save(net, path, input_spec=[InputSpec([None, 8], "float32")])
        m = jit.load(path)
        rng = np.random.default_rng(0)
        for B in (1, 3, 17):
            x = rng.normal(size=(B, 8)).astype("float32")
            out = np.asarray(m(x).numpy())
            ref = np.asarray(net(paddle.to_tensor(x)).numpy())
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_two_dynamic_dims_share_scope(self, tmp_path):
        from paddle_tpu import jit
        from paddle_tpu.static.input_spec import InputSpec
        paddle.seed(0)
        net = nn.Linear(8, 4)
        path = str(tmp_path / "m2")
        # [None, None, 8]: batch and sequence both dynamic
        jit.save(net, path, input_spec=[InputSpec([None, None, 8], "float32")])
        m = jit.load(path)
        rng = np.random.default_rng(0)
        for B, S in ((2, 3), (5, 7)):
            x = rng.normal(size=(B, S, 8)).astype("float32")
            out = np.asarray(m(x).numpy())
            ref = np.asarray(net(paddle.to_tensor(x)).numpy())
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


class TestCausalOffset:
    @staticmethod
    def _ref(q, k, v):
        b, sq, h, d = q.shape
        sk = k.shape[1]
        qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)
        kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
        vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
        s = qf @ kf.transpose(0, 1, 3, 2) / math.sqrt(d)
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
        return (jax.nn.softmax(s, -1) @ vf).transpose(0, 2, 1, 3)

    @pytest.mark.parametrize("sq,sk", [(128, 256), (128, 384)])
    def test_suffix_causal_matches_fallback(self, sq, sk):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, sq, 2, 64)).astype("float32"))
        k = jnp.asarray(rng.normal(size=(1, sk, 2, 64)).astype("float32"))
        v = jnp.asarray(rng.normal(size=(1, sk, 2, 64)).astype("float32"))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert out is not None
        ref = self._ref(q, k, v)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5

    def test_sq_gt_sk_defers_to_fallback(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q = jnp.zeros((1, 256, 2, 64))
        k = jnp.zeros((1, 128, 2, 64))
        assert flash_attention(q, k, k, causal=True, interpret=True) is None


# ---------------------------------------------------------------------------
# Round-5 advisor findings (closed in the paged-serving PR)
# ---------------------------------------------------------------------------
class TestTopPSamplingColumnShape:
    """ADVICE r5 #1: top_p_sampling must return [B, 1] column tensors
    (reference parity), not rank-1 [B]."""

    def test_shapes_and_argmax_limit(self):
        import paddle_tpu as paddle
        from paddle_tpu.tensor.search import top_p_sampling
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(rng.normal(size=(3, 16)).astype("float32"))
        ps = paddle.to_tensor(np.full((3,), 1e-6, np.float32))
        vals, ids = top_p_sampling(x, ps, seed=0)
        assert tuple(vals.shape) == (3, 1)
        assert tuple(ids.shape) == (3, 1)
        # int64 downcasts to int32 when x64 is disabled (conftest default)
        assert str(ids.numpy().dtype) in ("int32", "int64")
        # p ~ 0 keeps only the argmax -> callers indexing out[:, 0] get it
        np.testing.assert_array_equal(
            np.asarray(ids.numpy())[:, 0],
            np.argmax(np.asarray(x.numpy()), -1))

    def test_threshold_branch_keeps_shape(self):
        import paddle_tpu as paddle
        from paddle_tpu.tensor.search import top_p_sampling
        rng = np.random.default_rng(1)
        x = paddle.to_tensor(rng.normal(size=(2, 8)).astype("float32"))
        ps = paddle.to_tensor(np.full((2,), 0.9, np.float32))
        thr = paddle.to_tensor(np.full((2,), 0.01, np.float32))
        vals, ids = top_p_sampling(x, ps, threshold=thr, seed=1)
        assert tuple(vals.shape) == (2, 1) and tuple(ids.shape) == (2, 1)


class TestInplaceNonLeafGuard:
    """ADVICE r5 #2: in-place variants on a grad-requiring NON-leaf must
    raise instead of silently detaching upstream gradients."""

    def test_nonleaf_raises(self):
        import paddle_tpu as paddle
        x = paddle.to_tensor(np.ones((3,), np.float32), stop_gradient=False)
        y = x * 2.0                                # non-leaf on the tape
        assert not y.stop_gradient and not y.is_leaf
        with pytest.raises(RuntimeError, match="in-place"):
            y.exp_()

    def test_leaf_requiring_grad_raises_too(self):
        """Reference parity: 'Leaf Var that doesn't stop gradient can't use
        inplace strategy' — the leaf's pending grads would refer to the
        pre-mutation value."""
        import paddle_tpu as paddle
        p = paddle.to_tensor(np.ones((3,), np.float32), stop_gradient=False)
        with pytest.raises(RuntimeError, match="in-place"):
            p.exp_()
        with paddle.no_grad():                     # explicit opt-out works
            p.exp_()
        np.testing.assert_allclose(np.asarray(p.numpy()), np.exp(np.ones(3)),
                                   rtol=1e-6)

    def test_no_grad_paths_still_work(self):
        import paddle_tpu as paddle
        # non-leaf under no_grad: allowed
        x = paddle.to_tensor(np.ones((3,), np.float32), stop_gradient=False)
        y = x * 2.0
        with paddle.no_grad():
            y.sqrt_()
        np.testing.assert_allclose(np.asarray(y.numpy()), np.sqrt(2.0),
                                   rtol=1e-6)
        # stop_gradient non-leaf value: allowed
        z = paddle.to_tensor(np.full((3,), 4.0, np.float32))
        w = z + 0.0
        w.sqrt_()
        np.testing.assert_allclose(np.asarray(w.numpy()), 2.0, rtol=1e-6)


class TestFusedGenerateZeroNewTokens:
    """ADVICE r5 #3: max_new_tokens <= 0 returns the prompt unchanged
    instead of clobbering its last token."""

    def test_prompt_returned_unchanged(self):
        from paddle_tpu.models.llama import (llama_config_tiny,
                                             build_functional_llama,
                                             llama_generate,
                                             llama_generate_fused)
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=32)
        ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(0))
        params = (ep, bp, hp)
        ids = np.random.default_rng(0).integers(1, 64, (2, 6)).astype(np.int32)
        out = np.asarray(llama_generate_fused(params, cfg, ids,
                                              max_new_tokens=0))
        np.testing.assert_array_equal(out, ids)
        ref = np.asarray(llama_generate(params, cfg, ids, max_new_tokens=0))
        np.testing.assert_array_equal(out, ref)


class TestPackLseChunkedGrid:
    """ADVICE r5 #4: _pack_lse grids over s in fixed row chunks, so the
    repack stays correct (and VMEM-bounded) at multi-chunk lengths."""

    @pytest.mark.parametrize("s", [128, 1024, 2048, 2176])
    def test_multi_chunk_roundtrip(self, s):
        from paddle_tpu.ops.pallas.flash_attention import _pack_lse
        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, s, 1)).astype(np.float32)
        out = _pack_lse(jnp.asarray(x), interpret=True)
        assert out.shape == (2, s)
        np.testing.assert_array_equal(np.asarray(out), x[:, :, 0])


class TestProgramFeedStrongRef:
    """ADVICE r5 #5: Program holds the placeholder array itself, so a GC'd
    handle can never let CPython recycle the id into a misbind."""

    def test_feed_survives_placeholder_gc(self):
        import gc
        import paddle_tpu as paddle
        from paddle_tpu import static
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2, 3], "float32")
            y = x * 2.0
        # the program itself must keep the placeholder value alive
        assert "x" in prog._feeds
        held = prog._feeds["x"]
        del x
        gc.collect()
        # churn allocations to encourage id reuse of freed objects
        junk = [np.zeros((2, 3), np.float32) + i for i in range(64)]
        del junk
        exe = static.Executor()
        feed_val = np.arange(6, dtype=np.float32).reshape(2, 3)
        (out,) = exe.run(prog, feed={"x": feed_val}, fetch_list=[y])
        np.testing.assert_allclose(out, feed_val * 2.0, rtol=1e-6)
        assert prog._feeds["x"] is held
