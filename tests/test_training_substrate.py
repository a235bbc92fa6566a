"""Training substrate: optimizer, data pipeline, checkpointing,
gradient compression, sharding rules, end-to-end loss descent."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.registry import tiny
from repro.models import model_for
from repro.training import optimizer as opt
from repro.training import train_loop
from repro.training.compression import (
    _dequantize,
    _quantize,
    compressed_pod_mean,
    init_residuals,
)
from repro.training.data import DataConfig, SyntheticTokens

KEY = jax.random.PRNGKey(0)


class TestOptimizer:
    def test_adamw_decreases_quadratic(self):
        cfg = opt.AdamWConfig(peak_lr=0.1, warmup_steps=1, total_steps=100,
                              weight_decay=0.0)
        params = {"w": jnp.array([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(60):
            grads = {"w": 2 * params["w"]}
            params, state, _ = opt.update(cfg, grads, state, params)
        assert float(jnp.abs(params["w"]).max()) < 1.0

    def test_clip_norm(self):
        cfg = opt.AdamWConfig(clip_norm=1.0, warmup_steps=1)
        params = {"w": jnp.zeros(3)}
        state = opt.init(params)
        _, _, metrics = opt.update(cfg, {"w": jnp.full(3, 100.0)}, state, params)
        assert float(metrics["grad_norm"]) > 1.0  # reported pre-clip

    def test_lr_schedule_shape(self):
        cfg = opt.AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                              min_lr_ratio=0.1)
        lrs = [float(opt.cosine_lr(cfg, jnp.array(s))) for s in [0, 5, 10, 50, 100]]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(0.5)
        assert lrs[2] == pytest.approx(1.0)
        assert lrs[3] < 1.0
        assert lrs[4] == pytest.approx(0.1, abs=1e-6)


class TestData:
    def test_deterministic_and_seekable(self):
        cfg = DataConfig(vocab_size=1000, seq_len=32, global_batch=4, seed=7)
        ds1, ds2 = SyntheticTokens(cfg), SyntheticTokens(cfg)
        b5a = ds1.batch(5)["tokens"]
        b5b = ds2.batch(5)["tokens"]
        np.testing.assert_array_equal(b5a, b5b)
        assert b5a.shape == (4, 32)

    def test_host_slicing_partitions_global_batch(self):
        cfg = DataConfig(vocab_size=1000, seq_len=16, global_batch=8, seed=1)
        ds = SyntheticTokens(cfg)
        full = ds.batch(3)["tokens"]
        h0 = ds.batch(3, host_slice=(0, 2))["tokens"]
        h1 = ds.batch(3, host_slice=(1, 2))["tokens"]
        np.testing.assert_array_equal(np.concatenate([h0, h1]), full)

    def test_zipf_skew(self):
        cfg = DataConfig(vocab_size=5000, seq_len=256, global_batch=4, seed=2)
        toks = SyntheticTokens(cfg).batch(0)["tokens"]
        # Zipf: low token ids dominate.
        assert (toks < 50).mean() > 0.2


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        mgr.save(10, tree, blocking=True)
        assert mgr.latest_step() == 10
        out = mgr.restore(10, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
        np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(out["b"]["c"]), np.asarray(tree["b"]["c"]))

    def test_async_save_and_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"w": jnp.zeros(8)}
        for s in [1, 2, 3, 4]:
            mgr.save(s, tree)
        mgr.wait()
        mgr._gc()
        assert mgr.all_steps() == [3, 4]

    def test_crash_leaves_no_partial_checkpoint(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        # Simulate a crashed save: orphan tmp dir.
        os.makedirs(tmp_path / "step_00000099.tmp")
        assert mgr.latest_step() is None
        mgr.save(5, {"w": jnp.zeros(2)}, blocking=True)
        assert mgr.latest_step() == 5
        assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"w": jnp.zeros(4)}, blocking=True)
        with pytest.raises(ValueError):
            mgr.restore(1, {"w": jax.ShapeDtypeStruct((5,), jnp.float32)})

    def test_train_resume_is_bit_identical(self, tmp_path):
        """Train 6 steps straight vs 3 + checkpoint + resume 3."""
        cfg = tiny("granite-3-2b")
        model = model_for(cfg)
        tcfg = train_loop.TrainConfig(
            adamw=opt.AdamWConfig(peak_lr=1e-2, warmup_steps=1, total_steps=10)
        )
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 16, 2, seed=3))
        step = jax.jit(train_loop.make_train_step(model, tcfg))

        def run(state, lo, hi):
            for i in range(lo, hi):
                state, _ = step(state, {"tokens": jnp.asarray(data.batch(i)["tokens"])})
            return state

        s_straight = run(train_loop.init_state(model, KEY), 0, 6)
        s_half = run(train_loop.init_state(model, KEY), 0, 3)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(3, s_half, blocking=True)
        s_restored = mgr.restore(3, train_loop.abstract_state(model))
        s_resumed = run(s_restored, 3, 6)
        for a, b in zip(jax.tree.leaves(s_straight), jax.tree.leaves(s_resumed)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        x = jax.random.normal(KEY, (1000,))
        codes, scale = _quantize(x)
        out = _dequantize(codes, scale, 1000)
        max_err = float(jnp.max(jnp.abs(out - x)))
        assert max_err <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6

    def test_pod_mean_with_error_feedback(self):
        """shard_map over a fake 2-'pod' mesh: compressed mean approximates
        the true mean, and error feedback keeps the bias bounded over
        repeated rounds."""
        from jax.sharding import Mesh, PartitionSpec as P

        devs = np.array(jax.devices()[:1])
        if len(jax.devices()) < 2:
            # Single CPU device: emulate by calling the quantize path
            # directly (all_gather over axis of size 1 is identity).
            mesh = Mesh(devs.reshape(1), ("pod",))
            g = jax.random.normal(KEY, (64,))
            r = jnp.zeros((64,))

            def f(g, r):
                return compressed_pod_mean(g, r, "pod")

            out, new_r = jax.jit(
                jax.shard_map(
                    f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                    check_vma=False,
                )
            )(g, r)
            np.testing.assert_allclose(
                np.asarray(out + new_r), np.asarray(g), atol=1e-5
            )

    def test_residual_init_matches_structure(self):
        params = {"a": jnp.zeros((2, 3)), "b": jnp.ones(4)}
        res = init_residuals(params)
        assert res["a"].shape == (2, 3) and res["b"].shape == (4,)


class TestShardingRules:
    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def test_divisibility_fallback(self):
        from repro.distributed.sharding import PARAM_RULES, spec_for_shape
        from jax.sharding import Mesh

        # fake mesh sizes via a Mesh over 1 device but spec logic uses
        # mesh.shape — build an abstract mesh instead:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        spec = spec_for_shape((64, 128), ("embed", "mlp"), mesh, PARAM_RULES)
        assert spec == jax.sharding.PartitionSpec("data", "model")

    def test_abstract_mesh_divisibility(self):
        mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
        from repro.distributed.sharding import (
            CACHE_RULES,
            PARAM_RULES,
            spec_for_shape,
        )
        from jax.sharding import PartitionSpec as P

        # kv_heads=8 indivisible by model=16 -> falls through to head_dim.
        spec = spec_for_shape(
            (2048, 8, 128), ("embed", "kv_heads", "head_dim"), mesh, PARAM_RULES
        )
        assert spec == P("data", None, "model")
        # batch=1 (long_500k) falls through to sequence sharding.
        spec = spec_for_shape(
            (1, 524288, 8, 128),
            ("batch", "seq", "kv_heads", "head_dim"),
            mesh,
            CACHE_RULES,
        )
        assert spec == P(None, "data", None, "model")
        # mixtral experts 8 indivisible -> expert dim replicated, TP inside.
        spec = spec_for_shape(
            (8, 4096, 14336), ("expert", "embed", "mlp"), mesh, PARAM_RULES
        )
        assert spec == P(None, "data", "model")

    def test_multi_axis_batch(self):
        mesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        from repro.distributed.sharding import ACT_RULES, spec_for_shape
        from jax.sharding import PartitionSpec as P

        spec = spec_for_shape((256, 4096), ("batch", "seq"), mesh, ACT_RULES)
        assert spec == P(("pod", "data"))


class TestEndToEndTraining:
    def test_loss_descends_tiny_model(self):
        cfg = tiny("granite-3-2b")
        model = model_for(cfg)
        tcfg = train_loop.TrainConfig(
            adamw=opt.AdamWConfig(peak_lr=5e-3, warmup_steps=2, total_steps=30)
        )
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 32, 4, seed=0))
        step = jax.jit(train_loop.make_train_step(model, tcfg))
        state = train_loop.init_state(model, KEY)
        losses = []
        for i in range(25):
            state, m = step(state, {"tokens": jnp.asarray(data.batch(i)["tokens"])})
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5])

    def test_grad_accum_matches_large_batch(self):
        cfg = tiny("granite-3-2b")
        model = model_for(cfg)
        data = SyntheticTokens(DataConfig(cfg.vocab_size, 16, 4, seed=5))
        batch = {"tokens": jnp.asarray(data.batch(0)["tokens"])}
        mk = lambda k: train_loop.make_train_step(
            model,
            train_loop.TrainConfig(
                adamw=opt.AdamWConfig(peak_lr=1e-2, warmup_steps=1),
                grad_accum=k,
            ),
        )
        s1, _ = jax.jit(mk(1))(train_loop.init_state(model, KEY), batch)
        s2, _ = jax.jit(mk(2))(train_loop.init_state(model, KEY), batch)
        # Adam's rsqrt(v) amplifies f32 reduction-order noise between the
        # single-batch and accumulated paths; compare at optimizer scale.
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-3, rtol=0,
            )
