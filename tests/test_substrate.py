"""Substrate tests: optimizer, schedule, checkpoint, data pipeline."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpointer as ckpt
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import pipeline as data_mod
from repro.optim import adamw


class TestAdamW:
    def test_converges_on_quadratic(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw.init(params)
        target = jnp.asarray([1.0, 2.0])

        def loss(p):
            return jnp.sum(jnp.square(p["w"] - target))

        for _ in range(200):
            g = jax.grad(loss)(params)
            params, state, _ = adamw.update(cfg, g, state, params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   np.asarray(target), atol=1e-2)

    def test_grad_clipping(self):
        cfg = adamw.AdamWConfig(lr=1.0, grad_clip_norm=1.0,
                                weight_decay=0.0)
        params = {"w": jnp.zeros((4, 4))}
        state = adamw.init(params)
        g = {"w": jnp.full((4, 4), 100.0)}
        _, _, m = adamw.update(cfg, g, state, params)
        assert float(m["grad_norm"]) == pytest.approx(400.0)
        # effective step magnitude bounded by lr (clip makes mu/sqrt(nu)=1)
        # just check finiteness + boundedness:
        p2, _, _ = adamw.update(cfg, g, state, params)

    def test_no_decay_on_vectors(self):
        cfg = adamw.AdamWConfig(lr=1e-2, weight_decay=1.0)
        params = {"mat": jnp.ones((4, 4)), "vec": jnp.ones((4,))}
        state = adamw.init(params)
        zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
        p2, _, _ = adamw.update(cfg, zero_g, state, params)
        # matrix decays toward zero, vector untouched
        assert float(jnp.max(jnp.abs(p2["mat"]))) < 1.0
        np.testing.assert_allclose(np.asarray(p2["vec"]),
                                   np.ones((4,)), atol=1e-7)

    def test_bf16_params_f32_moments(self):
        params = {"w": jnp.ones((8,), jnp.bfloat16) * 0 + 1}
        params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
        state = adamw.init(params)
        assert state["mu"]["w"].dtype == jnp.float32
        cfg = adamw.AdamWConfig(lr=1e-3)
        g = {"w": jnp.ones((8, 8), jnp.bfloat16)}
        p2, s2, _ = adamw.update(cfg, g, state, params)
        assert p2["w"].dtype == jnp.bfloat16
        assert s2["nu"]["w"].dtype == jnp.float32

class TestCheckpoint:
    def _tree(self):
        return {"a": {"w": jnp.arange(6.0).reshape(2, 3)},
                "b": jnp.asarray([1, 2, 3], jnp.int32)}

    def test_roundtrip(self, tmp_path):
        tree = self._tree()
        ckpt.save(str(tmp_path), 7, tree, extra={"loss": 1.5})
        got, extra = ckpt.restore(str(tmp_path), 7, tree)
        np.testing.assert_array_equal(np.asarray(got["a"]["w"]),
                                      np.asarray(tree["a"]["w"]))
        assert extra["loss"] == 1.5
        assert ckpt.latest_step(str(tmp_path)) == 7

    def test_gc_keeps_last(self, tmp_path):
        tree = self._tree()
        for s in range(6):
            ckpt.save(str(tmp_path), s, tree, keep_last=3)
        steps = sorted(d for d in os.listdir(tmp_path)
                       if d.startswith("step_"))
        assert len(steps) == 3
        assert ckpt.latest_step(str(tmp_path)) == 5

    def test_shape_mismatch_rejected(self, tmp_path):
        tree = self._tree()
        ckpt.save(str(tmp_path), 0, tree)
        bad = {"a": {"w": jnp.zeros((3, 3))}, "b": tree["b"]}
        with pytest.raises(ckpt.CheckpointError, match="shape mismatch"):
            ckpt.restore(str(tmp_path), 0, bad)

    def test_incomplete_marker_rejected(self, tmp_path):
        import json
        tree = self._tree()
        path = ckpt.save(str(tmp_path), 0, tree)
        man = os.path.join(path, "manifest.json")
        with open(man) as f:
            m = json.load(f)
        m["complete"] = False
        with open(man, "w") as f:
            json.dump(m, f)
        with pytest.raises(ckpt.CheckpointError, match="incomplete"):
            ckpt.restore(str(tmp_path), 0, tree)

    def test_async_checkpointer(self, tmp_path):
        tree = self._tree()
        ac = ckpt.AsyncCheckpointer(str(tmp_path))
        for s in (1, 2, 3):
            ac.submit(s, tree, extra={"s": s})
        ac.close()
        assert ckpt.latest_step(str(tmp_path)) == 3
        got, extra = ckpt.restore(str(tmp_path), 3, tree)
        assert extra["s"] == 3


class TestDataPipeline:
    def test_determinism_and_independence(self):
        cfg = get_config("deepseek-7b").reduced()
        shape = ShapeConfig("t", 32, 4, "train")
        b1 = data_mod.synth_batch(cfg, shape, step=5, seed=42)
        b2 = data_mod.synth_batch(cfg, shape, step=5, seed=42)
        b3 = data_mod.synth_batch(cfg, shape, step=6, seed=42)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = get_config("deepseek-7b").reduced()
        shape = ShapeConfig("t", 32, 4, "train")
        b = data_mod.synth_batch(cfg, shape, step=0)
        np.testing.assert_array_equal(b["labels"][:, :-1],
                                      b["tokens"][:, 1:])
        assert b["tokens"].max() < cfg.vocab_size
        assert b["tokens"].min() >= 0

    def test_prefetch_pipeline_order(self):
        cfg = get_config("deepseek-7b").reduced()
        shape = ShapeConfig("t", 16, 2, "train")
        pipe = data_mod.Pipeline(cfg, shape, start_step=3)
        steps = [next(pipe)[0] for _ in range(4)]
        pipe.close()
        assert steps == [3, 4, 5, 6]

    def test_modality_batches(self):
        hub = get_config("hubert-xlarge").reduced()
        shape = ShapeConfig("t", 16, 2, "train")
        b = data_mod.synth_batch(hub, shape, 0)
        assert b["frames"].shape == (2, 16, hub.frontend_dim)
        pal = get_config("paligemma-3b").reduced()
        b = data_mod.synth_batch(pal, shape, 0)
        assert b["patches"].shape == (2, pal.n_prefix_tokens,
                                      pal.frontend_dim)
