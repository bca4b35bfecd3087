"""Substrate tests: optimizer, checkpoint (elastic restore), data pipeline,
predictor, sharding resolution, elastic planning."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.predictor import (SpeedPredictor, ema_baseline,
                                  last_value_baseline, train_predictor)
from repro.core.traces import TraceConfig, controlled_traces, sample_traces
from repro.checkpoint.checkpoint import (cleanup_old, latest_step,
                                         restore_checkpoint, save_checkpoint)
from repro.data.pipeline import (TokenPipeline, laplacian_matrix,
                                 make_graph, make_lr_dataset)
from repro.launch.partition import resolve_axes
from repro.models.params import ParamSpec, abstract, initialize, param_count
from repro.optim.optimizer import make_optimizer
from repro.runtime.elastic import ElasticPlan, FailureDetector, remesh_shape


class TestOptimizers:
    @pytest.mark.parametrize("name", ["adamw", "adafactor", "sgdm"])
    def test_reduces_quadratic(self, name):
        opt = make_optimizer(name, lr=0.1)
        params = {"w": jnp.asarray([3.0, -2.0, 1.0]),
                  "m": jnp.ones((4, 5)) * 2.0}
        state = opt.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2) + jnp.sum(p["m"] ** 2)

        l0 = float(loss(params))
        for step in range(60):
            grads = jax.grad(loss)(params)
            params, state = opt.update(grads, state, params,
                                       jnp.int32(step))
        assert float(loss(params)) < 0.1 * l0

    @pytest.mark.parametrize("name", ["adamw", "adafactor", "sgdm"])
    def test_state_specs_match_init(self, name):
        opt = make_optimizer(name)
        specs = {"a": ParamSpec((8, 16), ("embed", "mlp")),
                 "b": ParamSpec((4,), (None,))}
        params = initialize(specs, jax.random.PRNGKey(0))
        state = opt.init(params)
        spec_state = abstract(opt.state_specs(specs))
        flat_a = jax.tree.leaves(jax.tree.map(lambda x: x.shape, state))
        flat_b = jax.tree.leaves(jax.tree.map(lambda x: x.shape, spec_state))
        assert flat_a == flat_b

    def test_adafactor_memory_is_sublinear(self):
        """Factored state: a (1024, 1024) param gets 2×1024 state, not 2M."""
        opt = make_optimizer("adafactor")
        params = {"w": jnp.zeros((1024, 1024))}
        state = opt.init(params)
        n_state = sum(x.size for x in jax.tree.leaves(state))
        assert n_state == 2048


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        params = {"w": jnp.arange(6.0).reshape(2, 3),
                  "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
        opt_state = {"w": {"_s_m": jnp.zeros((2, 3))},
                     "nested": {"b": {"_s_m": jnp.ones((4,))}}}
        save_checkpoint(str(tmp_path), 7, params, opt_state,
                        extras={"pipeline": {"cursor": 112, "seed": 0}})
        assert latest_step(str(tmp_path)) == 7
        step, p2, o2, extras = restore_checkpoint(str(tmp_path), params,
                                                  opt_state)
        assert step == 7 and extras["pipeline"]["cursor"] == 112
        np.testing.assert_array_equal(np.asarray(p2["w"]),
                                      np.asarray(params["w"]))
        assert p2["nested"]["b"].dtype == jnp.bfloat16

    def test_cleanup_keeps_latest(self, tmp_path):
        p = {"w": jnp.zeros(2)}
        for s in (1, 2, 3, 4, 5):
            save_checkpoint(str(tmp_path), s, p)
        cleanup_old(str(tmp_path), keep=2)
        assert latest_step(str(tmp_path)) == 5
        assert len(os.listdir(tmp_path)) == 2

    def test_nonstrict_partial_restore(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"w": jnp.ones(3)})
        step, p2, _, _ = restore_checkpoint(
            str(tmp_path), {"w": jnp.zeros(3), "new": jnp.full(2, 9.0)},
            strict=False)
        np.testing.assert_array_equal(np.asarray(p2["w"]), np.ones(3))
        np.testing.assert_array_equal(np.asarray(p2["new"]), [9.0, 9.0])

    def test_strict_missing_raises(self, tmp_path):
        save_checkpoint(str(tmp_path), 1, {"w": jnp.ones(3)})
        with pytest.raises(KeyError):
            restore_checkpoint(str(tmp_path),
                               {"w": jnp.zeros(3), "x": jnp.zeros(1)})


class TestPipeline:
    def test_deterministic_and_restartable(self):
        p1 = TokenPipeline(vocab_size=100, batch=4, seq_len=8, seed=1)
        b1 = p1.next_batch()
        b2 = p1.next_batch()
        state = p1.state()
        b3 = p1.next_batch()
        p2 = TokenPipeline(vocab_size=100, batch=4, seq_len=8, seed=1)
        p2.restore(state)
        b3r = p2.next_batch()
        np.testing.assert_array_equal(b3["tokens"], b3r["tokens"])
        assert not np.array_equal(b1["tokens"], b2["tokens"])

    def test_vlm_fields(self):
        p = TokenPipeline(vocab_size=100, batch=2, seq_len=8, image_tokens=4,
                          image_dim=16)
        b = p.next_batch()
        assert b["image_embeds"].shape == (2, 4, 16)

    def test_lr_dataset_learnable(self):
        a, y, w = make_lr_dataset(rows=500, cols=20, seed=0)
        acc = ((a @ w > 0) * 2 - 1 == y).mean()
        assert acc > 0.8

    def test_graph(self):
        adj = make_graph(64, 4, seed=0)
        lap = laplacian_matrix(adj)
        np.testing.assert_allclose(lap.sum(1), 0.0, atol=1e-9)


class TestPredictor:
    def test_training_reduces_loss_and_tracks(self):
        traces = sample_traces(TraceConfig(n_nodes=6, n_iters=150), seed=1)
        params, metrics = train_predictor(traces, epochs=120)
        assert metrics["test_mape"] < 0.5
        assert np.isfinite(metrics["final_train_loss"])

    def test_online_api(self):
        sp = SpeedPredictor(4)
        assert (sp.predict() == 1.0).all()      # cold start: equal speeds
        sp.observe(np.array([1.0, 0.5, 1.0, 0.2]))
        pred = sp.predict()                     # last-value without params
        np.testing.assert_array_equal(pred, [1.0, 0.5, 1.0, 0.2])

    def test_baselines(self):
        h = np.array([[1.0, 2.0], [3.0, 4.0]])
        np.testing.assert_array_equal(last_value_baseline(h), [3.0, 4.0])
        assert ema_baseline(h).shape == (2,)


class TestShardingRules:
    def _mesh(self):
        from repro.launch.mesh import auto_axes
        return jax.make_mesh((1, 1), ("data", "model"), **auto_axes(2))

    def test_nondivisible_drops(self):
        from repro.launch.mesh import make_worker_mesh
        mesh = make_worker_mesh(1, axis="model")
        spec = resolve_axes(("vocab",), (7,), mesh)   # 7 % 1 == 0 -> sharded
        # with axis size 1 sharding is trivial; test divisibility via rules
        spec2 = resolve_axes(("heads",), (7,), mesh)
        assert spec is not None and spec2 is not None

    def test_no_double_assignment(self):
        mesh = self._mesh()
        spec = resolve_axes(("q_proj", "mlp"), (16, 16), mesh)
        flat = [e for e in spec if e is not None]
        assert len(set(flat)) == len(flat)


class TestElastic:
    def test_failure_detector_declares_dead(self):
        fd = FailureDetector(n=6, k=4, slack=0.15, dead_after=2)
        rt = np.array([1.0, 1.0, 1.0, 1.0, 1.0, 9.0])
        r1 = fd.evaluate(rt)
        assert 5 in r1["stragglers"] and not r1["dead"]
        r2 = fd.evaluate(rt)
        assert 5 in r2["dead"]

    def test_elastic_plan_skips_dead(self):
        ep = ElasticPlan(n=6, k=4)
        al = ep.plan(np.ones(6), dead={2})
        assert al.count[2] == 0
        assert (al.coverage() >= 4).all()

    def test_elastic_plan_below_k_raises(self):
        ep = ElasticPlan(n=5, k=4)
        with pytest.raises(RuntimeError):
            ep.plan(np.ones(5), dead={0, 1})

    def test_remesh(self):
        assert remesh_shape(512) == (32, 16)
        assert remesh_shape(240) == (15, 16)
        assert remesh_shape(8) is None
