"""Runtime integration: coded DP training (faults, timeout, restart) and
the failure detector."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.traces import TraceConfig, sample_traces
from repro.data.pipeline import TokenPipeline
from repro.models import build_model
from repro.models.params import initialize
from repro.optim.optimizer import make_optimizer
from repro.runtime.train_loop import CodedDPStep, TrainLoopConfig, train


def _tiny_setup():
    cfg = get_config("xlstm-125m").reduced()
    model = build_model(cfg)
    params = initialize(model.specs(), jax.random.PRNGKey(0))
    return cfg, model, params


class TestCodedDP:
    def test_coded_gradient_equals_plain(self):
        """Gradient decoded from coded DP groups == plain full-batch grad."""
        cfg, model, params = _tiny_setup()
        pipeline = TokenPipeline(vocab_size=cfg.vocab_size, batch=12,
                                 seq_len=16, seed=0)
        batch = pipeline.next_batch()
        coded = CodedDPStep(model.loss_fn, n_groups=6, s=2)
        grad, loss, info = coded.step(params, batch, np.ones(6))
        # plain reference: sum of per-partition grads == full-batch grad*?
        # partitions have unequal sizes; loss is mean-per-partition so the
        # decoded sum equals Σ_p grad(mean loss on p). Compare against that.
        parts = coded.partition_batch(batch, np.ones(6))
        want = None
        for p_ in parts:
            if next(iter(p_.values())).shape[0] == 0:
                continue
            g = jax.grad(model.loss_fn)(params, p_)
            g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
            want = g if want is None else jax.tree.map(jnp.add, want, g)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(grad),
                                  jax.tree.leaves(want)))
        scale = max(float(jnp.max(jnp.abs(b)))
                    for b in jax.tree.leaves(want))
        assert err / (scale + 1e-9) < 5e-3

    def test_straggler_does_not_break_decode(self):
        cfg, model, params = _tiny_setup()
        pipeline = TokenPipeline(vocab_size=cfg.vocab_size, batch=12,
                                 seq_len=16, seed=0)
        batch = pipeline.next_batch()
        coded = CodedDPStep(model.loss_fn, n_groups=6, s=2)
        speeds = np.array([1, 1, 1, 1, 0.05, 1.0])
        grad, loss, info = coded.step(params, batch, speeds)
        assert grad is not None and np.isfinite(loss)
        assert 4 in info["straggled"]

    def test_dead_group_tolerated(self):
        cfg, model, params = _tiny_setup()
        pipeline = TokenPipeline(vocab_size=cfg.vocab_size, batch=12,
                                 seq_len=16, seed=0)
        batch = pipeline.next_batch()
        coded = CodedDPStep(model.loss_fn, n_groups=6, s=2)
        grad, loss, info = coded.step(params, batch, np.ones(6),
                                      dead_groups={1, 4})
        assert grad is not None and np.isfinite(loss)


class TestTrainLoopE2E:
    def test_checkpoint_restart_resumes(self, tmp_path):
        """Kill after N steps; restart must resume from the checkpoint with
        the data cursor intact (no replay)."""
        cfg, model, params = _tiny_setup()
        opt = make_optimizer("adamw", lr=1e-3)
        traces = sample_traces(TraceConfig(n_nodes=4, n_iters=40), seed=0)

        def mk_pipeline():
            return TokenPipeline(vocab_size=cfg.vocab_size, batch=8,
                                 seq_len=16, seed=0)

        loop_cfg = TrainLoopConfig(total_steps=6, ckpt_every=3,
                                   ckpt_dir=str(tmp_path), n_groups=4,
                                   stragglers_tolerated=1, log_every=100)
        m1 = train(model, params, opt, mk_pipeline(), loop_cfg,
                   speed_traces=traces)
        # "crash" and restart with more steps: resumes from step 6's ckpt
        loop_cfg2 = TrainLoopConfig(total_steps=10, ckpt_every=3,
                                    ckpt_dir=str(tmp_path), n_groups=4,
                                    stragglers_tolerated=1, log_every=100)
        m2 = train(model, params, opt, mk_pipeline(), loop_cfg2,
                   speed_traces=traces)
        assert len(m2["losses"]) < 10          # resumed, not from scratch
        assert np.isfinite(m2["final_loss"])


class TestFailureDetector:
    def test_straggler_flagged_with_full_responses(self):
        from repro.runtime.elastic import FailureDetector
        det = FailureDetector(n=6, k=4, slack=0.2)
        rt = np.array([1.0, 1.0, 1.1, 1.0, 1.05, 5.0])
        out = det.evaluate(rt)
        assert out["stragglers"] == {5}
        assert np.isfinite(out["timeout"])

    def test_fewer_than_k_responders_still_finite_timeout(self):
        """inf responses must not poison the first-k mean (the §4.3 rule
        degrades to the finite responders)."""
        from repro.runtime.elastic import FailureDetector
        det = FailureDetector(n=6, k=4, slack=0.2)
        rt = np.array([1.0, 1.1, np.inf, np.inf, np.inf, np.inf])
        out = det.evaluate(rt)
        assert np.isfinite(out["timeout"])
        assert out["stragglers"] == {2, 3, 4, 5}

    def test_nobody_responds_strikes_everyone(self):
        from repro.runtime.elastic import FailureDetector
        det = FailureDetector(n=4, k=2, slack=0.2, dead_after=2)
        rt = np.full(4, np.inf)
        out1 = det.evaluate(rt)
        assert out1["stragglers"] == {0, 1, 2, 3}
        out2 = det.evaluate(rt)              # second strike ⇒ dead
        assert out2["dead"] == {0, 1, 2, 3}

    def test_strikes_accumulate_to_dead(self):
        from repro.runtime.elastic import FailureDetector
        det = FailureDetector(n=5, k=3, slack=0.15, dead_after=3)
        rt = np.array([1.0, 1.0, 1.0, 1.0, np.inf])
        for _ in range(2):
            out = det.evaluate(rt)
            assert out["dead"] == set()
            assert 4 in out["stragglers"]
        out = det.evaluate(rt)
        assert out["dead"] == {4}

