"""Expert-parallel MoE + pipeline-parallel tests.

Correctness oracle throughout: the same pure function executed unsharded
(single logical device view) vs. through the sharded path — GSPMD/shard_map
must not change the math.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.api import AutoDist
from autodist_tpu.models import get_model
from autodist_tpu.parallel import pipeline_apply, pipeline_value_and_grad
from autodist_tpu.resource_spec import ResourceSpec
import autodist_tpu.strategy as S


def make_mesh(shape, names):
    return Mesh(np.array(jax.devices()).reshape(shape), names)


def tiny_moe(**kw):
    return get_model(
        "moe_transformer", vocab_size=128, num_layers=1, d_model=32,
        num_heads=4, d_ff=64, max_seq_len=16, num_experts=4, **kw,
    )


class TestMoE:
    def test_forward_runs_and_routes(self):
        model = tiny_moe()
        params = model.init(jax.random.PRNGKey(0))
        batch = model.example_batch(4)
        loss = model.loss_fn(params, batch)
        assert np.isfinite(float(loss))

    def test_expert_vars_marked_and_sharded(self):
        AutoDist.reset_default()
        try:
            ad = AutoDist(
                resource_spec=ResourceSpec(resource_dict={
                    "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
                    "mesh": {"data": 2, "expert": 4},
                }),
                strategy_builder=S.AllReduce(),
                mesh_axes=("data", "expert"),
            )
            model = tiny_moe()
            params = model.init(jax.random.PRNGKey(0))
            batch = model.example_batch(4)
            step = ad.build(
                model.loss_fn, params, batch,
                sparse_names=model.sparse_names,
                expert_names=model.expert_names,
            )
            wi_plan = step.plan.var_plans["layers_0/moe/expert_wi"]
            assert wi_plan.pspec == P("expert", None, None)
            state = step.init(params)
            # Expert kernels really live sharded over the expert axis.
            shard_shape = state.params["layers_0"]["moe"]["expert_wi"].sharding.shard_shape(
                (4, 32, 64)
            )
            assert shard_shape == (1, 32, 64)
            state, metrics = step(state, batch)
            assert np.isfinite(float(metrics["loss"]))
        finally:
            AutoDist.reset_default()

    def test_sharded_loss_matches_unsharded(self):
        """EP sharding must not change the routed computation."""
        AutoDist.reset_default()
        try:
            model = tiny_moe()
            params = model.init(jax.random.PRNGKey(0))
            batch = model.example_batch(4)
            want = float(model.loss_fn(params, batch))

            ad = AutoDist(
                resource_spec=ResourceSpec(resource_dict={
                    "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
                    "mesh": {"data": 2, "expert": 4},
                }),
                strategy_builder=S.AllReduce(),
                mesh_axes=("data", "expert"),
            )
            step = ad.build(
                model.loss_fn, params, batch,
                sparse_names=model.sparse_names, expert_names=model.expert_names,
            )
            state = step.init(params)
            _, metrics = step(state, batch)
            np.testing.assert_allclose(float(metrics["loss"]), want, rtol=1e-4)
        finally:
            AutoDist.reset_default()

    def test_training_reduces_loss(self):
        AutoDist.reset_default()
        try:
            ad = AutoDist(
                resource_spec=ResourceSpec(resource_dict={
                    "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
                    "mesh": {"data": 2, "expert": 4},
                }),
                strategy_builder=S.AllReduce(),
                mesh_axes=("data", "expert"),
            )
            model = tiny_moe()
            params = model.init(jax.random.PRNGKey(0))
            batch = model.example_batch(8)
            from autodist_tpu.model_item import OptimizerSpec

            step = ad.build(
                model.loss_fn, params, batch,
                optimizer=OptimizerSpec("adam", {"learning_rate": 1e-2}),
                expert_names=model.expert_names,
            )
            state = step.init(params)
            losses = []
            for _ in range(8):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            assert losses[-1] < losses[0]
        finally:
            AutoDist.reset_default()


class TestPipeline:
    @staticmethod
    def stage_fn(sp, h):
        return jnp.tanh(h @ sp["w"] + sp["b"])

    def stacked(self, n_stages, d=16, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 2)
        return {
            "w": jax.random.normal(ks[0], (n_stages, d, d)) * 0.5,
            "b": jax.random.normal(ks[1], (n_stages, d)) * 0.1,
        }

    def sequential(self, params, x, n_stages):
        for s in range(n_stages):
            x = self.stage_fn(jax.tree.map(lambda a: a[s], params), x)
        return x

    @pytest.mark.parametrize("n_micro", [4, 8])
    def test_pipeline_matches_sequential_forward(self, n_micro):
        mesh = make_mesh((2, 4), ("data", "pipe"))
        params = self.stacked(4)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))
        want = self.sequential(params, x, 4)
        got = jax.jit(
            lambda p, xx: pipeline_apply(self.stage_fn, p, xx, n_micro, mesh=mesh)
        )(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_pipeline_matches_sequential_grads(self):
        mesh = make_mesh((1, 8), ("data", "pipe"))
        params = self.stacked(8, d=8)
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 8))

        def loss_pipe(p):
            return jnp.sum(pipeline_apply(self.stage_fn, p, x, 4, mesh=mesh) ** 2)

        def loss_seq(p):
            return jnp.sum(self.sequential(p, x, 8) ** 2)

        got = jax.jit(jax.grad(loss_pipe))(params)
        want = jax.grad(loss_seq)(params)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), atol=2e-4, rtol=2e-4
            )

    def test_trivial_pipe_axis_scans_sequentially(self):
        mesh = make_mesh((8,), ("data",))
        params = self.stacked(4)
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 16))
        got = pipeline_apply(self.stage_fn, params, x, 2, mesh=mesh)
        want = self.sequential(params, x, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_stage_mismatch_raises(self):
        mesh = make_mesh((1, 8), ("data", "pipe"))
        params = self.stacked(4)
        x = jnp.zeros((8, 16))
        with pytest.raises(ValueError, match="must equal mesh axis"):
            pipeline_apply(self.stage_fn, params, x, 2, mesh=mesh)


class Test1F1B:
    """1F1B scheduling (VERDICT r2 #8): the custom-vjp reverse-pipeline
    backward behind ``pipeline_apply(schedule='1f1b')``, and the fully
    interleaved loop in ``pipeline_value_and_grad``."""

    @staticmethod
    def two_layer_stage(sp, h):
        # A stage with an interior activation, so the gpipe-autodiff path
        # has per-tick residuals to save and the memory contrast is real.
        h = jnp.tanh(h @ sp["w1"])
        return jnp.tanh(h @ sp["w2"] + sp["b"])

    def stacked2(self, n_stages, d=16, dh=64, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return {
            "w1": jax.random.normal(ks[0], (n_stages, d, dh)) * 0.3,
            "w2": jax.random.normal(ks[1], (n_stages, dh, d)) * 0.3,
            "b": jax.random.normal(ks[2], (n_stages, d)) * 0.1,
        }

    def test_1f1b_matches_gpipe(self):
        # Schedules change memory, never values: forward, param grads and
        # the x cotangent must match the gpipe-autodiff path.
        mesh = make_mesh((1, 8), ("data", "pipe"))
        params = self.stacked2(8, d=8, dh=16)
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 8))

        def loss(p, xx, sched):
            return jnp.sum(pipeline_apply(
                self.two_layer_stage, p, xx, 4, mesh=mesh,
                schedule=sched) ** 2)

        fwd_g = jax.jit(lambda p: pipeline_apply(
            self.two_layer_stage, p, x, 4, mesh=mesh))(params)
        fwd_1 = jax.jit(lambda p: pipeline_apply(
            self.two_layer_stage, p, x, 4, mesh=mesh, schedule="1f1b"))(params)
        np.testing.assert_allclose(
            np.asarray(fwd_1), np.asarray(fwd_g), rtol=1e-6, atol=1e-7)
        gg = jax.jit(jax.grad(
            lambda p, xx: loss(p, xx, "gpipe"), argnums=(0, 1)))(params, x)
        g1 = jax.jit(jax.grad(
            lambda p, xx: loss(p, xx, "1f1b"), argnums=(0, 1)))(params, x)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            g1, gg)

    def test_unknown_schedule_raises(self):
        mesh = make_mesh((1, 8), ("data", "pipe"))
        with pytest.raises(ValueError, match="schedule"):
            pipeline_apply(self.two_layer_stage, self.stacked2(8), jnp.zeros((8, 16)),
                           2, mesh=mesh, schedule="2f2b")

    def test_interleaved_value_and_grad_matches_sequential(self):
        # True 1F1B: loss inside the pipelined region, one interleaved
        # fwd/bwd loop. Loss, stage grads and x cotangent must match plain
        # autodiff of the sequential stack.
        mesh = make_mesh((1, 8), ("data", "pipe"))
        S, d, dh = 8, 8, 16
        params = self.stacked2(S, d=d, dh=dh)
        x = jax.random.normal(jax.random.PRNGKey(5), (16, d))
        tgt = jax.random.normal(jax.random.PRNGKey(6), (16, d))

        def loss_head(o, t):
            return jnp.mean((o - t) ** 2)

        loss, grads, gx = jax.jit(
            lambda p, xx, tt: pipeline_value_and_grad(
                self.two_layer_stage, p, xx, loss_head, 4, targets=tt,
                mesh=mesh)
        )(params, x, tgt)

        def seq_loss(p, xx):
            out = xx
            for s in range(S):
                out = self.two_layer_stage(
                    jax.tree.map(lambda a: a[s], p), out)
            return jnp.mean((out - tgt) ** 2)

        want_l, (want_g, want_gx) = jax.value_and_grad(
            seq_loss, argnums=(0, 1))(params, x)
        np.testing.assert_allclose(float(loss), float(want_l), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            (grads, gx), (want_g, want_gx))

    def test_memory_shapes_of_the_three_schedules(self):
        # Compiled HLO buffer stats (VERDICT r2 #8 done-criterion):
        #   (a) gpipe-autodiff temp memory grows with n_micro (per-tick
        #       residuals) — the control showing the contrast is real;
        #   (b) the 1f1b backward saves only stage-boundary inputs — far
        #       smaller temp at large n_micro;
        #   (c) the interleaved loop's temp stays FLAT in n_micro: live
        #       activations are the O(S) ring buffer, the 1F1B property.
        mesh = make_mesh((1, 8), ("data", "pipe"))
        params = self.stacked2(8)

        def temp_bytes(f, *args):
            c = jax.jit(f).lower(*args).compile()
            return c.memory_analysis().temp_size_in_bytes

        def measure(n_micro):
            x = jax.random.normal(jax.random.PRNGKey(7), (n_micro * 4, 16))

            def lg(p, xx):
                return jnp.sum(pipeline_apply(
                    self.two_layer_stage, p, xx, n_micro, mesh=mesh) ** 2)

            def l1(p, xx):
                return jnp.sum(pipeline_apply(
                    self.two_layer_stage, p, xx, n_micro, mesh=mesh,
                    schedule="1f1b") ** 2)

            tg = temp_bytes(jax.grad(lg), params, x)
            t1 = temp_bytes(jax.grad(l1), params, x)
            ti = temp_bytes(
                lambda p, xx: pipeline_value_and_grad(
                    self.two_layer_stage, p, xx,
                    lambda o: jnp.mean(o ** 2), n_micro, mesh=mesh),
                params, x)
            return tg, t1, ti

        tg8, t18, ti8 = measure(8)
        tg32, t132, ti32 = measure(32)
        assert tg32 > 2 * tg8          # (a) control: gpipe grows ~linearly
        assert t132 < tg32 / 2         # (b) 1f1b backward is much leaner
        assert ti32 < 1.1 * ti8        # (c) interleaved: O(S), flat in n_micro


class TestPipelineRemat:
    def test_remat_stages_identical_math(self):
        # jax.checkpoint changes memory, never values: forward and grads
        # must match the non-remat pipeline bit-for-bit.
        mesh = make_mesh((2, 4), ("data", "pipe"))
        params = TestPipeline().stacked(4, d=8)
        x = jax.random.normal(jax.random.PRNGKey(7), (8, 8))

        def loss(p, remat):
            return jnp.sum(pipeline_apply(
                TestPipeline.stage_fn, p, x, 4, mesh=mesh,
                remat_stages=remat) ** 2)

        base = jax.jit(lambda p: loss(p, False))(params)
        rem = jax.jit(lambda p: loss(p, True))(params)
        np.testing.assert_allclose(float(base), float(rem), rtol=1e-6)
        gb = jax.jit(jax.grad(lambda p: loss(p, False)))(params)
        gr = jax.jit(jax.grad(lambda p: loss(p, True)))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
            gb, gr)


class TestPipelineTrainStep:
    """PipelineTrainStep / AutoDist.build_pipeline: the first-class PP
    train-step surface. Oracle: the same update math computed sequentially
    (no pipe axis) must match the pipelined 2x4 data x pipe mesh run."""

    @staticmethod
    def _problem():
        d, pipe = 8, 4
        k = jax.random.split(jax.random.PRNGKey(3), 3)
        params = {"w": jax.random.normal(k[0], (pipe, d, d)) * 0.3,
                  "b": jnp.zeros((pipe, d))}
        x = jax.random.normal(k[1], (16, d))
        tgt = jax.random.normal(k[2], (16, d))
        return params, x, tgt

    @staticmethod
    def _stage(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    @staticmethod
    def _loss_head(o, t):
        return jnp.mean((o - t) ** 2)

    def _make_step(self, mesh_dict):
        import optax

        from autodist_tpu.api import AutoDist
        from autodist_tpu.resource_spec import ResourceSpec

        AutoDist.reset_default()
        n = int(np.prod(list(mesh_dict.values())))
        ad = AutoDist(
            resource_spec=ResourceSpec(resource_dict={
                "nodes": [{"address": "localhost", "chips": n, "chief": True}],
                "mesh": mesh_dict,
            }),
            mesh_axes=tuple(mesh_dict),
        )
        return ad.build_pipeline(
            self._stage, self._loss_head, n_microbatches=4,
            optimizer=optax.sgd(0.1), donate_state=False)

    def test_matches_sequential_oracle(self):
        import optax

        params, x, tgt = self._problem()
        step = self._make_step({"data": 2, "pipe": 4})
        state = step.init(params)
        state, m = step(state, (x, tgt))
        assert np.isfinite(float(m["loss"]))

        # Oracle: plain autodiff through the sequential stage scan.
        def loss_fn(p, xx, tt):
            def body(h, sp):
                return self._stage(sp, h), None
            out, _ = jax.lax.scan(body, xx, p)
            outs = out.reshape((4, 4) + out.shape[1:])
            tts = tt.reshape((4, 4) + tt.shape[1:])
            return jnp.mean(jax.vmap(self._loss_head)(outs, tts))

        tx = optax.sgd(0.1)
        grads = jax.grad(loss_fn)(params, x, tgt)
        upd, _ = tx.update(grads, tx.init(params), params)
        want = optax.apply_updates(params, upd)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(state.params["w"])),
            np.asarray(want["w"]), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(
            float(m["loss"]), float(loss_fn(params, x, tgt)), rtol=1e-5)

    def test_windowed_run_and_evaluate(self):
        params, x, tgt = self._problem()
        step = self._make_step({"data": 2, "pipe": 4})
        state = step.init(params)
        ev0 = float(step.evaluate(state, (x, tgt))["loss"])
        state, m = step.run(state, (x, tgt), 3)
        assert m["loss"].shape == (3,)
        losses = [float(v) for v in np.asarray(m["loss"])]
        assert losses[-1] < losses[0]  # training progresses
        ev1 = float(step.evaluate(state, (x, tgt))["loss"])
        assert ev1 < ev0
        assert int(state.step) == 3

    def test_params_sharded_over_pipe_axis(self):
        params, x, tgt = self._problem()
        step = self._make_step({"data": 2, "pipe": 4})
        state = step.init(params)
        sh = state.params["w"].sharding
        assert sh.spec[0] == "pipe", sh.spec
