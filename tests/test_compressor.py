"""Gradient-compressor tests (reference compressor.py capability).

Numeric contract (c0 methodology): NoneCompressor must be bit-equivalent to
the pure-GSPMD path; cast compressors must approach it within cast tolerance;
error feedback must carry the rounding residual so the *sum over steps* of
applied updates tracks the uncompressed trajectory; PowerSGD must reconstruct
exactly when the gradient is genuinely low-rank.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from helpers import compiled_hlo

from autodist_tpu.kernel import DistributedTrainStep, GraphTransformer, build_mesh
from autodist_tpu.kernel.compressor import (
    HorovodCompressor,
    HorovodCompressorEF,
    NoneCompressor,
    PowerSGDCompressor,
    get_compressor,
)
from autodist_tpu.model_item import ModelItem, OptimizerSpec
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, StrategyCompiler

BATCH, DIN, DOUT = 16, 12, 4


def params0():
    k1, k2 = jax.random.split(jax.random.PRNGKey(123))
    return {"w": jax.random.normal(k1, (DIN, DOUT)), "b": jax.random.normal(k2, (DOUT,))}


def loss_fn(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] + params["b"] - y) ** 2)


def batch0():
    k1, k2 = jax.random.split(jax.random.PRNGKey(456))
    return (jax.random.normal(k1, (BATCH, DIN)), jax.random.normal(k2, (BATCH, DOUT)))


def build_step(compressor: str, lr=0.1):
    spec = ResourceSpec(resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    mesh = build_mesh(spec, axes=("data",))
    params = params0()
    mi = ModelItem.from_params(params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": lr}))
    strategy = AllReduce(compressor=compressor).build(mi, spec)
    compiled = StrategyCompiler(mi).compile(strategy)
    plan = GraphTransformer(compiled, mi, mesh).transform()
    step = DistributedTrainStep(plan, loss_fn, optax.sgd(lr))
    return step, params


def single_device_reference(n_steps=1, lr=0.1):
    params = params0()
    batch = batch0()
    for _ in range(n_steps):
        grads = jax.grad(loss_fn)(params, batch)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return params


def run_steps(compressor, n_steps=1, lr=0.1):
    step, params = build_step(compressor, lr)
    state = step.init(params)
    batch = batch0()
    for _ in range(n_steps):
        state, metrics = step(state, batch)
    return state, metrics


def test_none_compressor_matches_reference():
    state, _ = run_steps("NoneCompressor", n_steps=2)
    ref = single_device_reference(n_steps=2)
    np.testing.assert_allclose(np.asarray(state.params["w"]), np.asarray(ref["w"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.params["b"]), np.asarray(ref["b"]), atol=1e-5)


@pytest.mark.parametrize("name", ["HorovodCompressor", "HorovodCompressorEF"])
def test_cast_compressors_near_reference(name):
    state, metrics = run_steps(name, n_steps=3)
    ref = single_device_reference(n_steps=3)
    # bf16 wire precision: ~3 decimal digits.
    np.testing.assert_allclose(np.asarray(state.params["w"]), np.asarray(ref["w"]), atol=0.05)
    assert np.isfinite(float(metrics["loss"]))


def test_ef_residual_is_populated_and_per_shard():
    state, _ = run_steps("HorovodCompressorEF", n_steps=1)
    res = state.comp_state["w"]["local"]["residual"]
    assert res.shape == (8, DIN, DOUT)
    # Residual = rounding error of bf16 cast: tiny but generically nonzero.
    assert float(jnp.max(jnp.abs(res))) > 0
    assert float(jnp.max(jnp.abs(res))) < 0.1


def test_ef_beats_plain_cast_over_many_steps():
    """Error feedback should track the uncompressed trajectory at least as
    well as plain casting over a longer run."""
    ref = single_device_reference(n_steps=20)
    ef, _ = run_steps("HorovodCompressorEF", n_steps=20)
    plain, _ = run_steps("HorovodCompressor", n_steps=20)
    err_ef = float(jnp.linalg.norm(ef.params["w"] - ref["w"]))
    err_plain = float(jnp.linalg.norm(plain.params["w"] - ref["w"]))
    assert err_ef <= err_plain * 1.5  # EF must not be meaningfully worse
    assert err_ef < 0.05


def test_powersgd_exact_on_lowrank():
    """A rank-1 gradient matrix must round-trip exactly (up to float) through
    rank-2 PowerSGD once the power iteration aligns — single-worker psum."""
    comp = PowerSGDCompressor(rank=2)
    from autodist_tpu.model_item import VarItem

    var = VarItem(name="m", shape=(8, 6), dtype="float32")
    local = comp.init_local(var)
    shared = comp.init_shared(var)
    u = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    v = jnp.linspace(1.0, 2.0, 6).reshape(1, 6)
    g = u @ v

    def one(g, local, shared):
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        from autodist_tpu.utils.compat import shard_map

        f = shard_map(
            lambda g, l, s: comp.step(g, l, s, axis="data", nshards=1),
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),) * 3,
            out_specs=(jax.sharding.PartitionSpec(),) * 3,
            axis_names={"data"},
            check_vma=False,
        )
        return f(g, local, shared)

    # A few power iterations converge the basis; residual feeds back.
    for _ in range(3):
        approx, local, shared = one(g, local, shared)
    np.testing.assert_allclose(np.asarray(approx), np.asarray(g), atol=1e-4)
    assert float(jnp.linalg.norm(local["residual"])) < 1e-4


def test_powersgd_end_to_end_trains():
    state, metrics = run_steps("PowerSGDCompressor", n_steps=5)
    assert np.isfinite(float(metrics["loss"]))
    # Loss must decrease vs. the first step on a quadratic objective.
    first_loss = float(run_steps("PowerSGDCompressor", n_steps=1)[1]["loss"])
    assert float(metrics["loss"]) < first_loss


def test_wire_factor_formula():
    # Rank/shape-aware wire pricing (VERDICT r2 #9): the factor is computed
    # from the actual payloads the compressor's collectives carry.
    from autodist_tpu.strategy.cost_model import compressor_wire_factor

    ps = PowerSGDCompressor(rank=2)
    m, k = 256, 64
    assert ps.wire_factor((m, k)) == pytest.approx((m + k) * 2 / (m * k))
    # Higher-rank tensors flatten trailing dims into k.
    assert ps.wire_factor((m, 8, 8)) == pytest.approx((m + 64) * 2 / (m * 64))
    # Rank clamps to the matrix dims; vectors take the dense psum path.
    assert PowerSGDCompressor(rank=8).wire_factor((4, 2)) == pytest.approx(
        (4 + 2) * 2 / 8)
    assert ps.wire_factor((128,)) == 1.0
    # Tiny matrices honestly price WORSE than dense — not clamped to 1.
    assert PowerSGDCompressor(rank=2).wire_factor((2, 2)) == pytest.approx(2.0)
    assert HorovodCompressor().wire_factor((m, k)) == pytest.approx(0.5)
    assert NoneCompressor().wire_factor((m, k)) == 1.0
    # The cost model routes through the registry by IR name.
    assert compressor_wire_factor("PowerSGDCompressor", (m, k)) == (
        pytest.approx((m + k) * 2 / (m * k)))
    assert compressor_wire_factor(None, (m, k)) == 1.0


def test_powersgd_collective_payloads_match_wire_factor():
    """The compiled HLO's collectives must carry the rank-r factor
    payloads the wire factor prices — (m·r) and (k·r) element arrays —
    never the dense m×k gradient (the analog of test_sparse_wire's table
    assertion). Control: NoneCompressor's program DOES carry the dense
    payload, proving the inspection sees what it claims to."""
    from test_sparse_wire import _collective_sizes

    m, k, rank = 256, 64, 2
    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    mesh = build_mesh(spec, axes=("data",))
    kp = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(kp, (m, k))}

    def mat_loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    batch = (jax.random.normal(kp, (BATCH, m)), jax.random.normal(kp, (BATCH, k)))

    def hlo_sizes(compressor):
        mi = ModelItem.from_params(
            params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": 0.1}))
        strategy = AllReduce(compressor=compressor).build(mi, spec)
        plan = GraphTransformer(
            StrategyCompiler(mi).compile(strategy), mi, mesh).transform()
        step = DistributedTrainStep(plan, mat_loss, optax.sgd(0.1))
        state = step.init(params)
        hlo = compiled_hlo(step, state, batch)
        return _collective_sizes(hlo)

    dense = m * k
    factor_cap = max(m, k) * rank  # largest factor psum payload
    ps_sizes = hlo_sizes("PowerSGDCompressor")
    assert ps_sizes, "expected collectives in the compressed step"
    assert max(ps_sizes) <= factor_cap, (
        f"PowerSGD collective carries {max(ps_sizes)} elems "
        f"(> factor cap {factor_cap}; dense={dense})")
    none_sizes = hlo_sizes("NoneCompressor")
    assert max(none_sizes) >= dense  # control: dense psum is visible


def test_registry_and_unknown():
    assert isinstance(get_compressor("NoneCompressor"), NoneCompressor)
    assert isinstance(get_compressor("HorovodCompressor"), HorovodCompressor)
    assert isinstance(get_compressor("HorovodCompressorEF"), HorovodCompressorEF)
    assert isinstance(get_compressor("PowerSGDCompressor"), PowerSGDCompressor)
    with pytest.raises(ValueError):
        get_compressor("Gzip")


def test_compressed_path_with_sparse_embedding_matches_oracle():
    """A row-sharded (data-axis) embedding must survive the compressed
    shard_map: params enter the manual region replicated, so the global
    jnp.take indexes the full table. Regression for the r2 review finding
    where the table entered row-sliced and training went NaN."""
    import numpy as np
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    VOCAB, EDIM, BATCH = 64, 8, 32

    def loss_fn(params, batch):
        ids, y = batch
        x = jnp.take(params["embedding"], ids, axis=0)
        pred = (x @ params["w"]).squeeze(-1)
        return jnp.mean((pred - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    params = {
        "embedding": jax.random.normal(k1, (VOCAB, EDIM)),
        "w": jax.random.normal(k2, (EDIM, 1)),
    }
    batch = (
        jax.random.randint(k3, (BATCH,), 0, VOCAB),
        jax.random.normal(k1, (BATCH,)),
    )
    rs = ResourceSpec(
        resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]}
    )
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch
    )
    assert mi.sparse_variables
    strategy = StrategyCompiler(mi).compile(
        AllReduce(compressor="HorovodCompressor").build(mi, rs)
    )
    plan = GraphTransformer(strategy, mi, build_mesh(rs)).transform()
    # The table must be row-sharded for this to regress the finding.
    assert plan.plan_for("embedding").pspec[0] is not None
    step = DistributedTrainStep(plan, loss_fn, opt.make())
    state = step.init(params)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # Oracle: single-device full-batch step. The dense var w is bf16-cast
    # compressed (lossy); the sparse var skips compression, so the table
    # update must match tightly and w loosely.
    tx = opt.make()
    grads = jax.grad(loss_fn)(params, batch)
    updates, _ = tx.update(grads, tx.init(params), params)
    import optax

    expected = optax.apply_updates(params, updates)
    got = jax.device_get(step.logical_params(new_state))
    np.testing.assert_allclose(
        np.asarray(got["embedding"]),
        np.asarray(expected["embedding"]),
        rtol=2e-5, atol=2e-6,
    )
    np.testing.assert_allclose(
        np.asarray(got["w"]), np.asarray(expected["w"]), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("name", [
    "HorovodCompressor", "HorovodCompressorEF", "PowerSGDCompressor",
])
def test_compression_on_data_model_mesh(name):
    """Compression must survive a mixed data×model mesh (VERDICT r1 next
    #7): the compressed sync runs partial-manual over the data axis with
    the model axis left to GSPMD, instead of silently disabling itself."""
    import numpy as np
    import optax
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w1"])
        out = h @ params["w2"]
        return jnp.mean((out[:, 0] - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    params = {
        "w1": jax.random.normal(k1, (16, 32)) * 0.3,
        "w2": jax.random.normal(k2, (32, 16)) * 0.3,
    }
    batch = (jax.random.normal(k3, (32, 16)), jax.random.normal(k1, (32,)))
    rs = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
        "mesh": {"data": 4, "model": 2},
    })
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch)
    strategy = StrategyCompiler(mi).compile(
        AllReduce(compressor=name).build(mi, rs))
    plan = GraphTransformer(strategy, mi, build_mesh(rs, axes=("data", "model"))).transform()
    step = DistributedTrainStep(plan, loss_fn, opt.make())
    # The compressors must actually be active — not silently dropped.
    assert set(step._compressors) == {"w1", "w2"}
    state = step.init(params)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))

    # Oracle: single-device step. On the CPU backend the cast compressors
    # fall back to f32 wire (XLA CPU cannot compile bf16 collectives in a
    # partial-manual region), so Horovod* match tightly; PowerSGD is a
    # genuine low-rank approximation — only sanity-check trajectory.
    tx = opt.make()
    grads = jax.grad(loss_fn)(params, batch)
    updates, _ = tx.update(grads, tx.init(params), params)
    expected = optax.apply_updates(params, updates)
    got = jax.device_get(step.logical_params(new_state))
    if name != "PowerSGDCompressor":
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, jax.device_get(expected))
    else:
        state2, metrics2 = step(new_state, batch)
        assert float(metrics2["loss"]) < float(metrics["loss"]) * 1.05


def test_compression_on_data_model_mesh_with_tp_sharded_vars():
    """Partitioned AllReduce vars (param sharded on the model axis) keep
    their shardings through the partial-manual compressed region."""
    import numpy as np
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.base import StrategyCompiler
    from autodist_tpu.strategy.ir import AllReduceSynchronizer, NodeConfig
    from autodist_tpu.strategy.base import StrategyBuilder

    def loss_fn(params, batch):
        x, y = batch
        h = jnp.tanh(x @ params["w1"])
        out = h @ params["w2"]
        return jnp.mean((out[:, 0] - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    params = {
        "w1": jax.random.normal(k1, (16, 32)) * 0.3,
        "w2": jax.random.normal(k2, (32, 16)) * 0.3,
    }
    batch = (jax.random.normal(k3, (32, 16)), jax.random.normal(k1, (32,)))
    rs = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
        "mesh": {"data": 4, "model": 2},
    })
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch)

    class _TPCompressed(StrategyBuilder):
        def build(self, model_item, resource_spec):
            s = self._new_strategy(resource_spec)
            s.node_config = [
                NodeConfig(
                    var_name=v.name,
                    synchronizer=AllReduceSynchronizer(
                        compressor="HorovodCompressorEF"),
                    partitioner=("1,2" if v.name == "w1" else "2,1"),
                )
                for v in model_item.trainable_variables
            ]
            return s

    strategy = StrategyCompiler(mi).compile(_TPCompressed().build(mi, rs))
    plan = GraphTransformer(strategy, mi, build_mesh(rs, axes=("data", "model"))).transform()
    from jax.sharding import PartitionSpec as P
    assert plan.plan_for("w1").pspec == P(None, "model")
    assert plan.plan_for("w2").pspec == P("model", None)
    step = DistributedTrainStep(plan, loss_fn, opt.make())
    assert set(step._compressors) == {"w1", "w2"}
    state = step.init(params)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    import optax
    tx = opt.make()
    grads = jax.grad(loss_fn)(params, batch)
    updates, _ = tx.update(grads, tx.init(params), params)
    expected = optax.apply_updates(params, updates)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        jax.device_get(step.logical_params(new_state)),
        jax.device_get(expected))


def test_compression_with_grad_accumulation_matches_oracle():
    """grad_accum_steps and compression now compose: microbatching runs
    inside the compressed manual region, one compressed collective per
    step (r2 — the combination used to raise)."""
    import numpy as np
    import optax
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean(((x @ params["w"])[:, 0] - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    params = {"w": jax.random.normal(k1, (16, 4)) * 0.3}
    # 32 rows / 8 shards = 4 per shard, splits into 2 microbatches of 2.
    batch = (jax.random.normal(k2, (32, 16)), jax.random.normal(k3, (32,)))
    rs = ResourceSpec(
        resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch)
    strategy = StrategyCompiler(mi).compile(
        AllReduce(compressor="HorovodCompressorEF").build(mi, rs))
    plan = GraphTransformer(strategy, mi, build_mesh(rs)).transform()
    step = DistributedTrainStep(plan, loss_fn, opt.make(), grad_accum_steps=2)
    assert step._compressors
    state = step.init(params)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # Loss metric equals the full-batch loss at the old params.
    np.testing.assert_allclose(
        float(metrics["loss"]), float(loss_fn(params, batch)), rtol=1e-5)
    # bf16-compressed grads: loose tolerance vs the dense oracle.
    tx = opt.make()
    grads = jax.grad(loss_fn)(params, batch)
    updates, _ = tx.update(grads, tx.init(params), params)
    expected = optax.apply_updates(params, updates)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(new_state.params["w"])),
        np.asarray(expected["w"]), rtol=2e-2, atol=2e-2)


def test_compression_with_accum_rejects_indivisible_microbatch():
    import numpy as np
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler
    import optax

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean(((x @ params["w"])[:, 0] - y) ** 2)

    params = {"w": jnp.zeros((16, 4))}
    batch = (jnp.zeros((24, 16)), jnp.zeros((24,)))  # 24/8 = 3, not % 2
    rs = ResourceSpec(
        resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch)
    strategy = StrategyCompiler(mi).compile(
        AllReduce(compressor="HorovodCompressor").build(mi, rs))
    plan = GraphTransformer(strategy, mi, build_mesh(rs)).transform()
    step = DistributedTrainStep(plan, loss_fn, optax.sgd(0.1), grad_accum_steps=2)
    state = step.init(params)
    with pytest.raises(ValueError, match="microbatches"):
        step(state, batch)


def test_compression_accum_tolerates_replicated_batch_leaves():
    """A broadcast leaf (attention-mask shape (1, S)) rides through the
    compressed+accumulated region whole — it must be neither validated
    against nor split along its leading dim (r2 review)."""
    import numpy as np
    import optax
    from autodist_tpu.kernel.lowering import DistributedTrainStep, GraphTransformer
    from autodist_tpu.kernel.mesh import build_mesh
    from autodist_tpu.model_item import ModelItem, OptimizerSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.all_reduce_strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    def loss_fn(params, batch):
        x, mask, y = batch["x"], batch["mask"], batch["y"]
        h = (x * mask) @ params["w"]
        return jnp.mean((h[:, 0] - y) ** 2)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    params = {"w": jax.random.normal(k1, (16, 4)) * 0.3}
    batch = {
        "x": jax.random.normal(k2, (32, 16)),
        "mask": jnp.ones((1, 16)),  # leading dim 1: replicated leaf
        "y": jax.random.normal(k3, (32,)),
    }
    rs = ResourceSpec(
        resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    opt = OptimizerSpec("sgd", {"learning_rate": 0.1})
    mi = ModelItem.from_params(
        params, optimizer_spec=opt, loss_fn=loss_fn, example_batch=batch)
    strategy = StrategyCompiler(mi).compile(
        AllReduce(compressor="HorovodCompressor").build(mi, rs))
    plan = GraphTransformer(strategy, mi, build_mesh(rs)).transform()
    step = DistributedTrainStep(plan, loss_fn, optax.sgd(0.1), grad_accum_steps=2)
    state = step.init(params)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def _run_topk_shardwise(comp, grads, n_shards):
    """Shared harness: run comp.step per data shard over a [n_shards, N]
    gradient stack; returns (synced [n_shards, N], local_state)."""
    from autodist_tpu.model_item import VarItem

    var = VarItem(name="g", shape=grads.shape[1:], dtype="float32")
    local = jax.tree.map(
        lambda x: jnp.tile(x[None], (n_shards, 1)), comp.init_local(var))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n_shards]), ("data",))
    P = jax.sharding.PartitionSpec

    def shardwise(g, l):
        out, l2, _ = comp.step(
            g[0], jax.tree.map(lambda x: x[0], l), {}, axis="data",
            nshards=n_shards)
        return out[None], jax.tree.map(lambda x: x[None], l2)

    from autodist_tpu.utils.compat import shard_map

    f = shard_map(
        shardwise, mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
        axis_names={"data"}, check_vma=False,
    )
    return f(grads, local)


def test_topk_full_ratio_matches_dense_psum():

    """ratio=1.0 selects everything: TopK must reproduce the dense psum
    mean exactly (the sparsifier's correctness anchor)."""
    from autodist_tpu.kernel.compressor import TopKCompressor

    comp = TopKCompressor(ratio=1.0, min_size=1)
    n_shards, n_elems = 4, 32
    grads = jax.random.normal(jax.random.PRNGKey(7), (n_shards, n_elems))
    out, local2 = _run_topk_shardwise(comp, grads, n_shards)
    expected = jnp.mean(grads, axis=0)
    for s in range(n_shards):
        # rtol covers psum-vs-mean reassociation: an all-reduce may sum in
        # a different order than jnp.mean, which moves a couple of
        # near-cancelling elements by a few ulp.
        np.testing.assert_allclose(np.asarray(out[s]), np.asarray(expected),
                                   rtol=1e-5)
    # Full selection leaves no residual.
    np.testing.assert_allclose(np.asarray(local2["residual"]), 0.0, atol=1e-7)


def test_topk_disjoint_supports_union():
    """Two workers picking disjoint entries must land both contributions,
    each averaged over the worker count (dense-psum semantics restricted
    to the union support); everything unselected goes to the residual."""
    from autodist_tpu.kernel.compressor import TopKCompressor

    comp = TopKCompressor(ratio=0.25, min_size=1)  # k = 2 of 8
    g0 = jnp.array([10.0, -9.0, 0.1, 0.2, 0.0, 0.0, 0.3, 0.1])
    g1 = jnp.array([0.1, 0.2, -8.0, 7.0, 0.0, 0.1, 0.0, 0.2])
    grads = jnp.stack([g0, g1])
    out, local2 = _run_topk_shardwise(comp, grads, 2)
    expected = jnp.array([10.0, -9.0, -8.0, 7.0, 0, 0, 0, 0]) / 2.0
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expected),
                               rtol=1e-6)
    # Residuals carry exactly the unselected mass, per worker.
    np.testing.assert_allclose(np.asarray(local2["residual"][0]),
                               np.asarray(g0).copy() * (np.abs(g0) < 9.0),
                               rtol=1e-6)


@pytest.mark.slow
def test_topk_ef_end_to_end_trains():
    """Full pipeline: AllReduce(compressor=TopK) on an 8192-element weight
    (above min_size, so real sparsification) still trains the quadratic."""
    m, k = 128, 64
    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    mesh = build_mesh(spec, axes=("data",))
    kp = jax.random.PRNGKey(3)
    params = {"w": jax.random.normal(kp, (m, k)) * 0.1}

    def mat_loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    batch = (jax.random.normal(kp, (BATCH, m)), jax.random.normal(kp, (BATCH, k)))
    mi = ModelItem.from_params(
        params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": 0.05}))
    strategy = AllReduce(compressor="TopKCompressor").build(mi, spec)
    plan = GraphTransformer(
        StrategyCompiler(mi).compile(strategy), mi, mesh).transform()
    step = DistributedTrainStep(plan, mat_loss, optax.sgd(0.05))
    state = step.init(params)
    losses = []
    for _ in range(12):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    # 1% density updates ~82 of 8192 coords per step (plus EF ramp-up):
    # expect steady but modest decrease, not the dense rate.
    assert losses[-1] < losses[0] * 0.95, losses
    assert losses[-1] < losses[len(losses) // 2], losses  # still descending


def test_topk_wire_factor_and_aliases():
    from autodist_tpu.kernel.compressor import TopKCompressor
    from autodist_tpu.strategy.cost_model import compressor_wire_factor

    tk = TopKCompressor(ratio=0.01, min_size=4096)
    n_elems = 128 * 64
    k = max(1, int(n_elems * 0.01))
    # Gather payload grows with the group: factor = k*n/N.
    assert tk.wire_factor((128, 64), nshards=8) == pytest.approx(k * 8 / n_elems)
    assert tk.wire_factor((128, 64)) == pytest.approx(k / n_elems)
    # Below min_size the dense psum path runs.
    assert tk.wire_factor((16, 16), nshards=8) == 1.0
    # Enough workers price the gathered pairs above dense — not clamped.
    assert TopKCompressor(ratio=0.5, min_size=1).wire_factor(
        (64,), nshards=4) == pytest.approx(2.0)
    # Cost-model routing passes the group size through.
    assert compressor_wire_factor("TopKCompressor", (128, 64), 8) == (
        pytest.approx(k * 8 / n_elems))
    # Friendly aliases resolve.
    from autodist_tpu.kernel.compressor import (
        HorovodCompressor, HorovodCompressorEF, PowerSGDCompressor)
    assert isinstance(get_compressor("bf16"), HorovodCompressor)
    assert isinstance(get_compressor("ef"), HorovodCompressorEF)
    assert isinstance(get_compressor("powersgd"), PowerSGDCompressor)
    assert isinstance(get_compressor("topk"), TopKCompressor)


def test_topk_collective_payloads_match_wire_factor():
    """The compiled HLO must carry k-element gather payloads, never the
    dense 8192-element gradient (same methodology as the PowerSGD payload
    test)."""
    from test_sparse_wire import _collective_sizes

    m, k = 128, 64
    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    mesh = build_mesh(spec, axes=("data",))
    kp = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(kp, (m, k))}

    def mat_loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    batch = (jax.random.normal(kp, (BATCH, m)), jax.random.normal(kp, (BATCH, k)))
    mi = ModelItem.from_params(
        params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": 0.1}))
    strategy = AllReduce(compressor="TopKCompressor").build(mi, spec)
    plan = GraphTransformer(
        StrategyCompiler(mi).compile(strategy), mi, mesh).transform()
    step = DistributedTrainStep(plan, mat_loss, optax.sgd(0.1))
    state = step.init(params)
    hlo = compiled_hlo(step, state, batch)
    sizes = _collective_sizes(hlo)
    assert sizes, "expected collectives in the compressed step"
    dense = m * k
    topk_elems = max(1, int(dense * 0.01))
    gather_cap = 8 * topk_elems  # all-gather output: n_shards x k
    assert max(sizes) <= gather_cap, (
        f"TopK collective carries {max(sizes)} elems "
        f"(> gather cap {gather_cap}; dense={dense})")


def test_none_alias_is_a_true_noop():
    """compressor='none' must behave exactly like 'NoneCompressor': no
    compressed shard_map region, identical HLO, identical cost ranking —
    an active-but-identity region would make data-axis-sharded vars pay
    full-size wire (the lowering warning's hazard)."""
    from test_sparse_wire import _collective_sizes
    from autodist_tpu.strategy.cost_model import CostModel

    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    mesh = build_mesh(spec, axes=("data",))
    params = params0()

    def program(compressor):
        mi = ModelItem.from_params(
            params, optimizer_spec=OptimizerSpec("sgd", {"learning_rate": 0.1}))
        strategy = AllReduce(compressor=compressor).build(mi, spec)
        plan = GraphTransformer(
            StrategyCompiler(mi).compile(strategy), mi, mesh).transform()
        step = DistributedTrainStep(plan, loss_fn, optax.sgd(0.1))
        state = step.init(params)
        batch = batch0()
        hlo = compiled_hlo(step, state, batch)
        cost = CostModel(mi, spec).strategy_cost(strategy)
        return _collective_sizes(hlo), cost.total_s

    sizes_canonical, cost_canonical = program("NoneCompressor")
    sizes_alias, cost_alias = program("none")
    assert sizes_alias == sizes_canonical
    assert cost_alias == pytest.approx(cost_canonical)


def test_topk_decomposition_property_randomized():
    """Property over random inputs/shard counts: per worker,
    selected + residual == input exactly, and the synced output equals
    the scatter-add mean of all selections (TopK's conservation law)."""
    from autodist_tpu.kernel.compressor import TopKCompressor

    rng = np.random.default_rng(0)
    for trial in range(5):
        n_shards = int(rng.choice([2, 4, 8]))
        n_elems = int(rng.choice([16, 64, 256]))
        ratio = float(rng.choice([0.1, 0.25, 0.5]))
        comp = TopKCompressor(ratio=ratio, min_size=1)
        grads = jnp.asarray(rng.normal(size=(n_shards, n_elems)), jnp.float32)
        out, local2 = _run_topk_shardwise(comp, grads, n_shards)
        selected = np.asarray(grads) - np.asarray(local2["residual"])
        # Conservation: what was synced is exactly the mean of selections.
        np.testing.assert_allclose(
            np.asarray(out[0]), selected.sum(axis=0) / n_shards,
            rtol=1e-5, atol=1e-6,
            err_msg=f"trial {trial}: n={n_shards} N={n_elems} r={ratio}")
        # Every shard sees the identical synced tensor.
        for sh in range(1, n_shards):
            np.testing.assert_array_equal(np.asarray(out[sh]), np.asarray(out[0]))
        # Selection size: each worker contributed exactly k entries.
        k = max(1, int(n_elems * ratio))
        assert (np.count_nonzero(selected, axis=1) <= k).all()
