"""Host-offload (weight streaming) tests.

The reference's PS strategies park variables on host CPUs
(ps_strategy.py:38-55); the TPU rendering stores them in pinned host memory
and streams through HBM inside the step. In-jit memory-space transfers need
the TPU toolchain (the CPU runtime has no placement kernel), so on the CPU
test mesh we verify the *plumbing* (plan flags, sharding memory kinds, gate
behavior) and the TPU-only execution test runs on real hardware
(`python -m pytest tests/test_host_offload.py --run-integration` there).
"""
import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

import autodist_tpu.kernel.lowering as lowering
from autodist_tpu.kernel import DistributedTrainStep, GraphTransformer
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
import autodist_tpu.strategy as S


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return ((pred - batch["y"]) ** 2).mean()


def problem():
    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((8, 1)).astype(np.float32),
              "b": np.zeros((1,), np.float32)}
    batch = {"x": rng.standard_normal((16, 8)).astype(np.float32),
             "y": rng.standard_normal((16, 1)).astype(np.float32)}
    return params, batch


def make_plan(builder, host_offload, n_chips=8):
    params, batch = problem()
    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": n_chips, "chief": True}]
    })
    mesh = Mesh(np.array(jax.devices()[:n_chips]).reshape(n_chips), ("data",))
    item = ModelItem.from_params(params)
    compiled = S.StrategyCompiler(item).compile(builder.build(item, spec))
    return GraphTransformer(
        compiled, item, mesh, host_offload=host_offload
    ).transform(), params, batch


def test_gate_disables_offload_off_tpu():
    plan, params, batch = make_plan(S.PS(), host_offload=True)
    if jax.devices()[0].platform == "tpu":
        pytest.skip("gate-off test is for non-TPU backends")
    assert not plan.has_offload
    step = DistributedTrainStep(plan, loss_fn, optax.adam(0.05))
    state = step.init(params)
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_plan_marks_ps_vars_when_forced(monkeypatch):
    """Plumbing check: with the gate forced open, PS vars (and their
    optimizer slots) carry pinned_host shardings; AllReduce vars don't."""
    monkeypatch.setattr(lowering, "_memory_kinds_supported", lambda mesh: True)
    plan, params, batch = make_plan(S.PSLoadBalancing(), host_offload=True)
    assert plan.has_offload
    assert all(p.offload for p in plan.var_plans.values())
    shardings = plan.params_shardings(params)
    assert shardings["w"].memory_kind == "pinned_host"
    # device view strips the host placement (what compute uses).
    dev_shardings = plan.params_shardings(params, device_view=True)
    assert dev_shardings["w"].memory_kind != "pinned_host"

    opt_shapes = jax.eval_shape(optax.adam(0.05).init, params)
    opt_sh = jax.tree_util.tree_leaves(plan.opt_shardings(opt_shapes))
    assert any(s.memory_kind == "pinned_host" for s in opt_sh)

    ar_plan, _, _ = make_plan(S.AllReduce(), host_offload=True)
    assert not ar_plan.has_offload


@pytest.mark.integration
def test_offloaded_matches_resident_on_tpu():
    """Real-hardware numeric equivalence (run on a TPU host)."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs TPU")
    step_h_plan, params, batch = make_plan(S.PSLoadBalancing(), True, n_chips=1)
    assert step_h_plan.has_offload
    step_h = DistributedTrainStep(step_h_plan, loss_fn, optax.adam(0.05))
    state = step_h.init(params)
    assert state.params["w"].sharding.memory_kind == "pinned_host"
    for _ in range(5):
        state, m_h = step_h(state, batch)
    assert state.params["w"].sharding.memory_kind == "pinned_host"
    w_h = np.asarray(jax.device_get(state.params["w"]))

    step_d_plan, params, batch = make_plan(S.PSLoadBalancing(), False, n_chips=1)
    step_d = DistributedTrainStep(step_d_plan, loss_fn, optax.adam(0.05))
    state_d = step_d.init(params)
    for _ in range(5):
        state_d, m_d = step_d(state_d, batch)
    w_d = np.asarray(jax.device_get(state_d.params["w"]))
    np.testing.assert_allclose(w_h, w_d, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(m_h["loss"]), float(m_d["loss"]), rtol=1e-6)


# --------------------------------------------------------------------------- #
# Destination-driven offload (host_offload="from_strategy"; VERDICT r3 #4b)
# --------------------------------------------------------------------------- #
def test_from_strategy_offload_follows_cpu_destinations(monkeypatch):
    """PSLoadBalancing emits host-CPU reduction destinations (reference
    parity), so "from_strategy" offloads exactly those vars."""
    monkeypatch.setattr(lowering, "_memory_kinds_supported", lambda mesh: True)
    plan, params, batch = make_plan(S.PSLoadBalancing(),
                                    host_offload="from_strategy")
    assert plan.has_offload
    assert all(p.offload for p in plan.var_plans.values())


def test_from_strategy_keeps_non_cpu_destinations_in_hbm(monkeypatch):
    from autodist_tpu.strategy.ir import NodeConfig, PSSynchronizer, Strategy

    monkeypatch.setattr(lowering, "_memory_kinds_supported", lambda mesh: True)
    params, batch = problem()
    item = ModelItem.from_params(params)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    nodes = [
        NodeConfig("w", PSSynchronizer(reduction_destination="localhost:TPU:0")),
        NodeConfig("b", PSSynchronizer(reduction_destination="localhost:CPU:0")),
    ]
    plan = GraphTransformer(
        Strategy(node_config=nodes), item, mesh, host_offload="from_strategy"
    ).transform()
    assert not plan.plan_for("w").offload   # TPU destination: stays in HBM
    assert plan.plan_for("b").offload       # CPU destination: pinned host


def test_invalid_offload_mode_rejected():
    params, _ = problem()
    item = ModelItem.from_params(params)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    from autodist_tpu.strategy.ir import Strategy
    with pytest.raises(ValueError, match="host_offload"):
        GraphTransformer(Strategy(), item, mesh, host_offload="always")


def test_from_strategy_shard_table_overrides_node_destination(monkeypatch):
    """Shard destinations are the more specific contract: a stale node-level
    CPU destination must not offload a var whose shards all reduce on TPU."""
    from autodist_tpu.strategy.ir import NodeConfig, PSSynchronizer, Strategy

    monkeypatch.setattr(lowering, "_memory_kinds_supported", lambda mesh: True)
    params, _ = problem()
    item = ModelItem.from_params(params)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    nodes = [
        NodeConfig(
            "w",
            PSSynchronizer(reduction_destination="h:CPU:0"),
            partitioner="2,1",
            part_config=[
                NodeConfig(f"w/part_{i}",
                           PSSynchronizer(reduction_destination="h:TPU:0"))
                for i in range(2)
            ],
        ),
        NodeConfig("b", PSSynchronizer(reduction_destination="h:CPU:0")),
    ]
    plan = GraphTransformer(
        Strategy(node_config=nodes), item, mesh, host_offload="from_strategy"
    ).transform()
    assert not plan.plan_for("w").offload  # shard table (TPU) wins
    assert plan.plan_for("b").offload      # node-level CPU dest still honored
