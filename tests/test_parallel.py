"""Sequence-parallel attention tests: ring + ulysses vs dense reference.

Numeric-assertion methodology per SURVEY.md §4: exact comparisons against the
O(s^2) reference on an 8-device CPU mesh, forward AND gradients, causal and
full, including meshes where seq shares the device budget with data.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu.ops.flash_attention import mha_reference
from autodist_tpu.parallel import ring_attention, ulysses_attention


def make_mesh(shape, names):
    return Mesh(np.array(jax.devices()).reshape(shape), names)


def qkv(b=2, s=64, h=4, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_seq_parallel_matches_reference_forward(causal, impl):
    mesh = make_mesh((2, 4), ("data", "seq"))
    q, k, v = qkv()
    want = mha_reference(q, k, v, causal=causal)
    got = jax.jit(lambda a, b_, c: impl(a, b_, c, causal=causal, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", [ring_attention, ulysses_attention])
def test_seq_parallel_matches_reference_grads(causal, impl):
    mesh = make_mesh((8,), ("seq",))
    q, k, v = qkv(s=32, h=8)  # heads divisible by seq axis for ulysses
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, jnp.float32)

    def loss_ref(q_, k_, v_):
        return jnp.sum(mha_reference(q_, k_, v_, causal=causal) * g)

    def loss_sp(q_, k_, v_):
        return jnp.sum(impl(q_, k_, v_, causal=causal, mesh=mesh) * g)

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(q, k, v)
    for w, got_g, name in zip(want, got, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(w), atol=5e-5, rtol=5e-5,
            err_msg=f"d{name} mismatch",
        )


def test_ring_with_sharded_inputs():
    """Inputs already sharded batch×seq stay consistent (GSPMD composition)."""
    mesh = make_mesh((2, 4), ("data", "seq"))
    q, k, v = qkv(b=4, s=64)
    shard = NamedSharding(mesh, P("data", "seq", None, None))
    qs, ks, vs = (jax.device_put(x, shard) for x in (q, k, v))
    want = mha_reference(q, k, v, causal=True)
    got = jax.jit(lambda a, b_, c: ring_attention(a, b_, c, causal=True, mesh=mesh))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_trivial_seq_axis_falls_back():
    """Mesh without a seq axis: ring == flash fallback, no shard_map."""
    mesh = make_mesh((8,), ("data",))
    q, k, v = qkv(s=32)
    got = ring_attention(q, k, v, causal=False, mesh=mesh)
    want = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = make_mesh((8,), ("seq",))
    q, k, v = qkv(s=32, h=4)  # 4 heads, 8-way seq axis
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(lambda a, b_, c: ulysses_attention(a, b_, c, mesh=mesh))(q, k, v)


def test_ring_nondivisible_seq_raises():
    mesh = make_mesh((1, 8), ("data", "seq"))
    q, k, v = qkv(s=36)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh=mesh)


def test_transformer_ring_impl_end_to_end():
    """Flagship model trains a step with ring attention over a seq axis."""
    from autodist_tpu.api import AutoDist
    from autodist_tpu.models import get_model
    from autodist_tpu.resource_spec import ResourceSpec
    import autodist_tpu.strategy as S

    AutoDist.reset_default()
    try:
        ad = AutoDist(
            resource_spec=ResourceSpec(resource_dict={
                "nodes": [{"address": "localhost", "chips": 8, "chief": True}],
                "mesh": {"data": 2, "seq": 4},
            }),
            strategy_builder=S.AllReduce(),
            mesh_axes=("data", "seq"),
        )
        model = get_model(
            "transformer", vocab_size=64, num_layers=1, d_model=32,
            num_heads=4, d_ff=64, max_seq_len=32, attention_impl="ring",
        )
        params = model.init(jax.random.PRNGKey(0))
        batch = model.example_batch(4)
        step = ad.build(model.loss_fn, params, batch)
        state = step.init(params)
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    finally:
        AutoDist.reset_default()
