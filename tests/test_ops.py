"""Flash-attention kernel numerics vs the jnp reference (interpret mode on
CPU exercises the same kernel code paths that compile on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.flash_attention import flash_attention, mha_reference


def _make_qkv(rng, b=2, s=256, h=2, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype)
    k = jax.random.normal(kk, (b, s, h, d), dtype)
    v = jax.random.normal(kv, (b, s, h, d), dtype)
    return q, k, v


def _float32_reference(q, k, v, causal, cotangent):
    """Output and the three gradients of the jnp reference on the float32
    copies of the inputs: what every input dtype is held against."""
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    out, vjp = jax.vjp(lambda *a: mha_reference(*a, causal), q32, k32, v32)
    return out, vjp(cotangent.astype(jnp.float32))


# float32: the tolerances the kernel has always been held to. bfloat16: the
# reference is float32 on the same (bfloat16-rounded) inputs, so the gap is
# the kernel's own rounding: p and ds cast to bfloat16 before their products,
# and a bfloat16 result. Worst readings over the cases below (CPU, interpret
# mode, PR 28): forward 0.0074 absolute where |out| reaches 2.9, gradients
# 0.0137 absolute where |grad| reaches 4.0 (float32: 6.6e-7 and 3.6e-6). The
# bfloat16 tolerances are absolute and about twice those readings.
_TOL = {
    jnp.float32: dict(out=dict(atol=2e-5, rtol=2e-5), grad=dict(atol=5e-4, rtol=5e-4)),
    jnp.bfloat16: dict(out=dict(atol=1.5e-2, rtol=0), grad=dict(atol=3e-2, rtol=0)),
}


@pytest.mark.parametrize("blocks", [(128, 128), (None, None)],
                         ids=["explicit128", "derived"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("seq", [256, 384, 1024])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_forward_and_gradients_match_reference(causal, seq, dtype, blocks):
    """Forward and dQ, dK, dV against the float32 reference, over masked and
    unmasked tiles, one tile and many, float32 and bfloat16 operands, a
    caller's tiles and the derived ones. 384 is a multiple of 128 that the
    largest derived tile does not divide: the tile function must."""
    q, k, v = _make_qkv(jax.random.PRNGKey(seq + causal), b=1, s=seq, dtype=dtype)
    cot = jax.random.normal(jax.random.PRNGKey(7), q.shape, dtype)
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, causal, *blocks), q, k, v)
    grads = vjp(cot)
    ref_out, ref_grads = _float32_reference(q, k, v, causal, cot)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32), ref_out, **tol["out"])
    for got, want, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   **tol["grad"], err_msg=f"d{name} mismatch")


def test_derived_tiles_divide_the_sequence():
    """The tile function answers from the shape alone, with 128-multiples
    that divide the sequence, whatever the cap."""
    from autodist_tpu.ops.flash_attention import _tiles

    for seq in (128, 256, 384, 640, 1024, 1152, 4096):
        for tile in _tiles(seq, 64, jnp.bfloat16):
            assert tile % 128 == 0 and seq % tile == 0 and tile <= seq


def _eqns(jaxpr, primitive):
    """Every equation of one primitive under a jaxpr, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_eqns(sub, primitive))
    return found


def test_every_kernel_product_takes_bfloat16_operands():
    """The mechanism: on bfloat16 inputs all seven products of the three
    kernels (two forward, four in dK/dV, three in dQ, each traced once with
    and once without the mask) enter the MXU as bfloat16 x bfloat16 with a
    float32 result, and the calls keep the names and the first operand the
    benchmark's readers look for."""
    b, s, h, d = 2, 1024, 4, 64
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, True).astype(
            jnp.float32).sum(), (0, 1, 2))(q, k, v)

    calls = _eqns(jax.make_jaxpr(grads)(x, x, x).jaxpr, "pallas_call")
    names = [c.params["name"] for c in calls]
    assert names == ["flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"], names
    products = {"flash_fwd": 2, "flash_bwd_dkv": 4, "flash_bwd_dq": 3}
    for call in calls:
        first = call.invars[0].aval
        assert first.shape == (b * h, s, d) and first.dtype == jnp.bfloat16
        dots = _eqns(call.params["jaxpr"], "dot_general")
        # one body without the mask and one with it
        assert len(dots) == 2 * products[call.params["name"]], len(dots)
        for dot in dots:
            lhs, rhs = (v.aval.dtype for v in dot.invars)
            assert lhs == rhs == jnp.bfloat16, (call.params["name"], lhs, rhs)
            assert dot.outvars[0].aval.dtype == jnp.float32


def test_nonaligned_seq_falls_back():
    # seq not divisible by block size -> reference fallback, still correct +
    # differentiable.
    q, k, v = _make_qkv(jax.random.PRNGKey(2), s=100)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    g = jax.grad(lambda q: flash_attention(q, k, v, True).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_transformer_with_flash_impl():
    """The flagship model runs with attention_impl='flash'."""
    from autodist_tpu.models import get_model

    spec_dot = get_model("transformer", vocab_size=64, num_layers=1, d_model=32,
                         num_heads=2, d_ff=64, max_seq_len=128,
                         attention_impl="dot", dtype=jnp.float32)
    spec_flash = get_model("transformer", vocab_size=64, num_layers=1, d_model=32,
                           num_heads=2, d_ff=64, max_seq_len=128,
                           attention_impl="flash", dtype=jnp.float32)
    params = spec_dot.init(jax.random.PRNGKey(0))
    batch = spec_dot.example_batch(2)
    l1 = spec_dot.loss_fn(params, batch)
    l2 = spec_flash.loss_fn(params, batch)
    np.testing.assert_allclose(l1, l2, atol=1e-4, rtol=1e-4)


def test_fused_matmul_stats_matches_xla():
    """The experimental pallas matmul+BN-stats kernel
    (examples/benchmark/fused_conv_stats.py — the isolated rendering of
    ResNet's dominant fused-kernel shape) must agree with the XLA
    formulation in interpret mode: same product, same fp32 moments."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "examples", "benchmark"))
    from fused_conv_stats import fused_matmul_stats, xla_matmul_stats

    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 64)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128)).astype(jnp.bfloat16)
    y_p, s1_p, s2_p = fused_matmul_stats(x, w, block_m=512, interpret=True)
    y_x, s1_x, s2_x = xla_matmul_stats(x, w)
    np.testing.assert_allclose(np.asarray(y_p, np.float32),
                               np.asarray(y_x, np.float32), atol=1e-2)
    np.testing.assert_allclose(np.asarray(s1_p), np.asarray(s1_x), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2_p), np.asarray(s2_x), rtol=1e-4)
