"""Functional NN layers shared by the model zoo.

Pure functions over explicit param dicts: deterministic pytree paths (what
strategy builders key on), bfloat16-friendly compute, and shapes that keep
matmuls on the MXU (feature dims padded by the caller, not here).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# ------------------------------------------------------------------ initializers
def glorot(rng, shape, dtype=jnp.float32):
    fan_in, fan_out = _fans(shape)
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, shape, dtype, -limit, limit)


def he_normal(rng, shape, dtype=jnp.float32):
    fan_in, _ = _fans(shape)
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(rng, shape, dtype) * std


def normal(rng, shape, stddev=0.02, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * stddev


def _fans(shape) -> Tuple[int, int]:
    if len(shape) < 1:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    receptive = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
    return shape[-2] * receptive, shape[-1] * receptive


# ------------------------------------------------------------------------ dense
def dense_init(rng, in_dim: int, out_dim: int, use_bias: bool = True):
    p = {"kernel": glorot(rng, (in_dim, out_dim))}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,))
    return p


def dense(p, x, *, compute_dtype=None):
    k = p["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        k = k.astype(compute_dtype)
    y = x @ k
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


# -------------------------------------------------------------------- layernorm
def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layernorm(p, x, eps: float = 1e-6):
    # Normalize in fp32 regardless of compute dtype (numerics on TPU bf16).
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# -------------------------------------------------------------------- embedding
def embedding_init(rng, vocab: int, dim: int, stddev: float = 0.02):
    return {"embedding": normal(rng, (vocab, dim), stddev)}


def embedding_lookup(p, ids):
    """Row gather — the sparse-update path. ``jnp.take`` lowers to a
    ``gather`` primitive, which ModelItem's jaxpr scan detects as a
    sparse-update read (the reference's IndexedSlices analog,
    ``/root/reference/autodist/graph_item.py:275-296``)."""
    return jnp.take(p["embedding"], ids, axis=0)


# ------------------------------------------------------------------------- conv
def conv_init(rng, kh: int, kw: int, cin: int, cout: int):
    return {"kernel": he_normal(rng, (kh, kw, cin, cout))}


def conv(p, x, stride: int = 1, padding: str = "SAME", *, compute_dtype=None):
    """NHWC conv; kernel HWIO. Large convs are MXU work — XLA tiles them."""
    k = p["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        k = k.astype(compute_dtype)
    return lax.conv_general_dilated(
        x, k,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


# ---------------------------------------------------------------------- pooling
def max_pool(x, window: int, stride: int, padding: str = "SAME"):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1), padding
    )


def avg_pool(x, window: int, stride: int, padding: str = "SAME"):
    """Count-normalized average pool: border windows divide by the number of
    valid elements, not window², matching TF/reference semantics under SAME
    padding. The count map is shape-static, so XLA constant-folds it."""
    dims, strides = (1, window, window, 1), (1, stride, stride, 1)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
    if padding == "VALID":
        return summed / (window * window)
    counts = lax.reduce_window(
        jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype),
        0.0, lax.add, dims, strides, padding,
    )
    return summed / counts


def space_to_depth_stem(stem_conv, images, dtype):
    """Weight-equivalent MXU-friendly stem: 7x7/s2 conv on 3 channels →
    4x4/s1 conv on 12 channels over 2x2-space-to-depth input.

    The 7x7 kernel reads input rows r ∈ [-2, 4] around each output center;
    padded to 8 taps those land in 4 blocks of 2, so the padded kernel
    reshapes exactly to [4, 4, 12, cout]. The 3-channel original keeps
    125/128 MXU lanes idle; 12 channels is 4x denser. (MLPerf ResNet's
    standard TPU transform; requires even H and W.)
    """
    b, h, w, c = images.shape
    x = images.reshape(b, h // 2, 2, w // 2, 2, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)

    k = stem_conv["kernel"]                      # [7, 7, 3, cout]
    k = jnp.pad(k, ((0, 1), (0, 1), (0, 0), (0, 0)))       # [8, 8, 3, cout]
    kh, kw, cin, cout = k.shape
    k = k.reshape(kh // 2, 2, kw // 2, 2, cin, cout)
    k = k.transpose(0, 2, 1, 3, 4, 5).reshape(kh // 2, kw // 2, 4 * cin, cout)

    x = x.astype(dtype)
    return lax.conv_general_dilated(
        x, k.astype(dtype),
        window_strides=(1, 1),
        # block-space receptive field is blocks [i-1, i+2]: pad 1 low, 2 high
        padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


# -------------------------------------------------------------------- batchnorm
def batchnorm_init(dim: int):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def _batchnorm_autodiff(p, x, eps: float = 1e-5):
    """The r2 HBM-lean forward, differentiated by autodiff — kept as the
    A/B reference for the custom-vjp default below (resnet_bounds.py
    variant ``autodiffbn``). See :func:`batchnorm` for the semantics."""
    x32 = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = x32.mean(axes)
    # Clamp: E[x²]−E[x]² cancels catastrophically for high-mean/low-variance
    # channels and can come out slightly negative, which rsqrt turns to NaN.
    var = jnp.maximum((x32 * x32).mean(axes) - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    return (((x32 - mean) * (p["scale"] * inv)) + p["bias"]).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _batchnorm_core(scale, bias, x, eps):
    return _batchnorm_autodiff({"scale": scale, "bias": bias}, x, eps)


def _batchnorm_core_fwd(scale, bias, x, eps):
    x32 = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = x32.mean(axes)
    var_raw = (x32 * x32).mean(axes) - mean * mean
    var = jnp.maximum(var_raw, 0.0)
    inv = lax.rsqrt(var + eps)
    y = (((x32 - mean) * (scale * inv)) + bias).astype(x.dtype)
    # Residuals beyond x itself are per-channel vectors — the backward
    # re-derives x_hat from (x, mean, inv) instead of saving an
    # activation-sized x_hat the way autodiff-through-the-moments would.
    # The clamp mask rides along so the backward can zero the variance
    # path exactly where the clamp froze it (matching autodiff).
    return y, (x, mean, inv, scale, var_raw > 0.0)


def _batchnorm_core_bwd(eps, res, dy):
    x, mean, inv, scale, var_live = res
    axes = tuple(range(x.ndim - 1))
    n = float(np.prod([x.shape[a] for a in axes]))
    dy32 = dy.astype(jnp.float32)
    x_hat = (x.astype(jnp.float32) - mean) * inv
    # One fused reduction pass over (dy, dy·x_hat), then one fused
    # elementwise pass — the classic analytic BN backward:
    #   dx = (γ·inv)·(dy − E[dy] − x̂·E[dy·x̂])
    # In the clamped-variance regime (catastrophic cancellation pushed the
    # one-pass variance negative; forward froze it at 0) the variance term
    # is dropped per channel: d var/dx is identically 0 there, which is
    # also what autodiff-through-the-clamp produces.
    sum_dy = dy32.sum(axes)
    sum_dy_xhat = (dy32 * x_hat).sum(axes)
    dbias = sum_dy
    dscale = sum_dy_xhat
    var_term = jnp.where(var_live, sum_dy_xhat / n, 0.0)
    dx = (scale * inv) * (dy32 - sum_dy / n - x_hat * var_term)
    return dscale, dbias, dx.astype(x.dtype)


_batchnorm_core.defvjp(_batchnorm_core_fwd, _batchnorm_core_bwd)


def batchnorm(p, x, eps: float = 1e-5):
    """Training-mode batch norm over N,H,W (batch statistics only).

    Running averages are an inference concern; the training hot loop — what
    the benchmarks measure — always uses batch stats, so they are omitted
    from the differentiable path. Under data parallelism the stats are
    per-shard (the reference behaved identically: each replica normalized
    its own split batch).

    HBM-lean formulation (r2, measured +14% ResNet-50 step rate on the
    bench chip): statistics reduce in fp32 in ONE pass (E[x²]−E[x]²
    instead of the two-pass mean/var — one read of the activation tensor
    computes both moments). The normalization subtracts the mean BEFORE
    scaling, in fp32 *register* precision inside one fused elementwise
    kernel (XLA reads bf16, writes bf16; the fp32 intermediate never
    reaches HBM), so high-mean/low-variance channels cancel exactly — a
    folded ``x*scale+bias`` in bf16 would lose the cancellation to
    rounding.

    The backward is hand-written (r3): autodiff through the moments saves
    activation-sized intermediates and re-reads x on several paths; the
    custom vjp saves only (x, per-channel mean/inv) and lowers to exactly
    one reduction pass + one elementwise pass
    (``tests/test_models.py::test_batchnorm_custom_vjp_matches_autodiff``
    pins it to the autodiff gradients bit-for-bit-tight)."""
    return _batchnorm_core(p["scale"], p["bias"], x, eps)


# ----------------------------------------------------------------------- losses
def per_token_xent(logits, labels):
    """Per-position cross-entropy (fp32 logsumexp), no reduction."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - label_logit


def softmax_xent(logits, labels):
    """Mean cross-entropy. Under pjit with batch sharded on the data axis the
    mean induces the gradient ``psum`` — the AllReduce synchronizer's job in
    the reference (``all_reduce_synchronizer.py:100-126``) done by autodiff."""
    return per_token_xent(logits, labels).mean()


def sigmoid_xent(logits, labels):
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


# ---------------------------------------------------------------------- rmsnorm
def rmsnorm_init(dim: int, unit_offset: bool = False):
    """RMSNorm weight: zeros where the norm adds a unit offset (the scale
    is ``1 + weight``), ones where it does not."""
    return {"weight": jnp.zeros((dim,)) if unit_offset else jnp.ones((dim,))}


def rmsnorm(p, x, eps: float, unit_offset: bool = False):
    """``x / sqrt(mean(x^2) + eps) * scale`` with ``scale = weight`` or
    ``1 + weight``; mean and scale in float32, result in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    w = p["weight"].astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w if unit_offset else w)).astype(x.dtype)


# ------------------------------------------------------------------------- rope
def rope(x, positions, theta: float):
    """Rotary position embedding over the whole head dimension, in the
    rotate-half convention: ``x [..., H, D]`` at ``positions [...]`` (one
    per leading index). Pair ``(i, i + D/2)`` turns by ``positions *
    theta^(-2i/D)``; angles and the rotation in float32, result in ``x``'s
    type."""
    d = x.shape[-1]
    return rope_freqs(
        x, positions, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rope_freqs(x, positions, inv_freq):
    """:func:`rope` with the ``D / 2`` frequencies given (a model that
    scales them, as YaRN does, states its own)."""
    d = x.shape[-1]
    angle = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


# -------------------------------------------------------------------- gated mlp
def gated_mlp_init(rng, dim: int, hidden: int):
    k = jax.random.split(rng, 3)
    return {"gate": dense_init(k[0], dim, hidden, use_bias=False),
            "up": dense_init(k[1], dim, hidden, use_bias=False),
            "down": dense_init(k[2], hidden, dim, use_bias=False)}


def gated_mlp(p, x, *, compute_dtype=None):
    """``down(silu(gate x) * up x)``: the products accumulate in float32
    and come back in ``compute_dtype``."""
    g = dense(p["gate"], x, compute_dtype=compute_dtype)
    u = dense(p["up"], x, compute_dtype=compute_dtype)
    return dense(p["down"], jax.nn.silu(g) * u, compute_dtype=compute_dtype)


# ------------------------------------------------------------------ untied head
def lm_head(p, x, columns=None, *, compute_dtype=None):
    """Logits from a head of its own (no tie to the embedding), in
    float32: ``x [..., D] @ kernel [D, V']``, the first ``columns`` of the
    kernel where only those are wanted (a head that holds several
    next-offset predictions side by side)."""
    k = p["kernel"] if columns is None else p["kernel"][:, :columns]
    if compute_dtype is not None:
        x, k = x.astype(compute_dtype), k.astype(compute_dtype)
    return jnp.matmul(x, k, preferred_element_type=jnp.float32)
