"""What every family's plain reference shares: matrix products in a named
precision, the norm and the activation most blocks use, the gap of a served
token's logit, and Adam followed step by step.

The model's own equations (its block, its embedding and head, its loss and
their gradients) are the family's: ``perfbench/families/<family>.py``. This
module imports nothing of ``autodist_tpu`` and nothing of a family.

``precision`` picks how every matrix product is computed:

- ``float32``  : float32 operands, ``Precision.HIGHEST`` — the reference.
- ``bfloat16`` : operands rounded to bfloat16, float32 accumulation — what
  the configurations state the program computes in.
- ``fp8``      : operands rounded to float8 (e4m3, three bits of
  mantissa where bfloat16 has seven; one absmax scale per operand, as fp8
  inference scales its tensors), float32 accumulation — the control: the
  nearest precision below bfloat16. Eight-bit integers with a scale per row
  and per column were tried first and read like bfloat16 itself (seven
  bits against eight of significand), so they separate nothing.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")
FP8_MAX = 448.0   # largest finite float8_e4m3fn


def _fake_fp8(x):
    scale = jnp.max(jnp.abs(x)) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # Rounded forward, straight through backward: the gradient of a
    # quantised product is taken as that of the rounded operands.
    return x + jax.lax.stop_gradient(rounded - x)


def matmul(a, b, precision: str):
    """``a @ b`` with float32 accumulation in the named precision."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "float32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return jnp.matmul(_fake_fp8(a), _fake_fp8(b),
                          precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def layernorm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


# ------------------------------------------------------------------ serving
@jax.jit
def best_logits(table):
    """Per position the best logit and its token, of a ``[S, V]`` table."""
    return table.max(-1), table.argmax(-1).astype(jnp.int32)


@jax.jit
def logit_gaps(best, table, picked):
    """How far each ``picked[t]`` lies below the best logit at position t."""
    return best - jnp.take_along_axis(table, picked[:, None], axis=-1)[:, 0]


# ----------------------------------------------------------------- training
def adam_reference(loss_sum_and_grads, params, batches, model: dict, *,
                   learning_rate: float,
                   b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                   block_rows: int = 2, precision: str = "float32",
                   rows_used: slice = slice(None), shardings=None):
    """Follow ``len(batches)`` Adam steps from ``params`` on
    ``batches[i] [B, S]`` (int32 host arrays) under the family's
    ``loss_sum_and_grads(params, rows, model, precision) -> (summed loss,
    predictions, gradients in the parameters' tree)``, the loss a mean over all
    predictions of the step's rows, gradients accumulated ``block_rows``
    rows at a time so that float32 activations fit.

    ``rows_used`` plants a fault for the control tests: only those rows of
    each batch are seen (half a batch left out, or one chip's share when
    the exchange between chips is left out), the mean taken over the rest.

    Returns ``(losses, grad_norms, change_norms)``: the loss of each step,
    and per parameter leaf (flattened in ``jax.tree`` order) the norm of
    the first step's gradient and of the parameters' change after the last.
    """
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))

    def accumulate(p, acc, rows):
        s, n, g = loss_sum_and_grads(p, rows, model, precision)
        return add(acc, g), s, n

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, g, t):
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        def leaf(p_, m_, v_):
            mhat = m_ / (1 - b1 ** t)
            vhat = v_ / (1 - b2 ** t)
            return p_ - learning_rate * mhat / (jnp.sqrt(vhat) + eps)
        return jax.tree.map(leaf, p, m, v), m, v

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(x * x))
                               for x in jax.tree.leaves(t)])
    diff_norms = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum((x - y) ** 2))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=shardings)

    p = jax.tree.map(jnp.copy, params)
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], None
    for step, batch in enumerate(batches, start=1):
        rows = batch[rows_used]
        acc, total, count = zeros(params), 0.0, 0
        for i in range(0, len(rows), block_rows):
            acc, s, n = accumulate(p, acc, jnp.asarray(rows[i:i + block_rows]))
            total, count = total + float(s), count + int(n)
        g = jax.tree.map(lambda a: a / count, acc)
        losses.append(total / count)
        if grad_norms is None:
            grad_norms = [float(x) for x in norms(g)]
        p, m, v = update(p, m, v, g, jnp.float32(step))
    change_norms = [float(x) for x in diff_norms(p, params)]
    return losses, grad_norms, change_norms
