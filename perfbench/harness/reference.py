"""Plain GPT-2 reference: forward, loss, gradients and Adam in jax.numpy.

Follows the published model (Radford et al. 2019; the layer equations of
``modeling_gpt2.py``): learned positions, pre-LayerNorm blocks, multi-head
causal attention scaled by 1/sqrt(head size), tanh-GELU MLP, a final
LayerNorm and a head tied to the token embedding. No kernel, no cache, no
batching tricks. It imports nothing of ``autodist_tpu`` and reads only
weights that the benchmark itself made from the seed (``weights.py``).

Departures, each forced by what the program runs: q, k and v are three
matrices where GPT-2 fuses them (same mathematics); the LayerNorm epsilon
is whatever the configuration file states (the program hard-codes 1e-6
where GPT-2 publishes 1e-5, so the file lists it under ``reduced``).

``precision`` picks how every matrix product is computed:

- ``float32``  : float32 operands, ``Precision.HIGHEST`` — the reference.
- ``bfloat16`` : operands rounded to bfloat16, float32 accumulation — what
  the configuration states the program computes in.
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


def _dense(p, x, precision):
    return matmul(x, p["kernel"], precision) + p["bias"]


def block(p, x, n_head: int, eps: float, precision: str):
    """One pre-norm block on ``x [B, S, D]`` (float32)."""
    b, s, d = x.shape
    hd = d // n_head
    h = layernorm(x, p["ln1"], eps)
    q = _dense(p["attn"]["wq"], h, precision).reshape(b, s, n_head, hd)
    k = _dense(p["attn"]["wk"], h, precision).reshape(b, s, n_head, hd)
    v = _dense(p["attn"]["wv"], h, precision).reshape(b, s, n_head, hd)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))    # [B, H, S, hd]
    scores = matmul(q, k.transpose(0, 1, 3, 2), precision) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = matmul(probs, v, precision).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _dense(p["attn"]["wo"], o, precision)
    h = layernorm(x, p["ln2"], eps)
    h = gelu_tanh(_dense(p["mlp"]["fc1"], h, precision))
    return x + _dense(p["mlp"]["fc2"], h, precision)


def embed(embedding, positions, tokens):
    return (embedding[tokens] + positions[:tokens.shape[1]]).astype(jnp.float32)


def head_loss(ln_f, embedding, x, tokens, eps: float, precision: str):
    """Summed next-token cross-entropy from the last block's output:
    position t predicts token t+1."""
    lg = matmul(layernorm(x, ln_f, eps), embedding.T, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.sum()


# The model runs layer by layer, one small program for a block, so that it
# compiles in seconds whatever the depth and never holds more than one
# block's activations besides the layers' inputs.
_static = dict(static_argnames=("n_head", "eps", "precision"))
_embed = jax.jit(embed)
_block = partial(jax.jit, **_static)(block)


@partial(jax.jit, **_static)
def _block_vjp(p, x, dy, n_head, eps, precision):
    return jax.vjp(lambda p_, x_: block(p_, x_, n_head, eps, precision), p, x)[1](dy)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head_loss_grads(ln_f, embedding, x, tokens, eps, precision):
    return jax.value_and_grad(head_loss, argnums=(0, 1, 2))(
        ln_f, embedding, x, tokens, eps, precision)


@jax.jit
def _embed_vjp(embedding, positions, tokens, dx):
    return jax.vjp(lambda e, p: embed(e, p, tokens), embedding, positions)[1](dx)


def _block_args(model, precision):
    return dict(n_head=model["n_head"], eps=model["layer_norm_epsilon"],
                precision=precision)


def hidden(params, tokens, model: dict, precision: str = "float32"):
    """tokens ``[B, S]`` -> the last block's output ``[B, S, D]`` and the
    input of every block (kept for the backward pass)."""
    x = _embed(params["embed"]["embedding"], params["pos_embed"]["embedding"], tokens)
    inputs = []
    for i in range(model["n_layer"]):
        inputs.append(x)
        x = _block(params[f"layers_{i}"], x, **_block_args(model, precision))
    return x, inputs


@partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(ln_f, embedding, x, eps, precision):
    return matmul(layernorm(x, ln_f, eps), embedding.T, precision)


def logits(params, tokens, model: dict, precision: str = "float32"):
    x, _ = hidden(params, tokens, model, precision)
    return _logits(params["ln_f"], params["embed"]["embedding"], x,
                   model["layer_norm_epsilon"], precision)


def loss_sum(params, tokens, model: dict, precision: str = "float32"):
    """Summed next-token cross-entropy over ``tokens [B, S]`` and the
    number of predictions."""
    x, _ = hidden(params, tokens, model, precision)
    total = head_loss(params["ln_f"], params["embed"]["embedding"], x, tokens,
                      model["layer_norm_epsilon"], precision)
    return total, tokens.shape[0] * (tokens.shape[1] - 1)


def loss_sum_and_grads(params, tokens, model: dict, precision: str = "float32"):
    """``loss_sum`` with its gradient in the parameters' own tree, by the
    chain rule over the blocks: backward through the head, then block by
    block, then the two embedding tables (the token table also has the
    head's share: it is tied)."""
    eps = model["layer_norm_epsilon"]
    emb, pos = params["embed"]["embedding"], params["pos_embed"]["embedding"]
    x, inputs = hidden(params, tokens, model, precision)
    total, (d_ln_f, d_emb_head, dx) = _head_loss_grads(
        params["ln_f"], emb, x, tokens, eps, precision)
    grads = {"ln_f": d_ln_f}
    for i in reversed(range(model["n_layer"])):
        grads[f"layers_{i}"], dx = _block_vjp(
            params[f"layers_{i}"], inputs.pop(), dx, **_block_args(model, precision))
    d_emb, d_pos = _embed_vjp(emb, pos, tokens, dx)
    grads["embed"] = {"embedding": d_emb + d_emb_head}
    grads["pos_embed"] = {"embedding": d_pos}
    return total, tokens.shape[0] * (tokens.shape[1] - 1), grads


# ------------------------------------------------------------------ serving
@jax.jit
def _best(table):
    return table.max(-1), table.argmax(-1).astype(jnp.int32)


def next_token_logits(params, tokens, model: dict, precision: str):
    """For one padded sequence ``tokens [S]``: per position the best next
    logit, its token, and the whole ``[S, V]`` table."""
    table = logits(params, tokens[None], model, precision)[0]
    return (*_best(table), table)


@jax.jit
def logit_gaps(best, table, picked):
    """How far each ``picked[t]`` lies below the best logit at position t."""
    return best - jnp.take_along_axis(table, picked[:, None], axis=-1)[:, 0]


# ----------------------------------------------------------------- training
def adam_reference(params, batches, model: dict, *, learning_rate: float,
                   b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                   block_rows: int = 2, precision: str = "float32",
                   rows_used: slice = slice(None), shardings=None):
    """Follow ``len(batches)`` Adam steps from ``params`` on
    ``batches[i] [B, S]`` (int32 host arrays), the loss a mean over all
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
