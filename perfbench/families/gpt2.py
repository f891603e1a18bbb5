"""Family ``gpt2``: everything that is the model's, for a configuration
file that states ``"family": "gpt2"`` and carries GPT-2's published keys
(``n_layer``, ``n_embd``, ``n_head``, ``n_inner``, ``n_positions``,
``vocab_size``, ``layer_norm_epsilon``; ``param_dtype`` for the type the
leaves are held in, float32 where it says nothing).

A family file gives the harness, for a configuration's dict:

- ``check_config(model, reduced)``: its own assertions about its keys, and
  which of them are widths that ``reduced`` may never list;
- weights from the seed: ``make_params``, ``make_leaf``, ``param_shapes``,
  ``row_shardings``, ``change_norms``, ``reference_params``;
- the program's side: ``decode_model(model)`` (what ``build_inference`` is
  given), ``loss_fn(model, mix)`` (what ``AutoDist.build`` is given),
  ``vocab_size(model)`` (the rows of vocabulary the traffic draws from and
  the logits cover);
- the plain reference: ``next_token_logits``, ``logit_gaps``,
  ``adam_reference`` in the precisions of ``harness/reference.py``;
- required work: ``train_flops_per_token``, ``prefill_flops``,
  ``decode_flops`` and ``kernel_work(kernel, model, facts)`` for every named
  kernel its programs call.

Nothing of the measurement is here: no clock, no window, no trace.

**Weights.** The tree has the layout the program's transformer reads
(``embed``, ``pos_embed``, ``layers_<i>`` with ``ln1 / attn.{wq,wk,wv,wo} /
ln2 / mlp.{fc1,fc2}``, ``ln_f``). Values follow GPT-2's initialisation
(normal, 0.02; the two projections into the residual stream scaled by
1/sqrt(2 L)), except that LayerNorm scales, and every bias, are small random
numbers and not ones and zeros: a path that drops a bias or a scale then
shows in the comparison.

**Reference.** Plain GPT-2 in jax.numpy: forward, loss, gradients. Follows
the published model (Radford et al. 2019; the layer equations of
``modeling_gpt2.py``): learned positions, pre-LayerNorm blocks, multi-head
causal attention scaled by 1/sqrt(head size), tanh-GELU MLP, a final
LayerNorm and a head tied to the token embedding. No kernel, no cache, no
batching tricks. It takes nothing that the program has made and reads only
weights that the benchmark itself made from the seed. Departures, each
forced by what the program runs: q, k and v are three matrices where GPT-2
fuses them (same mathematics); the LayerNorm epsilon is whatever the
configuration file states (the program hard-codes 1e-6 where GPT-2 publishes
1e-5, so the file lists it under ``reduced``).

**Counts.** The work the mathematics needs, not what an implementation
does: causal attention at half of the full square, no recomputation, the
head once where only one position's logits are needed. One multiply-add is
two operations.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from perfbench.harness import reference, weights
from perfbench.harness.counts import causal_pairs
from perfbench.harness.reference import (best_logits, gelu_tanh, layernorm,  # noqa: F401
                                         logit_gaps, matmul)

WIDTHS = ("n_embd", "n_inner")


# ------------------------------------------------------------ configuration
def check_config(model: dict, reduced) -> None:
    """Raise where the file is not a GPT-2 configuration, or ``reduced``
    names a width."""
    if model["n_embd"] % model["n_head"]:
        raise ValueError(f"n_embd {model['n_embd']} is not a whole number of "
                         f"n_head {model['n_head']} heads")
    if model["n_inner"] != 4 * model["n_embd"]:
        raise ValueError("GPT-2's n_inner is 4 x n_embd; the file states "
                         f"{model['n_inner']} beside {model['n_embd']}")
    for key in reduced:
        if key.endswith(("_dim", "_rank")) or key in WIDTHS:
            raise ValueError(f"`reduced` may never name a width: {key!r}")


def vocab_size(model: dict) -> int:
    return int(model["vocab_size"])


def _dtype(model: dict):
    return jnp.dtype(model.get("param_dtype", "float32"))


# ------------------------------------------------------------------ weights
def _layout(model: dict):
    """``{path: (shape, kind, std)}`` with path a tuple of dict keys."""
    d, f, v = model["n_embd"], model["n_inner"], vocab_size(model)
    resid = 0.02 / math.sqrt(2 * model["n_layer"])
    out = {("embed", "embedding"): ((v, d), "normal", 0.02),
           ("pos_embed", "embedding"): ((model["n_positions"], d), "normal", 0.02),
           ("ln_f", "scale"): ((d,), "scale", 0.1),
           ("ln_f", "bias"): ((d,), "normal", 0.02)}
    for i in range(model["n_layer"]):
        lay = f"layers_{i}"
        for ln in ("ln1", "ln2"):
            out[(lay, ln, "scale")] = ((d,), "scale", 0.1)
            out[(lay, ln, "bias")] = ((d,), "normal", 0.02)
        for w, (a, b, std) in {"wq": (d, d, 0.02), "wk": (d, d, 0.02),
                               "wv": (d, d, 0.02), "wo": (d, d, resid)}.items():
            out[(lay, "attn", w, "kernel")] = ((a, b), "normal", std)
            out[(lay, "attn", w, "bias")] = ((b,), "normal", 0.01)
        for w, (a, b, std) in {"fc1": (d, f, 0.02), "fc2": (f, d, resid)}.items():
            out[(lay, "mlp", w, "kernel")] = ((a, b), "normal", std)
            out[(lay, "mlp", w, "bias")] = ((b,), "normal", 0.01)
    return out


def param_shapes(model: dict):
    return weights.param_shapes(_layout(model), _dtype(model))


def make_params(model: dict, seed: int, shardings=None):
    return weights.make_params(_layout(model), seed, shardings, _dtype(model))


def make_leaf(model: dict, seed: int, path: tuple):
    return weights.make_leaf(_layout(model), seed, path, _dtype(model))


def change_norms(model: dict, seed: int, params) -> list:
    return weights.change_norms(_layout(model), seed, params, _dtype(model))


def row_shardings(model: dict, devices):
    return weights.row_shardings(_layout(model), devices)


def reference_params(model: dict, seed: int):
    """What ``next_token_logits`` is handed: the whole tree, made once a
    run; it fits beside nothing else only because the engine is freed first.
    (A family that cannot hold its float32 reference hands the seed on and
    builds a layer at a time through ``make_leaf``.)"""
    return make_params(model, seed)


# -------------------------------------------------------- the program's side
def transformer_config(model: dict, **more):
    """The program's ``TransformerConfig`` for a configuration file: the
    published sizes and nothing the program chooses for itself, unless the
    file pins a path under ``assumed.transformer_config``."""
    from autodist_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=vocab_size(model), num_layers=model["n_layer"],
        d_model=model["n_embd"], num_heads=model["n_head"],
        d_ff=model["n_inner"], max_seq_len=model["n_positions"], **more,
        **model.get("assumed", {}).get("transformer_config", {}))


def decode_model(model: dict):
    """What ``AutoDist.build_inference(params, decode_model=...)`` is given."""
    from autodist_tpu.models import transformer as T

    return T.decode_model(transformer_config(model))


def loss_fn(model: dict, mix: dict):
    """What ``AutoDist.build`` is given: ``loss_fn(params, batch)`` for the
    job the traffic file states (``remat``: false, or "block" for the
    model's per-block checkpoint)."""
    from autodist_tpu.models import transformer as T

    cfg = transformer_config(model, remat=mix.get("remat") == "block")

    def loss_fn(p, b):
        return T.loss_fn(p, b, cfg)

    return loss_fn


# ---------------------------------------------------------------- reference
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
def next_token_logits(params, tokens, model: dict, precision: str):
    """For one padded sequence ``tokens [S]``: per position the best next
    logit, its token, and the whole ``[S, V]`` table."""
    table = logits(params, tokens[None], model, precision)[0]
    return (*best_logits(table), table)


# ----------------------------------------------------------------- training
def adam_reference(params, batches, model: dict, **kw):
    """``harness/reference.adam_reference`` under this family's gradients."""
    return reference.adam_reference(loss_sum_and_grads, params, batches, model, **kw)


# ------------------------------------------------------------ required work
def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product for every token, the
    tied head left out: 4 d^2 + 2 d f a layer."""
    d, f = model["n_embd"], model["n_inner"]
    return model["n_layer"] * (4 * d * d + 2 * d * f)


def head_flops(model: dict) -> int:
    """One position's logits: d x V multiply-adds."""
    return 2 * model["n_embd"] * model["vocab_size"]


def attention_flops(model: dict, pairs: int) -> int:
    """QK^T and PV over all layers for ``pairs`` query-key pairs (each
    pair: 2 d operations in each of the two products)."""
    return model["n_layer"] * 4 * model["n_embd"] * pairs


def forward_flops_sequence(model: dict, seq: int, head_positions: int) -> int:
    """Forward pass over one sequence of ``seq`` tokens, logits taken at
    ``head_positions`` of them."""
    return (2 * matmul_params(model) * seq
            + attention_flops(model, causal_pairs(seq))
            + head_flops(model) * head_positions)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token, for
    sequences of ``seq`` with a prediction at every position but the last."""
    return 3.0 * forward_flops_sequence(model, seq, seq - 1) / seq


def prefill_flops(model: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens up to its first generated token: only
    the last position needs logits."""
    return forward_flops_sequence(model, prompt, 1)


def decode_flops(model: dict, context: int) -> int:
    """One generated token whose query sees ``context`` keys."""
    return (2 * matmul_params(model) + attention_flops(model, context)
            + head_flops(model))


def decode_kv_bytes(model: dict, context: int, itemsize: int = 2) -> int:
    """Bytes of live keys and values one decode query has to read, over
    all layers (``itemsize`` 2: bfloat16 pages)."""
    return model["n_layer"] * 2 * context * model["n_embd"] * itemsize


def flash_flops(model: dict, rows: int, seq: int, backward: bool) -> int:
    """One layer's attention over ``rows`` sequences under the causal
    mask: two products forward, four backward (dV, dP, dQ, dK)."""
    per = 2 * model["n_embd"] * causal_pairs(seq) * rows
    return per * (4 if backward else 2)


def flash_bytes(model: dict, rows: int, seq: int, backward: bool,
                itemsize: int = 2) -> int:
    """q, k, v read and o written forward; those four, dO read and dq, dk,
    dv written backward."""
    tensor = rows * seq * model["n_embd"] * itemsize
    return tensor * (8 if backward else 4)


KERNELS = ("paged_attention", "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def kernel_work(kernel: str, model: dict, facts: dict):
    """``(operations, bytes)`` one call of a named kernel requires: one
    layer's attention, for what the run knows (``facts``: ``rows`` and
    ``context`` of a decode step, ``rows`` and ``seq`` of a train step).

    ``paged_attention``: the live keys and values of ``rows`` queries that
    each see ``context`` keys, and their two products. ``flash_fwd``: the
    two causal products; q, k, v read and o written. The backward's four
    products and eight tensors go half to each of its two kernels (dV and
    dK to ``flash_bwd_dkv``, dP and dQ to ``flash_bwd_dq``; what either
    computes again is not required work), so the two together are the whole
    backward as ``flash_flops`` / ``flash_bytes`` count it."""
    one_layer = dict(model, n_layer=1)
    if kernel == "paged_attention":
        rows, context = facts["rows"], facts["context"]
        return (rows * attention_flops(one_layer, context),
                rows * decode_kv_bytes(one_layer, context))
    rows, seq = facts["rows"], facts["seq"]
    if kernel == "flash_fwd":
        return (flash_flops(one_layer, rows, seq, False),
                flash_bytes(one_layer, rows, seq, False))
    if kernel in ("flash_bwd_dkv", "flash_bwd_dq"):
        return (flash_flops(one_layer, rows, seq, True) // 2,
                flash_bytes(one_layer, rows, seq, True) // 2)
    raise KeyError(f"family gpt2 counts no kernel named {kernel!r}; it has {KERNELS}")
