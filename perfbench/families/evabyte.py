"""Family ``evabyte``: everything that is the model's, for a configuration
file that states ``"family": "evabyte"`` and carries EvaByte's published
keys (huggingface.co/EvaByte/EvaByte ``config.json``: ``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``vocab_size``, ``num_pred_heads``, ``window_size``, ``chunk_size``,
``rope_theta``, ``rms_norm_eps``, ``norm_add_unit_offset``,
``max_position_embeddings``; ``param_dtype`` for the type the leaves are
held in). What a family file gives the harness is listed at the top of
``families/gpt2.py``; this one serves only (no ``loss_fn``).

It imports the program's model at import time, so a checkout that has no
``autodist_tpu.models.evabyte`` fails on a cell of this family in its first
second, before a byte of weights is made.

**The layer** (EVA, Zheng et al., "Efficient Attention via Control
Variates", arXiv 2302.04542; the config's keys). Block, pre-norm:
``h = x + Attn(N1(x))``, ``y = h + W_down(silu(W_gate N2(h)) * W_up N2(h))``,
``N(u) = u / sqrt(mean(u^2) + eps) * (1 + w)``; no biases; embedding and
head untied. ``q, k, v = W_q u, W_k u, W_v u``, H heads of d; rotary
positions (rotate-half, whole head) on q and k; ``s = d^-1/2``. Window W,
chunk c. Per head two learned vectors ``mu, phi``; chunk ``j`` has one
summary pair from its own c rotated keys and values,
``k~_j = sum_n softmax_n(s k_n.mu) k_n``, ``v~_j = sum_n softmax_n(s
k_n.phi) v_n``. Token ``i`` takes one softmax over the exact keys of its
own window ``i // W`` up to itself and the summaries of every chunk of
every earlier window (``j < (i // W) * W / c``). The head is ``D x (V *
num_pred_heads)``; columns ``[0, V)`` are the next byte's.

**Weights.** The tree the program's model reads (``embed``, ``layers_<i>``
with ``norm1 / attn.{wq,wk,wv,wo,mu,phi} / norm2 / mlp.{gate,up,down}``,
``norm_f``, ``head``), bfloat16 leaves. The scales are chosen so that the
comparison can see the mechanism (a nearly uniform attention that adds
nearly nothing would hide a missing summary): ``W_q, W_k`` at ``D^-1/2``
and ``mu, phi`` at 1, so that ``s q.k``, ``s k.mu`` and ``s k.phi`` have a
standard deviation near 1; ``W_v, W_o`` at ``3 D^-1/2``, so that over some
2,000 entries attention's part of the residual is of the MLP's size
(``W_gate, W_up`` at ``D^-1/2``, ``W_down`` at ``0.7 F^-1/2``); embedding
at 1, head at ``D^-1/2`` (logits of standard deviation near 1); norm
weights small random numbers about 0 (the scale is ``1 + w``). The
configuration file lists them under ``assumed``.

**Reference.** The equations above in jax.numpy, float32,
``Precision.HIGHEST``, over the whole sequence: no cache, no kernel, no
pages, no chunked prefill. One sequence at a time and one layer at a time,
each layer's weights made from the seed through ``make_leaf`` and dropped
again (16 layers of float32 are 12.9 GB; one is 0.8 GB), and inside a
layer one window of queries at a time (a window's scores against its own
keys and every summary are 0.9 GB; all windows' at once would not fit).
``precision`` also names two planted faults, computed in float32:
``no_summaries`` (window-only attention) and ``early_summaries`` (a
window's summaries shown one window early: to the tokens of that same
window).

**Counts.** What the mathematics needs: the block's matrix products, the
next byte's 320 columns of the head, attention over ``entries(p) = p % W +
1 + (p // W) * (W / c)`` entries, and the two pooling softmaxes. Where
only a mean context is known (``kernel_work`` without ``entries``) the
window's phase is taken as uniform: ``(W + 1) / 2`` exact keys.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import autodist_tpu.models.evabyte as program    # no such model, no such cell
from perfbench.harness import weights
from perfbench.harness.reference import best_logits, logit_gaps, matmul  # noqa: F401

# The catalog row's widths (model-configs guide, architectures.jsonl,
# "EvaByte"): a configuration of this family carries them unchanged.
PUBLISHED = {"hidden_size": 4096, "intermediate_size": 11008,
             "num_attention_heads": 32, "num_key_value_heads": 32,
             "vocab_size": 320, "num_pred_heads": 8, "window_size": 2048,
             "chunk_size": 16, "max_position_embeddings": 32768,
             "rope_theta": 100000, "rms_norm_eps": 1e-05}
FAULTS = ("no_summaries", "early_summaries")
KERNELS = ("eva_paged_attention",)


# ------------------------------------------------------------ configuration
def check_config(model: dict, reduced) -> None:
    """Raise where a width is not the published one, or ``reduced`` names
    one."""
    for key, value in PUBLISHED.items():
        if model[key] != value:
            raise ValueError(f"{key} is {model[key]}; EvaByte publishes {value}")
    if model["window_size"] % model["chunk_size"]:
        raise ValueError("the window is not a whole number of chunks")
    for key in reduced:
        if key.endswith(("_dim", "_rank", "_size")) or key in PUBLISHED:
            raise ValueError(f"`reduced` may never name a width: {key!r}")


def vocab_size(model: dict) -> int:
    return int(model["vocab_size"])


def _dtype(model: dict):
    return jnp.dtype(model.get("param_dtype", "bfloat16"))


def _head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


# ------------------------------------------------------------------ weights
def _layout(model: dict):
    d, f = model["hidden_size"], model["intermediate_size"]
    h, hd = model["num_attention_heads"], _head_dim(model)
    unit = d ** -0.5
    out = {("embed", "embedding"): ((vocab_size(model), d), "normal", 1.0),
           ("norm_f", "weight"): ((d,), "normal", 0.1),
           ("head", "kernel"): ((d, vocab_size(model) * model["num_pred_heads"]),
                                "normal", unit)}
    for i in range(model["num_hidden_layers"]):
        lay = f"layers_{i}"
        for norm in ("norm1", "norm2"):
            out[(lay, norm, "weight")] = ((d,), "normal", 0.1)
        for w, std in {"wq": unit, "wk": unit, "wv": 3 * unit, "wo": 3 * unit}.items():
            out[(lay, "attn", w, "kernel")] = ((d, d), "normal", std)
        for w in ("mu", "phi"):
            out[(lay, "attn", w)] = ((h, hd), "normal", 1.0)
        for w, (a, b, std) in {"gate": (d, f, unit), "up": (d, f, unit),
                               "down": (f, d, 0.7 * f ** -0.5)}.items():
            out[(lay, "mlp", w, "kernel")] = ((a, b), "normal", std)
    return out


def param_shapes(model: dict):
    return weights.param_shapes(_layout(model), _dtype(model))


def make_params(model: dict, seed: int, shardings=None):
    """The whole tree, a top-level group a call (a layer; the embedding;
    the head): one call over 16 layers would hold its float32 normals
    beside the bfloat16 leaves. The layers share one compiled program, the
    leaves' numbers being arguments."""
    layout, dtype = _layout(model), _dtype(model)
    order = weights._order(layout)
    key = weights.seed_key(seed)

    @partial(jax.jit, static_argnums=(2,))
    def group(key, indices, specs):
        return [weights._make_leaf(key, i, *spec, dtype)
                for i, spec in zip(indices, specs)]

    by_top = {}
    for path in sorted(layout):
        by_top.setdefault(path[0], []).append(path)
    flat = {}
    for paths in by_top.values():
        indices = jnp.asarray([order[p] for p in paths], jnp.int32)
        flat.update(zip(paths, group(key, indices, tuple(layout[p] for p in paths))))
    tree = weights._nest(flat)
    return tree if shardings is None else jax.device_put(tree, shardings)


def make_leaf(model: dict, seed: int, path: tuple):
    return weights.make_leaf(_layout(model), seed, path, _dtype(model))


def reference_params(model: dict, seed: int):
    """The seed, handed on: ``next_token_logits`` makes each layer's
    weights when it reaches the layer."""
    return {"seed": int(seed)}


# -------------------------------------------------------- the program's side
def program_config(model: dict, **more):
    """The program's ``EvaByteConfig`` for a configuration file: the
    published sizes, and nothing the program chooses for itself (``more``
    is for a test that pins one)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "max_position_embeddings", "rms_norm_eps",
            "norm_add_unit_offset", "rope_theta", "window_size", "chunk_size",
            "num_pred_heads")
    kw = {k: model[k] for k in keys}
    kw["dtype"] = jnp.dtype(model.get("compute_dtype", "bfloat16"))
    kw.update(more)
    return program.EvaByteConfig(**kw)


def decode_model(model: dict):
    """What ``AutoDist.build_inference(params, decode_model=...)`` is given."""
    return program.decode_model(program_config(model))


# ---------------------------------------------------------------- reference
def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """``x [S, H, d]`` at positions ``0..S-1``, rotate-half over all of d."""
    s, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + turned * sin


def block(p, x, *, heads, window, chunk, theta, eps, precision, fault=None):
    """One block on one sequence ``x [S, D]`` (float32), ``S`` a whole
    number of windows. Returns the block's output and, for whoever asks how
    the weights' scales came out, the root mean square of attention's and
    of the MLP's part of the residual."""
    s, d = x.shape
    hd, n_win, per_win = d // heads, s // window, window // chunk
    scale = hd ** -0.5
    h = _rmsnorm(x, p["norm1"]["weight"], eps)
    q, k, v = (matmul(h, p["attn"][w]["kernel"], precision).reshape(s, heads, hd)
               for w in ("wq", "wk", "wv"))
    q, k = _rope(q, theta), _rope(k, theta)

    # one summary pair per chunk, from the chunk's own keys and values
    kc = k.reshape(s // chunk, chunk, heads, hd).transpose(0, 2, 1, 3)   # [J,H,c,d]
    vc = v.reshape(s // chunk, chunk, heads, hd).transpose(0, 2, 1, 3)

    def pooled(values, vector):
        logits = matmul(kc, vector[None, :, :, None], precision)[..., 0] * scale
        weights_ = jax.nn.softmax(logits, axis=-1)                       # [J,H,c]
        return matmul(weights_[:, :, None, :], values, precision)[:, :, 0]   # [J,H,d]

    k_sum = pooled(kc, p["attn"]["mu"]).transpose(1, 0, 2)               # [H,J,d]
    v_sum = pooled(vc, p["attn"]["phi"]).transpose(1, 0, 2)
    n_sum = k_sum.shape[1]

    def one_window(args):
        wi, qw, kw, vw = args                          # [W,H,d] each
        qh, kh, vh = (t.transpose(1, 0, 2) for t in (qw, kw, vw))    # [H,W,d]
        exact = matmul(qh, kh.transpose(0, 2, 1), precision) * scale     # [H,W,W]
        exact = jnp.where(jnp.tril(jnp.ones((window, window), bool)), exact, -jnp.inf)
        summ = matmul(qh, k_sum.transpose(0, 2, 1), precision) * scale   # [H,W,J]
        shown = {None: wi, "no_summaries": 0, "early_summaries": wi + 1}[fault]
        summ = jnp.where(jnp.arange(n_sum) < shown * per_win, summ, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([exact, summ], -1), axis=-1)
        out = (matmul(probs[..., :window], vh, precision)
               + matmul(probs[..., window:], v_sum, precision))          # [H,W,d]
        return out.transpose(1, 0, 2)

    split = lambda t: t.reshape(n_win, window, heads, hd)   # noqa: E731
    o = jax.lax.map(one_window, (jnp.arange(n_win), split(q), split(k), split(v)))
    attn = matmul(o.reshape(s, d), p["attn"]["wo"]["kernel"], precision)
    x = x + attn
    h = _rmsnorm(x, p["norm2"]["weight"], eps)
    mlp = matmul(jax.nn.silu(matmul(h, p["mlp"]["gate"]["kernel"], precision))
                 * matmul(h, p["mlp"]["up"]["kernel"], precision),
                 p["mlp"]["down"]["kernel"], precision)
    rms = lambda t: jnp.sqrt((t * t).mean())    # noqa: E731
    return x + mlp, (rms(attn), rms(mlp))


_block = jax.jit(block, static_argnames=(
    "heads", "window", "chunk", "theta", "eps", "precision", "fault"))


@partial(jax.jit, static_argnames=("eps", "vocab", "precision"))
def _head(x, norm_w, kernel, eps, vocab, precision):
    return matmul(_rmsnorm(x, norm_w, eps), kernel[:, :vocab], precision)


def _layer_params(model: dict, seed: int, name: str):
    """One top-level group of the tree, float32, made from the seed."""
    flat = {path[1:]: make_leaf(model, seed, path).astype(jnp.float32)
            for path in _layout(model) if path[0] == name}
    return weights._nest(flat)


def logits(params, tokens, model: dict, precision: str = "float32", parts=None):
    """Next-byte logits ``[S, V]`` of one sequence ``tokens [S]``; ``parts``
    (a list) collects each layer's (attention, MLP) residual RMS."""
    fault = precision if precision in FAULTS else None
    precision = "float32" if fault else precision
    seed, window = params["seed"], model["window_size"]
    s = tokens.shape[0]
    padded = -(-s // window) * window
    tokens = jnp.pad(tokens, (0, padded - s))
    x = _layer_params(model, seed, "embed")["embedding"][tokens]
    for i in range(model["num_hidden_layers"]):
        x, rms = _block(
            _layer_params(model, seed, f"layers_{i}"), x,
            heads=model["num_attention_heads"], window=window,
            chunk=model["chunk_size"], theta=float(model["rope_theta"]),
            eps=model["rms_norm_eps"], precision=precision, fault=fault)
        if parts is not None:
            parts.append(rms)
    return _head(x, _layer_params(model, seed, "norm_f")["weight"],
                 _layer_params(model, seed, "head")["kernel"],
                 model["rms_norm_eps"], vocab_size(model), precision)[:s]


def next_token_logits(params, tokens, model: dict, precision: str):
    """For one padded sequence ``tokens [S]``: per position the best next
    logit, its token, and the whole ``[S, V]`` table. ``precision`` is one
    of ``harness/reference.PRECISIONS`` or of ``FAULTS``."""
    table = logits(params, tokens, model, precision)
    return (*best_logits(table), table)


# ------------------------------------------------------------ required work
def entries(model: dict, p: int) -> int:
    """Entries the query at position ``p`` attends over."""
    w, c = model["window_size"], model["chunk_size"]
    return p % w + 1 + (p // w) * (w // c)


def entries_sum(model: dict, n: int) -> int:
    """``sum(entries(p) for p in range(n))``, window by window."""
    w, c = model["window_size"], model["chunk_size"]
    full, rest = divmod(n, w)
    total = full * w * (w + 1) // 2 + w * (w // c) * (full * (full - 1) // 2)
    return total + rest * (rest + 1) // 2 + rest * full * (w // c)


def matmul_params(model: dict) -> int:
    """Parameters in a matrix product for every token, the head left out:
    4 d^2 + 3 d f a layer."""
    d, f = model["hidden_size"], model["intermediate_size"]
    return model["num_hidden_layers"] * (4 * d * d + 3 * d * f)


def head_flops(model: dict) -> int:
    """One position's next-byte logits: d x V multiply-adds."""
    return 2 * model["hidden_size"] * model["vocab_size"]


def attention_flops(model: dict, n_entries: int) -> int:
    """QK^T and PV over all layers for ``n_entries`` query-entry pairs."""
    return model["num_hidden_layers"] * 4 * model["hidden_size"] * n_entries


def summary_flops(model: dict, positions: int) -> int:
    """The two pooling softmaxes over all layers: per position a dot
    product with each of mu and phi and a share in each weighted sum."""
    return model["num_hidden_layers"] * 8 * model["hidden_size"] * positions


def prefill_flops(model: dict, prompt: int) -> int:
    """A prompt of ``prompt`` bytes up to its first generated byte."""
    return (2 * matmul_params(model) * prompt
            + attention_flops(model, entries_sum(model, prompt))
            + summary_flops(model, prompt) + head_flops(model))


def decode_flops(model: dict, context: int) -> int:
    """One generated byte whose query is the ``context``-th position (it
    sits at ``context - 1``)."""
    return (2 * matmul_params(model)
            + attention_flops(model, entries(model, context - 1))
            + summary_flops(model, 1) + head_flops(model))


def served_entries(model: dict, finished) -> float:
    """Mean of ``entries(p)`` over the bytes the decode step served, for
    ``finished`` = ``[(prompt bytes, served bytes)]``: served byte ``i >= 1``
    of a request comes from the query at ``prompt + i - 1`` (byte 0 is the
    prefill's). None where there is none."""
    total = count = 0
    for prompt, n in finished:
        if n > 1:
            total += entries_sum(model, prompt + n - 1) - entries_sum(model, prompt)
            count += n - 1
    return total / count if count else None


def entry_bytes(model: dict, itemsize: int = 2) -> int:
    """One entry's key and value, one layer (16 KB in bfloat16)."""
    return 2 * model["hidden_size"] * itemsize


def kernel_work(kernel: str, model: dict, facts: dict):
    """``(operations, bytes)`` one call of ``eva_paged_attention`` requires
    (one layer): ``rows`` rows of ``queries`` queries (1 in the decode
    step) that each see ``entries`` entries on average, and ``entries_read``
    entries a row to read (``entries`` where one query reads them). Where
    the run knows only a mean ``context``, the window's phase is taken as
    uniform."""
    if kernel not in KERNELS:
        raise KeyError(f"family evabyte counts no kernel named {kernel!r}; "
                       f"it has {KERNELS}")
    seen = facts.get("entries")
    if seen is None:
        w, c = model["window_size"], model["chunk_size"]
        p = max(facts["context"] - 1.0, 0.0)
        seen = p + 1 if p < w else (w + 1) / 2 + (p // w) * (w // c)
    rows, queries = facts["rows"], facts.get("queries", 1)
    one_layer = dict(model, num_hidden_layers=1)
    return (rows * queries * attention_flops(one_layer, seen),
            rows * facts.get("entries_read", seen) * entry_bytes(model))

