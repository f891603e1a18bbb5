"""Family ``nemotron_h``: everything that is the model's, for a configuration
file that states ``"family": "nemotron_h"`` and carries the published keys
of huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``
(``model_type: nemotron_h``). What a family file gives the harness is
listed at the top of ``families/gpt2.py``; this one serves only.

It imports the program's model at import time, so a checkout that has no
``autodist_tpu.models.nemotron_h`` fails on a cell of this family in its
first second, before a byte of weights is made.

**What the file's cut keys mean.** ``hybrid_override_pattern`` and
``num_hidden_layers`` are the layers on this chip (the published pattern's
first ones); ``n_routed_experts`` is the number of routed experts *this chip
holds* (its ``share`` group says which) and ``published.n_routed_experts``
what the router scores over; ``vocab_size`` is the rows of the embedding and
head this chip holds.

**The layers** (``M`` Mamba-2, ``E`` experts, ``*`` attention; each block
``x <- x + mixer(N(x))``, ``N(u) = u / sqrt(mean(u^2) + eps) w``; final
norm, untied head). *Mamba-2* (arXiv 2405.21060): ``[z | xBC | dt] = u
W_in``; ``xBC = SiLU(conv(xBC) + b)``, causal and depthwise over
``conv_kernel`` positions; ``x [H, P], B [G, N], C [G, N]``; ``dt =
softplus(dt + dt_bias)``, no clamp; ``A = -exp(A_log)``; head ``h`` of group
``h // (H / G)``: ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
S_t C_t + D_h x_t``; ``N_G(y * SiLU(z)) W_out``, the norm in ``G`` groups.
The width is ``mamba_num_heads x mamba_head_dim`` (``expand`` is not read,
as the published modeling code does not). *Experts*: ``sigma =
sigmoid(u W_r)`` over all published experts, the ``num_experts_per_tok``
largest of ``sigma + b``, weights the chosen ``sigma`` over their sum times
``routed_scaling_factor``; ``E(u) = W_down relu(W_up u)^2``, the shared
expert of the same form; the chosen experts *held* and the shared expert
add up, what the absent ones would add is left out here as in the program.
*Attention*: 32 query heads over 2 KV heads of 128 (query head ``h`` reads
KV head ``h // 16``), scale ``128^-1/2``, causal, **no rotary embedding**.

**Weights** (random from the seed, listed under ``assumed`` in the file).
Embedding 1; norm weights ``1 + 0.1 n``; ``W_in`` and the attention's
``W_q, W_k, W_v``, router, experts' and shared expert's ``W_up`` at
``D^-1/2``; the convolution at ``K^-1/2`` and its bias 0.1; ``W_out`` at
``(H P)^-1/2``, the attention's ``W_o`` at ``(Hq Dh)^-1/2``, the shared
expert's ``W_down`` at ``F_s^-1/2`` and the routed experts' at
``ROUTED_DOWN x F^-1/2`` (a route that flips between the program's
bfloat16 and the reference's float32 puts a whole held expert's term into
one and not the other, as in ``families/kimi_k2.py``); the selection bias at
the file's ``router_bias_std``. The dynamics follow the published
initialisation: ``A_log = log(U[1, 16])``, ``dt_bias = softplus^-1(dt)``,
``dt`` log-uniform in ``[time_step_min, time_step_max]`` floored at
``time_step_floor``, ``D = 1``.

**Reference.** The equations above in jax.numpy, float32,
``Precision.HIGHEST``, one whole sequence and one layer at a time, the
layer's weights made from the seed through ``make_leaf`` and dropped again:
no cache, no pages, no kernel, no grouped product (the held experts a plain
loop with a mask), and the Mamba layer as its **sequential** recurrence, a
position at a time, sharing none of the program's block algebra.
``precision`` also names the planted faults, computed in float32:
``state_not_carried`` (the state starts from zero at every ``chunk_size``
positions, as a block scan that drops what it carries between blocks does),
``stale_slot_state`` (the sequence starts from the state and convolution
tail that the same sequence left, as a slot whose last request's state an
admission kept), ``no_conv_tail`` (the convolution sees only its own
position, as a step that drops the tail does), ``ungated_norm`` (no
``SiLU(z)`` before the norm) and ``plain_relu`` (``relu`` where the experts
square it).

**Counts.** What the mathematics needs: the blocks' matrix products (the
routed experts at the held share of a token's ``K``), the recurrence's
``5 H P N`` operations a position and layer, the convolution, causal
attention at half the square, one position's logits over the rows held.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import autodist_tpu.models.nemotron_h as program    # no such model, no such cell
from perfbench.harness import counts, weights
from perfbench.harness.reference import best_logits, logit_gaps, matmul  # noqa: F401

# The catalog row's widths (model-configs guide, architectures.jsonl,
# "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"): a configuration of this family
# carries them unchanged.
PUBLISHED = {"hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
             "num_key_value_heads": 2, "mamba_num_heads": 64, "mamba_head_dim": 64,
             "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
             "intermediate_size": 1856, "moe_intermediate_size": 1856,
             "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
             "n_shared_experts": 1, "routed_scaling_factor": 2.5,
             "layer_norm_epsilon": 1e-05, "mlp_hidden_act": "relu2",
             "mamba_hidden_act": "silu", "time_step_min": 0.001,
             "time_step_max": 0.1, "time_step_floor": 0.0001}
REDUCIBLE = ("num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
             "vocab_size")
FAULTS = ("state_not_carried", "stale_slot_state", "no_conv_tail", "ungated_norm",
          "plain_relu")
KERNELS = ("ssm_state_update", "gmm", "paged_attention")
ROUTED_DOWN = 0.1     # the routed experts' W_down, in units of F^-1/2
QUERY_WINDOW = 512
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ configuration
def pattern(model: dict) -> str:
    return model["hybrid_override_pattern"]


def check_config(model: dict, reduced) -> None:
    """Raise where a width is not the published one, ``reduced`` names
    anything but the four counts a chip's share cuts, or the pattern is not
    the published one's first layers."""
    for key, value in PUBLISHED.items():
        if model[key] != value:
            raise ValueError(f"{key} is {model[key]}; Nemotron-3-Nano publishes {value}")
    for key in reduced:
        if key not in REDUCIBLE:
            raise ValueError(f"`reduced` may name only {REDUCIBLE}: {key!r}")
    kept = pattern(model)
    if len(kept) != model["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not hold num_hidden_layers")
    published = model.get("published", {}).get("hybrid_override_pattern", kept)
    if not published.startswith(kept):
        raise ValueError("hybrid_override_pattern is not the published "
                         "pattern's first layers")
    if any(kept.count(c) == 0 for c in "ME*"):
        raise ValueError("a kind of layer is missing from the pattern kept")
    first, count = experts_held(model)
    if count != model["n_routed_experts"] or first + count > routed_experts(model):
        raise ValueError("the share's experts are not the file's n_routed_experts")


def vocab_size(model: dict) -> int:
    return int(model["vocab_size"])


def routed_experts(model: dict) -> int:
    """What the router scores over: the published count."""
    return int(model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"]))


def experts_held(model: dict):
    """``(first, count)`` of the routed experts this chip holds."""
    share = model.get("share", {})
    return (int(share.get("experts_first", 0)),
            int(share.get("experts_count", model["n_routed_experts"])))


def layers(model: dict, kind: str):
    return [i for i, c in enumerate(pattern(model)) if c == kind]


def expert_layers(model: dict) -> int:
    return len(layers(model, "E"))


def held_expert_slots(model: dict) -> int:
    """Held experts times expert layers: what ``moe_experts_hit`` of a
    step can reach."""
    return experts_held(model)[1] * expert_layers(model)


def _dtype(model: dict):
    return jnp.dtype(model.get("param_dtype", "bfloat16"))


def _dims(model: dict):
    """``(D, H, P, G, N, K)`` of a Mamba layer."""
    return (model["hidden_size"], model["mamba_num_heads"], model["mamba_head_dim"],
            model["n_groups"], model["ssm_state_size"], model["conv_kernel"])


def _conv_dim(model: dict) -> int:
    _, h, p, g, n, _ = _dims(model)
    return h * p + 2 * g * n


# ------------------------------------------------------------------ weights
def _layout(model: dict):
    d, h, p, g, n, k = _dims(model)
    di, conv = h * p, _conv_dim(model)
    hq, hkv, dh = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    f, fs = model["moe_intermediate_size"], model["moe_shared_expert_intermediate_size"]
    n_held, n_all = experts_held(model)[1], routed_experts(model)
    unit = d ** -0.5
    out = {("embed", "embedding"): ((vocab_size(model), d), "normal", 1.0),
           ("norm_f", "weight"): ((d,), "scale", 0.1),
           ("head", "kernel"): ((d, vocab_size(model)), "normal", unit)}
    for i, kind in enumerate(pattern(model)):
        lay = f"layers_{i}"
        out[(lay, "norm", "weight")] = ((d,), "scale", 0.1)
        if kind == "M":
            m = (lay, "mixer")
            out[m + ("in_proj", "kernel")] = ((d, di + conv + h), "normal", unit)
            out[m + ("conv", "kernel")] = ((k, conv), "normal", k ** -0.5)
            out[m + ("conv", "bias")] = ((conv,), "normal", 0.1)
            out[m + ("dt_bias",)] = ((h,), "dt_bias", (
                model["time_step_min"], model["time_step_max"], model["time_step_floor"]))
            out[m + ("A_log",)] = ((h,), "a_log", (1.0, 16.0))
            out[m + ("D",)] = ((h,), "ones", 1.0)
            out[m + ("norm", "weight")] = ((di,), "scale", 0.1)
            out[m + ("out_proj", "kernel")] = ((di, d), "normal", di ** -0.5)
        elif kind == "E":
            out[(lay, "router", "kernel")] = ((d, n_all), "normal", unit)
            out[(lay, "router", "bias")] = ((n_all,), "normal", model["router_bias_std"])
            out[(lay, "experts", "up")] = ((n_held, d, f), "normal", unit)
            out[(lay, "experts", "down")] = ((n_held, f, d), "normal",
                                             ROUTED_DOWN * f ** -0.5)
            out[(lay, "shared", "up", "kernel")] = ((d, fs), "normal", unit)
            out[(lay, "shared", "down", "kernel")] = ((fs, d), "normal", fs ** -0.5)
        else:
            for w, (shape, std) in {"wq": ((d, hq * dh), unit), "wk": ((d, hkv * dh), unit),
                                    "wv": ((d, hkv * dh), unit),
                                    "wo": ((hq * dh, d), (hq * dh) ** -0.5)}.items():
                out[(lay, "attn", w, "kernel")] = (shape, "normal", std)
    return out


def _make_leaf(key, index, shape, kind, spec, dtype):
    """The kinds of leaf the harness makes, and the Mamba layer's own: its
    decay ``A_log = log(U[lo, hi])``, its time step's bias ``softplus^-1(dt)``
    and its skip ``D = 1``."""
    k = jax.random.fold_in(key, index)
    if kind == "a_log":
        lo, hi = spec
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, lo, hi))
    elif kind == "dt_bias":
        lo, hi, floor = spec
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    else:
        return weights._make_leaf(key, index, shape, kind, spec, dtype)
    return x.astype(dtype)


def param_shapes(model: dict):
    return weights.param_shapes(_layout(model), _dtype(model))


def make_params(model: dict, seed: int, shardings=None):
    """The whole tree, a top-level group a call (a layer; the embedding;
    the head): layers of one kind share one compiled program, the leaves'
    numbers being arguments."""
    layout, dtype = _layout(model), _dtype(model)
    order = weights._order(layout)
    key = weights.seed_key(seed)

    @partial(jax.jit, static_argnums=(2,))
    def group(key, indices, specs):
        return [_make_leaf(key, i, *spec, dtype) for i, spec in zip(indices, specs)]

    by_top = {}
    for path in sorted(layout):
        by_top.setdefault(path[0], []).append(path)
    flat = {}
    for paths in by_top.values():
        indices = jnp.asarray([order[p] for p in paths], jnp.int32)
        flat.update(zip(paths, group(key, indices, tuple(layout[p] for p in paths))))
    tree = weights._nest(flat)
    return tree if shardings is None else jax.device_put(tree, shardings)


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _one_leaf(key, index, shape, kind, spec, dtype):
    return _make_leaf(key, index, shape, kind, spec, dtype)


def make_leaf(model: dict, seed: int, path: tuple):
    """One leaf alone, the same values ``make_params`` gives it."""
    layout = _layout(model)
    return _one_leaf(weights.seed_key(seed), jnp.int32(weights._order(layout)[path]),
                     *layout[path], _dtype(model))


def reference_params(model: dict, seed: int):
    """The seed, handed on: ``next_token_logits`` makes each layer's
    weights when it reaches the layer."""
    return {"seed": int(seed)}


# -------------------------------------------------------- the program's side
def program_config(model: dict, **more):
    """The program's ``NemotronHConfig`` for a configuration file: the
    published sizes, the share held, and nothing the program chooses for
    itself (``more`` is for a test that pins one)."""
    keys = ("vocab_size", "hidden_size", "hybrid_override_pattern",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
            "conv_kernel", "chunk_size", "time_step_min", "time_step_max",
            "time_step_floor", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "layer_norm_epsilon")
    kw = {k: model[k] for k in keys}
    kw["n_routed_experts"] = routed_experts(model)
    kw["experts_held"] = experts_held(model)
    kw["max_position_embeddings"] = model["serving"]["max_len"]
    kw["dtype"] = jnp.dtype(model.get("compute_dtype", "bfloat16"))
    kw.update(more)
    return program.NemotronHConfig(**kw)


def decode_model(model: dict):
    """What ``AutoDist.build_inference(params, decode_model=...)`` is given."""
    return program.decode_model(program_config(model))


# ---------------------------------------------------------------- reference
def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _relu2(x, fault):
    r = jax.nn.relu(x)
    return r if fault == "plain_relu" else r * r


def _recurrence(x, dt, a, b, c, d, s0, reset_every, fault):
    """A position at a time: ``x [S, H, P]``, ``dt [S, H]``, ``b, c [S, G,
    N]`` from ``s0 [H, P, N]``; returns ``(y [S, H, P], last state)``."""
    heads, per = x.shape[1], x.shape[1] // b.shape[1]

    def step(s, inp):
        t, xt, dtt, bt, ct = inp
        if fault == "state_not_carried":
            s = jnp.where(t % reset_every == 0, 0.0, s)
        bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)  # [H, N]
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, ch, precision=_HIGHEST) + d[:, None] * xt
        return s, y

    s, y = jax.lax.scan(step, s0, (jnp.arange(x.shape[0]), x, dt, b, c))
    return y, s


def _conv(xbc, w, bias, tail, fault):
    """Causal depthwise convolution of ``xbc [S, C]`` after ``tail [K-1,
    C]``, then SiLU."""
    k = w.shape[0]
    if fault == "no_conv_tail":
        return jax.nn.silu(xbc * w[k - 1] + bias)
    full = jnp.concatenate([tail, xbc], axis=0)
    s = xbc.shape[0]
    return jax.nn.silu(sum(full[j:j + s] * w[j] for j in range(k)) + bias)


def mamba_block(p, x, *, heads, per_head, groups, state, eps, reset_every,
                precision, fault=None):
    """One Mamba block on one sequence ``x [S, D]`` (float32)."""
    s = x.shape[0]
    m = p["mixer"]
    u = _rmsnorm(x, p["norm"]["weight"], eps)
    proj = matmul(u, m["in_proj"]["kernel"], precision)
    di = heads * per_head
    conv = di + 2 * groups * state
    z, xbc, dt_raw = proj[:, :di], proj[:, di:di + conv], proj[:, di + conv:]
    w, bias = m["conv"]["kernel"], m["conv"]["bias"]
    dt = jax.nn.softplus(dt_raw + m["dt_bias"])
    a = -jnp.exp(m["A_log"])
    k = w.shape[0]

    def run(tail, s0):
        h = _conv(xbc, w, bias, tail, fault)
        xs = h[:, :di].reshape(s, heads, per_head)
        bs = h[:, di:di + groups * state].reshape(s, groups, state)
        cs = h[:, di + groups * state:].reshape(s, groups, state)
        return _recurrence(xs, dt, a, bs, cs, m["D"], s0, reset_every, fault)

    tail = jnp.zeros((k - 1, conv), jnp.float32)
    s0 = jnp.zeros((heads, per_head, state), jnp.float32)
    if fault == "stale_slot_state":
        # the slot's last request was this same sequence: its state and tail
        _, s0 = run(tail, s0)
        tail = xbc[s - (k - 1):]
    y, _ = run(tail, s0)
    y = y.reshape(s, di)
    if fault != "ungated_norm":
        y = y * jax.nn.silu(z)
    g = y.reshape(s, groups, -1)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
    y = g.reshape(s, di) * m["norm"]["weight"]
    return x + matmul(y, m["out_proj"]["kernel"], precision)


def expert_block(p, x, *, first, k, scaling, eps, precision, fault=None):
    """One expert block: the held experts' share of the routed sum and the
    shared expert, relu² throughout."""
    u = _rmsnorm(x, p["norm"]["weight"], eps)
    sigma = jax.nn.sigmoid(matmul(u, p["router"]["kernel"], precision))
    _, chosen = jax.lax.top_k(sigma + p["router"]["bias"], k)            # [S, K]
    wts = jnp.take_along_axis(sigma, chosen, axis=-1)
    wts = wts / wts.sum(-1, keepdims=True) * scaling
    e = p["experts"]
    out = jnp.zeros_like(u)
    for j in range(e["up"].shape[0]):
        mine = jnp.where(chosen == first + j, wts, 0.0).sum(-1)          # [S]
        y = matmul(_relu2(matmul(u, e["up"][j], precision), fault), e["down"][j], precision)
        out = out + mine[:, None] * y
    sh = p["shared"]
    out = out + matmul(_relu2(matmul(u, sh["up"]["kernel"], precision), fault),
                       sh["down"]["kernel"], precision)
    return x + out


def attention_block(p, x, *, hq, hkv, dh, eps, precision, fault=None):
    """Causal grouped attention on one sequence, a window of queries at a
    time."""
    s = x.shape[0]
    a = p["attn"]
    u = _rmsnorm(x, p["norm"]["weight"], eps)
    q = matmul(u, a["wq"]["kernel"], precision).reshape(s, hkv, hq // hkv, dh)
    keys = matmul(u, a["wk"]["kernel"], precision).reshape(s, hkv, dh).transpose(1, 2, 0)
    values = matmul(u, a["wv"]["kernel"], precision).reshape(s, hkv, dh).transpose(1, 0, 2)
    n_win = -(-s // QUERY_WINDOW)
    q = jnp.pad(q, ((0, n_win * QUERY_WINDOW - s), (0, 0), (0, 0), (0, 0)))

    def one_window(args):
        wi, qw = args                                                     # [W, Hkv, r, Dh]
        qg = qw.transpose(1, 2, 0, 3).reshape(hkv, -1, dh)                # [Hkv, r W, Dh]
        scores = matmul(qg, keys, precision) * dh ** -0.5                 # [Hkv, r W, S]
        q_pos = jnp.tile(wi * QUERY_WINDOW + jnp.arange(QUERY_WINDOW), hq // hkv)
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos[:, None], scores, -jnp.inf)
        o = matmul(jax.nn.softmax(scores, axis=-1), values, precision)    # [Hkv, r W, Dh]
        return o.reshape(hkv, hq // hkv, QUERY_WINDOW, dh).transpose(2, 0, 1, 3)

    o = jax.lax.map(one_window, (jnp.arange(n_win), q.reshape(
        n_win, QUERY_WINDOW, hkv, hq // hkv, dh)))
    return x + matmul(o.reshape(-1, hq * dh)[:s], a["wo"]["kernel"], precision)


_mamba = jax.jit(mamba_block, static_argnames=(
    "heads", "per_head", "groups", "state", "eps", "reset_every", "precision", "fault"))
_experts = jax.jit(expert_block, static_argnames=(
    "first", "k", "scaling", "eps", "precision", "fault"))
_attention = jax.jit(attention_block, static_argnames=(
    "hq", "hkv", "dh", "eps", "precision", "fault"))


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, kernel, eps, precision):
    return matmul(_rmsnorm(x, norm_w, eps), kernel, precision)


def _layer_params(model: dict, seed: int, name: str):
    """One top-level group of the tree, float32, made from the seed."""
    flat = {path[1:]: make_leaf(model, seed, path).astype(jnp.float32)
            for path in _layout(model) if path[0] == name}
    return weights._nest(flat)


def logits(params, tokens, model: dict, precision: str = "float32"):
    """Next-token logits ``[S, V]`` (the rows held) of one sequence
    ``tokens [S]``."""
    fault = precision if precision in FAULTS else None
    precision = "float32" if fault else precision
    seed, eps = params["seed"], model["layer_norm_epsilon"]
    _, h, p, g, n, _ = _dims(model)
    x = _layer_params(model, seed, "embed")["embedding"][tokens]
    for i, kind in enumerate(pattern(model)):
        lp = _layer_params(model, seed, f"layers_{i}")
        if kind == "M":
            x = _mamba(lp, x, heads=h, per_head=p, groups=g, state=n, eps=eps,
                       reset_every=model["chunk_size"], precision=precision, fault=fault)
        elif kind == "E":
            x = _experts(lp, x, first=experts_held(model)[0],
                         k=model["num_experts_per_tok"],
                         scaling=model["routed_scaling_factor"], eps=eps,
                         precision=precision, fault=fault)
        else:
            x = _attention(lp, x, hq=model["num_attention_heads"],
                           hkv=model["num_key_value_heads"], dh=model["head_dim"],
                           eps=eps, precision=precision, fault=fault)
    return _head(x, _layer_params(model, seed, "norm_f")["weight"],
                 _layer_params(model, seed, "head")["kernel"], eps, precision)


def next_token_logits(params, tokens, model: dict, precision: str):
    """For one padded sequence ``tokens [S]``: per position the best next
    logit, its token, and the whole ``[S, V]`` table. ``precision`` is one
    of ``harness/reference.PRECISIONS`` or of ``FAULTS``."""
    table = logits(params, tokens, model, precision)
    return (*best_logits(table), table)


# ------------------------------------------------------------ required work
def matmul_params(model: dict) -> float:
    """Parameters in a matrix product for every token, the head left out:
    a Mamba layer's two projections, an attention layer's four, and an
    expert layer's router, shared expert and the held share of a token's
    ``K`` routed experts."""
    d, h, p, _, _, _ = _dims(model)
    mamba = d * (h * p + _conv_dim(model) + h) + h * p * d
    hq, kv = model["num_attention_heads"] * model["head_dim"], \
        model["num_key_value_heads"] * model["head_dim"]
    attn = d * (hq + 2 * kv) + hq * d
    routed = model["num_experts_per_tok"] * experts_held(model)[1] / routed_experts(model)
    expert = (d * routed_experts(model) + 2 * d * model["moe_shared_expert_intermediate_size"]
              + routed * 2 * d * model["moe_intermediate_size"])
    return (len(layers(model, "M")) * mamba + len(layers(model, "*")) * attn
            + expert_layers(model) * expert)


def head_flops(model: dict) -> int:
    return 2 * model["hidden_size"] * vocab_size(model)


def _token_flops(model: dict) -> float:
    """What a position needs outside attention's pairs and the head: the
    products, and a Mamba layer's convolution and recurrence."""
    _, h, p, _, n, k = _dims(model)
    mamba = 2 * k * _conv_dim(model) + 5 * h * p * n
    return 2 * matmul_params(model) + len(layers(model, "M")) * mamba


def _pair_flops(model: dict) -> int:
    """A query-key pair of every head: score and weighted value."""
    return 2 * model["num_attention_heads"] * 2 * model["head_dim"]


def prefill_flops(model: dict, prompt: int) -> float:
    """A prompt up to its first generated token."""
    return (_token_flops(model) * prompt
            + len(layers(model, "*")) * _pair_flops(model) * counts.causal_pairs(prompt)
            + head_flops(model))


def decode_flops(model: dict, context: int) -> float:
    """One generated token whose query sees ``context`` positions."""
    return (_token_flops(model) + len(layers(model, "*")) * _pair_flops(model) * context
            + head_flops(model))


def kernel_work(kernel: str, model: dict, facts: dict):
    """``(operations, bytes)`` one call of a named kernel requires (one
    layer). ``ssm_state_update``: each row the step updates (``ssm_rows``,
    a step's mean) reads and writes its float32 state once and reads its
    ``x``, ``dt``, ``B``, ``C`` and writes ``y``; ``5 H P N`` operations a
    row. ``gmm`` (one projection of the experts' grouped product): the
    weights of the experts that have a pair, once, and the pairs' rows in
    and out; ``pairs`` and ``experts_hit`` are a decode step's, summed
    over the expert layers as its span carries them, and a call's are a
    layer's share of them. ``paged_attention`` (one attention layer of a
    decode step, each KV head's query heads folded into its query axis):
    ``rows`` queries that each see ``context`` positions read the keys and
    values of the KV heads there once, and every query head's two
    products."""
    d, h, p, g, n, _ = _dims(model)
    if kernel == "ssm_state_update":
        rows = facts["ssm_rows"]
        return rows * 5 * h * p * n, rows * 4 * (2 * h * p * n + 2 * h * p + h + 2 * g * n)
    if kernel == "gmm":
        f, n_layers = model["moe_intermediate_size"], expert_layers(model)
        pairs, hit = facts["pairs"] / n_layers, facts["experts_hit"] / n_layers
        return pairs * 2 * d * f, (hit * d * f + pairs * (d + f)) * 2
    if kernel == "paged_attention":
        pairs = facts["rows"] * facts["context"]
        kv = model["num_key_value_heads"] * model["head_dim"]
        return pairs * _pair_flops(model), pairs * 2 * kv * 2
    raise KeyError(f"family nemotron_h counts no kernel named {kernel!r}; "
                   f"it has {KERNELS}")
