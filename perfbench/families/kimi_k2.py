"""Family ``kimi_k2``: everything that is the model's, for a configuration
file that states ``"family": "kimi_k2"`` and carries the published keys of
huggingface.co/moonshotai/Kimi-K2.6 ``config.json`` (``model_type:
kimi_k2``, the DeepSeek-V3 block). What a family file gives the harness is
listed at the top of ``families/gpt2.py``; this one serves only.

It imports the program's model at import time, so a checkout that has no
``autodist_tpu.models.kimi_k2`` fails on a cell of this family in its first
second, before a byte of weights is made.

**What the file's cut keys mean.** ``n_routed_experts`` in the file is the
number of routed experts *this chip holds* (its ``share`` group says which);
``published.n_routed_experts`` is what the router scores over and stays the
router's width. ``vocab_size`` is the rows of the embedding and head this
chip holds (its slice of the published vocabulary). ``num_hidden_layers``
is the layers on this chip (the first ones: the dense layer, then expert
layers).

**The layer** (DeepSeek-V2 arXiv 2405.04434, DeepSeek-V3 arXiv 2412.19437).
Pre-norm, ``N(u) = u / sqrt(mean(u^2) + eps) w``, no biases: ``h = x +
Attn(N1 x)``, ``y = h + FFN(N2 h)``; final norm, untied head. *Latent
attention:* ``c_q = Nq(u W_qa)``, ``q = c_q W_qb`` in H heads of ``[q_nope
dn | q_rope dr]``; ``[c_kv | k_r] = u W_kva``, ``c = Nkv(c_kv)``, ``k_rope =
R(k_r)`` (one for all heads), ``q_rope <- R(q_rope)``; ``[k_nope | v]_head
= c W_kvb``; ``score = s (q_nope.k_nope + q_rope.k_rope)``, causal softmax,
``o = sum p v -> W_o``; ``s = (dn + dr)^-1/2 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1``. ``R`` is YaRN's rotation (``yarn_inv_freq`` below, its own
copy of the arithmetic), pairs taken half-split. *Experts* (layers
``first_k_dense_replace`` on; layer 0 a gated MLP): ``sigma = sigmoid(u
W_g)`` over all published experts; the ``num_experts_per_tok`` largest of
``sigma + b``; weights the chosen ``sigma`` over their sum times
``routed_scaling_factor``; ``FFN(u) = sum over the chosen experts *held*
of w_e E_e(u) + E_shared(u)``: what the absent experts would add is left
out here as in the program, and that partial result goes on to the next
layer.

**Weights** (random from the seed; scales chosen so that the comparison
can see each mechanism, listed under ``assumed`` in the file). Embedding 1;
norm weights ``1 + 0.1 n``; ``W_qa, W_kva`` at ``D^-1/2``, ``W_qb`` at
``q_lora_rank^-1/2``, ``W_kvb`` at ``kv_lora_rank^-1/2``: ``s q.k`` has a
standard deviation near 2 and the rotated part carries a third of its
variance; ``W_o`` at ``3 (H dv)^-1/2``; gated MLPs ``D^-1/2, D^-1/2,
F^-1/2``; router ``D^-1/2``; head ``D^-1/2``; the selection bias ``b`` at
the file's ``router_bias_std`` (0.002: near half the spacing of ``sigma``
between the 8th and the 9th of 384, so it changes some selections and
leaves the experts' loads near even; at 0.01, where an expert one standard
deviation up is chosen about a quarter more often, ``serve_tok_s`` spread
3.1% over twelve seeds against the 3.5% a new cell is admitted under).
**The routed experts' ``W_down`` is
at ``0.5 F^-1/2``, and that is set by the comparison, not by taste:** the
8th and 9th of 384 scores lie about 0.005 apart, bfloat16's error in the
residual moves a score by about a tenth of that, so the served path and the
float32 reference choose another 8th expert for some percent of
(token, layer)s, and whenever one of the two is held (12/384) a whole
expert's term is in one and not in the other: a jump in that token's
logits, not rounding. At ``3 F^-1/2`` the program read ``logit_gap`` 0.40 to
0.64 on five seeds and the *reference itself in bfloat16* 0.37, beside the
fp8 control's 0.85 (my chip runs, PR 36): no limit fits between. The jump
scales with the routed term; at 0.5 the program reads 0.015 to 0.138 on 23
seeds, the fp8 control 0.54 to 0.74 and ``unnormalised_topk`` 0.95 to
1.96. The same arithmetic makes ``biased_weights`` unreadable at any scale
tried: under ``norm_topk_prob`` the chosen weights are near uniform
whatever ``b`` adds (it read 0.0001 to 0.043, under the program's own
readings: the issue's criterion that the limit lie under every planted
fault is NOT met for this one; the limits file says so).

**Reference.** The equations above in jax.numpy, float32,
``Precision.HIGHEST``, one whole sequence at a time: no cache, no pages, no
kernel, no absorbed form, no grouped product (the held experts are a plain
loop with a mask). One layer at a time, its weights made from the seed
through ``make_leaf`` and dropped again (an expert layer in float32 is 2.7
GB); inside a layer the queries go a window of 512 at a time (all heads'
scores of 3,840 x 3,840 would be 3.8 GB). ``precision`` also names the
planted faults, computed in float32: ``no_rope_term`` (scores without
``q_rope.k_rope``), ``biased_weights`` (weights from ``sigma + b``),
``unnormalised_topk`` (no division by the sum), ``no_shared_expert``.

**Counts.** What the mathematics needs: the block's matrix products (the
routed experts at the held share of a token's ``K``, ``K x held /
published`` of an expert on average), one position's logits over the rows
held, attention expanded for a prompt (each position's keys and values made
once, ``dn + dr`` and ``dv`` a pair and head) and absorbed for a decode
step (``dn x Ckv`` and ``Ckv x dv`` a head for the two absorbed
projections, ``(Ckv + dr) + Ckv`` a pair and head).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import autodist_tpu.models.kimi_k2 as program    # no such model, no such cell
from perfbench.harness import counts, weights
from perfbench.harness.reference import best_logits, logit_gaps, matmul  # noqa: F401

# The catalog row's widths (model-configs guide, architectures.jsonl,
# "Kimi-K2.6"): a configuration of this family carries them unchanged.
PUBLISHED = {"hidden_size": 7168, "intermediate_size": 18432,
             "moe_intermediate_size": 2048, "q_lora_rank": 1536,
             "kv_lora_rank": 512, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "v_head_dim": 128,
             "num_attention_heads": 64, "num_key_value_heads": 64,
             "num_experts_per_tok": 8, "n_shared_experts": 1,
             "first_k_dense_replace": 1, "routed_scaling_factor": 2.827,
             "rope_theta": 50000, "rms_norm_eps": 1e-05,
             "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                              "mscale": 1, "mscale_all_dim": 1,
                              "original_max_position_embeddings": 4096,
                              "type": "yarn"}}
REDUCIBLE = ("num_hidden_layers", "n_routed_experts", "vocab_size")
FAULTS = ("no_rope_term", "biased_weights", "unnormalised_topk",
          "no_shared_expert")
KERNELS = ("mla_paged_attention", "gmm")
ROUTED_DOWN = 0.5     # the routed experts' W_down, in units of F^-1/2
QUERY_WINDOW = 512


# ------------------------------------------------------------ configuration
def check_config(model: dict, reduced) -> None:
    """Raise where a width is not the published one, or ``reduced`` names
    anything but the three counts a chip's share cuts."""
    for key, value in PUBLISHED.items():
        if model[key] != value:
            raise ValueError(f"{key} is {model[key]}; Kimi-K2.6 publishes {value}")
    for key in reduced:
        if key not in REDUCIBLE:
            raise ValueError(f"`reduced` may name only {REDUCIBLE}: {key!r}")
    first, count = experts_held(model)
    if count != model["n_routed_experts"] or first + count > routed_experts(model):
        raise ValueError("the share's experts are not the file's n_routed_experts")
    if model["num_hidden_layers"] <= model["first_k_dense_replace"]:
        raise ValueError("no expert layer is left")


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


def expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def held_expert_slots(model: dict) -> int:
    """Held experts times expert layers: what ``moe_experts_hit`` of a
    step can reach."""
    return experts_held(model)[1] * expert_layers(model)


def _dtype(model: dict):
    return jnp.dtype(model.get("param_dtype", "bfloat16"))


def _dims(model: dict):
    return (model["hidden_size"], model["num_attention_heads"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["kv_lora_rank"], model["q_lora_rank"])


# ------------------------------------------------------------------ weights
def _layout(model: dict):
    d, h, dn, dr, dv, ckv, qr = _dims(model)
    f, inter = model["moe_intermediate_size"], model["intermediate_size"]
    n_held, n_all = experts_held(model)[1], routed_experts(model)
    unit = d ** -0.5
    out = {("embed", "embedding"): ((vocab_size(model), d), "normal", 1.0),
           ("norm_f", "weight"): ((d,), "scale", 0.1),
           ("head", "kernel"): ((d, vocab_size(model)), "normal", unit)}
    for i in range(model["num_hidden_layers"]):
        lay = f"layers_{i}"
        out[(lay, "norm1", "weight")] = ((d,), "scale", 0.1)
        out[(lay, "norm2", "weight")] = ((d,), "scale", 0.1)
        attn = {"wq_a": ((d, qr), unit), "wq_b": ((qr, h * (dn + dr)), qr ** -0.5),
                "wkv_a": ((d, ckv + dr), unit),
                "wkv_b": ((ckv, h * (dn + dv)), ckv ** -0.5),
                "wo": ((h * dv, d), 3 * (h * dv) ** -0.5)}
        for w, (shape, std) in attn.items():
            out[(lay, "attn", w, "kernel")] = (shape, "normal", std)
        out[(lay, "attn", "q_norm", "weight")] = ((qr,), "scale", 0.1)
        out[(lay, "attn", "kv_norm", "weight")] = ((ckv,), "scale", 0.1)
        if i < model["first_k_dense_replace"]:
            mlps = {"mlp": inter}
        else:
            mlps = {"shared": f * model["n_shared_experts"]}
            out[(lay, "router", "kernel")] = ((d, n_all), "normal", unit)
            out[(lay, "router", "bias")] = ((n_all,), "normal",
                                             model["router_bias_std"])
            for w, (shape, std) in {"gate": ((n_held, d, f), unit),
                                    "up": ((n_held, d, f), unit),
                                    "down": ((n_held, f, d), ROUTED_DOWN * f ** -0.5)}.items():
                out[(lay, "experts", w)] = (shape, "normal", std)
        for name, width in mlps.items():
            for w, (a, b, std) in {"gate": (d, width, unit), "up": (d, width, unit),
                                   "down": (width, d, width ** -0.5)}.items():
                out[(lay, name, w, "kernel")] = ((a, b), "normal", std)
    return out


def param_shapes(model: dict):
    return weights.param_shapes(_layout(model), _dtype(model))


def make_params(model: dict, seed: int, shardings=None):
    """The whole tree, a top-level group a call (a layer; the embedding;
    the head), so that no call holds more float32 normals than a layer's
    beside the bfloat16 leaves. The expert layers share one compiled
    program, the leaves' numbers being arguments."""
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
    """The program's ``KimiK2Config`` for a configuration file: the
    published sizes, the share held, and nothing the program chooses for
    itself (``more`` is for a test that pins one)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "routed_scaling_factor", "norm_topk_prob",
            "rms_norm_eps", "rope_theta", "rope_scaling")
    kw = {k: model[k] for k in keys}
    kw["n_routed_experts"] = routed_experts(model)
    kw["experts_held"] = experts_held(model)
    kw["max_position_embeddings"] = model["serving"]["max_len"]
    kw["dtype"] = jnp.dtype(model.get("compute_dtype", "bfloat16"))
    kw.update(more)
    return program.KimiK2Config(**kw)


def decode_model(model: dict):
    """What ``AutoDist.build_inference(params, decode_model=...)`` is given."""
    return program.decode_model(program_config(model))


# ---------------------------------------------------------------- reference
def _rmsnorm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model: dict) -> np.ndarray:
    """``dr / 2`` frequencies: ``f_i = theta^(-2i/dr)`` where a pair turns
    more than ``beta_fast`` times within the original context, ``f_i /
    factor`` where fewer than ``beta_slow``, a linear ramp between."""
    d, base, rs = model["qk_rope_head_dim"], float(model["rope_theta"]), model["rope_scaling"]
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f / rs["factor"] * ramp + f * (1.0 - ramp)).astype(np.float32)


def softmax_scale(model: dict) -> float:
    rs = model["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, inv_freq, cos_sin_scale):
    """``x [S, ..., dr]`` at positions ``0..S-1``, half-split pairs."""
    s, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32).reshape((s,) + (1,) * (x.ndim - 1)) * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1) * cos_sin_scale
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1) * cos_sin_scale
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + turned * sin


def _gated(p, u, precision):
    return matmul(jax.nn.silu(matmul(u, p["gate"]["kernel"], precision))
                  * matmul(u, p["up"]["kernel"], precision),
                  p["down"]["kernel"], precision)


def _experts(p, u, *, first, k, scaling, precision, fault):
    """The held experts' share of the routed sum and the shared expert."""
    sigma = jax.nn.sigmoid(matmul(u, p["router"]["kernel"], precision))
    biased = sigma + p["router"]["bias"]
    _, chosen = jax.lax.top_k(biased, k)                              # [S, K]
    source = biased if fault == "biased_weights" else sigma
    w = jnp.take_along_axis(source, chosen, axis=-1)
    if fault != "unnormalised_topk":
        w = w / w.sum(-1, keepdims=True)
    w = w * scaling
    out = jnp.zeros_like(u)
    e = p["experts"]
    for j in range(e["gate"].shape[0]):
        mine = jnp.where(chosen == first + j, w, 0.0).sum(-1)        # [S]
        y = matmul(jax.nn.silu(matmul(u, e["gate"][j], precision))
                   * matmul(u, e["up"][j], precision), e["down"][j], precision)
        out = out + mine[:, None] * y
    if fault != "no_shared_expert":
        out = out + _gated(p["shared"], u, precision)
    return out


def block(p, x, inv_freq, *, heads, dn, dr, dv, ckv, eps, scale, rope_scale,
          first, k, scaling, precision, fault=None):
    """One block on one sequence ``x [S, D]`` (float32). Returns the output
    and, for whoever asks how the weights' scales came out, the root mean
    square of attention's and of the FFN's part of the residual."""
    s = x.shape[0]
    a = p["attn"]
    u = _rmsnorm(x, p["norm1"]["weight"], eps)
    c_q = _rmsnorm(matmul(u, a["wq_a"]["kernel"], precision), a["q_norm"]["weight"], eps)
    q = matmul(c_q, a["wq_b"]["kernel"], precision).reshape(s, heads, dn + dr)
    kv = matmul(u, a["wkv_a"]["kernel"], precision)
    c = _rmsnorm(kv[:, :ckv], a["kv_norm"]["weight"], eps)
    k_rope = _rotate(kv[:, ckv:], inv_freq, rope_scale)                   # [S, dr]
    q_rope = _rotate(q[..., dn:], inv_freq, rope_scale)
    if fault == "no_rope_term":
        q_rope = jnp.zeros_like(q_rope)
    kvb = matmul(c, a["wkv_b"]["kernel"], precision).reshape(s, heads, dn + dv)
    keys = jnp.concatenate([kvb[..., :dn],
                            jnp.broadcast_to(k_rope[:, None], (s, heads, dr))], -1)
    keys_t = keys.transpose(1, 2, 0)                                      # [H, dn+dr, S]
    values = kvb[..., dn:].transpose(1, 0, 2)                             # [H, S, dv]
    queries = jnp.concatenate([q[..., :dn], q_rope], -1)
    n_win = -(-s // QUERY_WINDOW)
    queries = jnp.pad(queries, ((0, n_win * QUERY_WINDOW - s), (0, 0), (0, 0)))

    def one_window(args):
        wi, qw = args                                                     # [W, H, dn+dr]
        scores = matmul(qw.transpose(1, 0, 2), keys_t, precision) * scale  # [H, W, S]
        q_pos = wi * QUERY_WINDOW + jnp.arange(QUERY_WINDOW)
        scores = jnp.where(jnp.arange(s)[None, :] <= q_pos[:, None], scores, -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), values, precision).transpose(1, 0, 2)

    o = jax.lax.map(one_window, (jnp.arange(n_win), queries.reshape(
        n_win, QUERY_WINDOW, heads, dn + dr)))
    attn = matmul(o.reshape(-1, heads * dv)[:s], a["wo"]["kernel"], precision)
    x = x + attn
    u = _rmsnorm(x, p["norm2"]["weight"], eps)
    if "mlp" in p:
        ffn = _gated(p["mlp"], u, precision)
    else:
        ffn = _experts(p, u, first=first, k=k, scaling=scaling,
                       precision=precision, fault=fault)
    rms = lambda t: jnp.sqrt((t * t).mean())    # noqa: E731
    return x + ffn, (rms(attn), rms(ffn))


_block = jax.jit(block, static_argnames=(
    "heads", "dn", "dr", "dv", "ckv", "eps", "scale", "rope_scale", "first",
    "k", "scaling", "precision", "fault"))


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm_w, kernel, eps, precision):
    return matmul(_rmsnorm(x, norm_w, eps), kernel, precision)


def _layer_params(model: dict, seed: int, name: str):
    """One top-level group of the tree, float32, made from the seed."""
    flat = {path[1:]: make_leaf(model, seed, path).astype(jnp.float32)
            for path in _layout(model) if path[0] == name}
    return weights._nest(flat)


def logits(params, tokens, model: dict, precision: str = "float32", parts=None):
    """Next-token logits ``[S, V]`` (the rows held) of one sequence ``tokens
    [S]``; ``parts`` (a list) collects each layer's (attention, FFN)
    residual RMS."""
    fault = precision if precision in FAULTS else None
    precision = "float32" if fault else precision
    seed = params["seed"]
    d, h, dn, dr, dv, ckv, _ = _dims(model)
    rs = model["rope_scaling"]
    rope_scale = (_yarn_mscale(rs["factor"], rs["mscale"])
                  / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    inv_freq = jnp.asarray(yarn_inv_freq(model))
    x = _layer_params(model, seed, "embed")["embedding"][tokens]
    for i in range(model["num_hidden_layers"]):
        x, rms = _block(
            _layer_params(model, seed, f"layers_{i}"), x, inv_freq, heads=h,
            dn=dn, dr=dr, dv=dv, ckv=ckv, eps=model["rms_norm_eps"],
            scale=softmax_scale(model), rope_scale=rope_scale,
            first=experts_held(model)[0], k=model["num_experts_per_tok"],
            scaling=model["routed_scaling_factor"], precision=precision,
            fault=fault)
        if parts is not None:
            parts.append(rms)
    return _head(x, _layer_params(model, seed, "norm_f")["weight"],
                 _layer_params(model, seed, "head")["kernel"],
                 model["rms_norm_eps"], precision)


def next_token_logits(params, tokens, model: dict, precision: str):
    """For one padded sequence ``tokens [S]``: per position the best next
    logit, its token, and the whole ``[S, V]`` table. ``precision`` is one
    of ``harness/reference.PRECISIONS`` or of ``FAULTS``."""
    table = logits(params, tokens, model, precision)
    return (*best_logits(table), table)


# ------------------------------------------------------------ required work
def matmul_params(model: dict) -> float:
    """Parameters in a matrix product for every token, the head and the
    latent's up-projection left out (attention's counts have that): the
    attention projections a layer, the dense layers' MLP, and an expert
    layer's router, shared expert and the held share of a token's ``K``
    routed experts."""
    d, h, dn, dr, dv, ckv, qr = _dims(model)
    attn = d * qr + qr * h * (dn + dr) + d * (ckv + dr) + h * dv * d
    one = 3 * d * model["moe_intermediate_size"]
    routed = model["num_experts_per_tok"] * experts_held(model)[1] / routed_experts(model)
    expert = d * routed_experts(model) + one * (model["n_shared_experts"] + routed)
    return (model["num_hidden_layers"] * attn
            + model["first_k_dense_replace"] * 3 * d * model["intermediate_size"]
            + expert_layers(model) * expert)


def head_flops(model: dict) -> int:
    return 2 * model["hidden_size"] * vocab_size(model)


def prefill_flops(model: dict, prompt: int) -> float:
    """A prompt up to its first generated token, attention expanded: every
    position's keys and values made once, every causal pair scored and
    summed per head."""
    _, h, dn, dr, dv, ckv, _ = _dims(model)
    expand = 2 * ckv * h * (dn + dv) * prompt
    pairs = 2 * h * (dn + dr + dv) * counts.causal_pairs(prompt)
    return (2 * matmul_params(model) * prompt
            + model["num_hidden_layers"] * (expand + pairs) + head_flops(model))


def decode_flops(model: dict, context: int) -> float:
    """One generated token whose query sees ``context`` positions,
    attention absorbed."""
    _, h, dn, dr, dv, ckv, _ = _dims(model)
    absorb = 2 * h * ckv * (dn + dv)
    pairs = 2 * h * (2 * ckv + dr) * context
    return (2 * matmul_params(model)
            + model["num_hidden_layers"] * (absorb + pairs) + head_flops(model))


def kernel_work(kernel: str, model: dict, facts: dict):
    """``(operations, bytes)`` one call of a named kernel requires (one
    layer). ``mla_paged_attention``: ``rows`` rows that each see ``context``
    cached positions, every latent row (``Ckv + dr`` values of the pool's
    type) read once, ``(Ckv + dr) + Ckv`` multiply-adds a pair and head.
    ``gmm`` (one projection of the experts' grouped product): the weights
    of the experts that have a pair, once, and the pairs' rows in and out;
    ``pairs`` and ``experts_hit`` are a decode step's, summed over the
    expert layers as its span carries them, and a call's are a layer's
    share of them."""
    d, h, _, dr, _, ckv, _ = _dims(model)
    if kernel == "mla_paged_attention":
        seen = facts["rows"] * facts["context"]
        return seen * 2 * h * (2 * ckv + dr), seen * (ckv + dr) * 2
    if kernel == "gmm":
        f, layers = model["moe_intermediate_size"], expert_layers(model)
        pairs, hit = facts["pairs"] / layers, facts["experts_hit"] / layers
        return pairs * 2 * d * f, (hit * d * f + pairs * (d + f)) * 2
    raise KeyError(f"family kimi_k2 counts no kernel named {kernel!r}; "
                   f"it has {KERNELS}")
