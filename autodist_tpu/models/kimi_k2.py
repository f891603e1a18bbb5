"""Kimi K2 (the DeepSeek-V3 block): latent attention over a page pool with
no head axis, and token-choice sparse experts of which this chip holds a
share (DeepSeek-V2, arXiv 2405.04434; DeepSeek-V3, arXiv 2412.19437;
huggingface.co/moonshotai/Kimi-K2.6 ``config.json``, ``model_type:
kimi_k2``).

Pre-norm block, RMSNorm without offset, no biases: ``h = x + Attn(N1 x)``,
``y = h + FFN(N2 h)``; final norm, untied head. Residual sums, norm
statistics, router scores and every softmax are float32; leaves are held in
``param_dtype`` and matrix products run in ``dtype`` with float32
accumulation.

**Latent attention.** ``c_q = Nq(u W_qa)``, ``q = c_q W_qb`` in heads of
``[q_nope | q_rope]``; ``[c_kv | k_r] = u W_kva``, ``c = Nkv(c_kv)``,
``k_rope = R(k_r)`` (one for all heads), ``q_rope <- R(q_rope)``; ``[k_nope
| v]_head = c W_kvb``; ``score = s (q_nope.k_nope + q_rope.k_rope)``, ``s =
(dn + dr)^-1/2 m^2`` with YaRN's ``m``. ``R`` is YaRN's rotation
(:func:`yarn_inv_freq`), pairs taken half-split as ``layers.rope`` takes
them. **What is cached is ``[c | k_rope]``**, one row a position a layer
for all heads (:func:`init_paged_cache`: one leaf a layer, ``[n_pages,
page_len, page_width]``, the row's 576 values in whole lane tiles). A prefill chunk expands the
latents it sees through ``W_kvb`` and attends per head; a decode step
absorbs ``W_kvb`` into the query and the output and attends in the latent
space (``ops/paged_attention.py``). The two are the same mathematics.

**Experts** (layers ``first_k_dense_replace`` on; a gated MLP before):
``sigma = sigmoid(u W_g)`` over all ``n_routed_experts``; the
``num_experts_per_tok`` largest of ``sigma + b`` are chosen; their weights
are the chosen ``sigma`` (without ``b``) over their sum, times
``routed_scaling_factor``; ``FFN(u) = sum_chosen w_e E_e(u) + E_shared(u)``
(``models/routed.py``, the layer Nemotron-H shares).
No capacity, no token dropped. **A chip holds experts ``[first, first +
count)``** (``experts_held``): it routes over all experts, normalises over
all chosen, and adds the terms of the chosen experts it holds plus the
shared expert. Under an expert axis of several chips the partial results
add up to the uncut layer, the shared expert counted once; the exchange
that adds them is not here, and with one share the partial result is what
goes on to the next layer. The held experts' products are one grouped
matrix product a projection (``ops/grouped_matmul.py``).

Three renderings: :func:`forward` over whole sequences (expanded attention,
dense masks; tests and any later training), and the two serving programs
:func:`forward_paged_prefill_chunk` and :func:`forward_paged_decode_step`,
which :func:`decode_model` hands to
:class:`~autodist_tpu.serve.InferenceEngine`. Both end their token vector
with two facts only the device knows, summed over the expert layers:
``moe_pairs`` (token-expert pairs that fell on held experts) and
``moe_experts_hit`` (held experts with at least one pair).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models import layers as L
from autodist_tpu.models import routed
from autodist_tpu.ops import paged_attention as pa_ops
from autodist_tpu.serve import pages as serve_pages

STEP_FACTS = ("moe_pairs", "moe_experts_hit")


def _yarn_defaults() -> Dict[str, Any]:
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class KimiK2Config:
    """The source's keys, and what the program chooses."""

    vocab_size: int = 163840          # rows of the embedding and head held
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384       # what the router scores over
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Dict[str, Any] = field(default_factory=_yarn_defaults)
    max_position_embeddings: int = 262144
    # (first, count): the routed experts this chip holds; None is all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16           # compute dtype of the matmuls
    # gather | kernel | auto (the Mosaic kernels on a TPU, plain jnp off it)
    paged_attention_impl: str = "auto"
    # A page of 128 positions: a latent row is 1,152 bytes, so a page of 16
    # would be an 18 KB copy and the decode kernel's grid step would cost
    # more in index maps than its pages take to stream (PERF.md section 6).
    page_len: int = 128
    prefill_chunk: int = 512
    kv_quant: bool = False              # int8 latent pages: refused
    expert_act: str = "silu_gated"      # models/routed.py EXPERT_ACTS

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def page_width(self) -> int:
        """Columns of a cached row as the pool holds it: the latent's,
        rounded up to whole lane tiles of 128 (576 -> 640, the columns past
        the latent zero). The chip's tiled layout pads the last dim to that
        anyway; stated, XLA keeps the leaf rows-major as the kernel reads
        it, where at 576 beside a page of 128 positions it laid the
        positions minor and copied the whole pool to and from the kernel's
        layout in every program (PERF.md section 6)."""
        w = self.latent_width
        return -(-w // 128) * 128 if w >= 128 else w

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def softmax_scale(self) -> float:
        rs = self.rope_scaling or {}
        m = _yarn_mscale(rs.get("factor", 1), rs.get("mscale_all_dim", 0))
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def cache_layout(self) -> serve_pages.CacheLayout:
        return serve_pages.CacheLayout(
            page_len=self.page_len, prefill_chunk=self.prefill_chunk,
            page_axis=0, latent=True)


# ------------------------------------------------------------------------ yarn
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: KimiK2Config) -> np.ndarray:
    """The rotation's ``qk_rope_head_dim / 2`` frequencies: ``theta^(-2i/d)``
    for the pairs that turn often within the original context, that over
    ``factor`` for the slow ones, a linear ramp between (YaRN, Peng et al.,
    arXiv 2309.00071, as the released model code computes it)."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    rs = cfg.rope_scaling
    if not rs:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    mask = 1.0 - ramp
    return (extra / rs["factor"] * (1 - mask) + extra * mask).astype(np.float32)


def _rope_scale(cfg: KimiK2Config) -> float:
    """What YaRN multiplies cos and sin by: ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)`` (1 where the two are equal)."""
    rs = cfg.rope_scaling or {}
    f = rs.get("factor", 1)
    return _yarn_mscale(f, rs.get("mscale", 1)) / _yarn_mscale(
        f, rs.get("mscale_all_dim", 0))


def _rotate(x, positions, cfg: KimiK2Config):
    """``x [..., H, dr]`` at ``positions [...]``."""
    y = L.rope_freqs(x, positions, jnp.asarray(yarn_inv_freq(cfg)))
    scale = _rope_scale(cfg)
    return y if scale == 1.0 else (y.astype(jnp.float32) * scale).astype(x.dtype)


# ---------------------------------------------------------------------- params
def init_params(rng, cfg: KimiK2Config) -> Dict[str, Any]:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    f, n_held = cfg.moe_intermediate_size, cfg.held[1]
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, d),
        "norm_f": L.rmsnorm_init(d),
        "head": L.dense_init(keys[1], d, cfg.vocab_size, use_bias=False),
    }
    for i in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[i + 2], 12)
        layer = {
            "norm1": L.rmsnorm_init(d), "norm2": L.rmsnorm_init(d),
            "attn": {
                "wq_a": L.dense_init(k[0], d, cfg.q_lora_rank, use_bias=False),
                "q_norm": L.rmsnorm_init(cfg.q_lora_rank),
                "wq_b": L.dense_init(k[1], cfg.q_lora_rank, h * cfg.qk_head_dim,
                                     use_bias=False),
                "wkv_a": L.dense_init(k[2], d, cfg.latent_width, use_bias=False),
                "kv_norm": L.rmsnorm_init(cfg.kv_lora_rank),
                "wkv_b": L.dense_init(
                    k[3], cfg.kv_lora_rank,
                    h * (cfg.qk_nope_head_dim + cfg.v_head_dim), use_bias=False),
                "wo": L.dense_init(k[4], h * cfg.v_head_dim, d, use_bias=False),
            },
        }
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = L.gated_mlp_init(k[5], d, cfg.intermediate_size)
        else:
            layer["router"] = {
                "kernel": L.normal(k[6], (d, cfg.n_routed_experts), d ** -0.5),
                "bias": jnp.zeros((cfg.n_routed_experts,))}
            layer["shared"] = L.gated_mlp_init(k[7], d, f * cfg.n_shared_experts)
            layer["experts"] = {
                "gate": L.normal(k[8], (n_held, d, f), d ** -0.5),
                "up": L.normal(k[9], (n_held, d, f), d ** -0.5),
                "down": L.normal(k[10], (n_held, f, d), f ** -0.5)}
        params[f"layers_{i}"] = layer
    return params


# ---------------------------------------------------------------------- pieces
def _norm(p, x, cfg: KimiK2Config):
    return L.rmsnorm(p, x, cfg.rms_norm_eps)


def _dense(p, x, cfg: KimiK2Config):
    return L.dense(p, x, compute_dtype=cfg.dtype)


def _queries(attn_p, u, positions, cfg: KimiK2Config):
    """``u [..., D]`` at ``positions [...]`` -> ``q_nope [..., H, dn]``,
    rotated ``q_rope [..., H, dr]``."""
    c_q = _norm(attn_p["q_norm"], _dense(attn_p["wq_a"], u, cfg), cfg)
    q = _dense(attn_p["wq_b"], c_q, cfg).reshape(
        u.shape[:-1] + (cfg.num_attention_heads, cfg.qk_head_dim))
    return (q[..., : cfg.qk_nope_head_dim],
            _rotate(q[..., cfg.qk_nope_head_dim:], positions, cfg))


def _latents(attn_p, u, positions, cfg: KimiK2Config):
    """The row that is cached: ``[Nkv(c_kv) | R(k_r)]``, ``[..., Ckv + dr]``."""
    kv = _dense(attn_p["wkv_a"], u, cfg)
    c = _norm(attn_p["kv_norm"], kv[..., : cfg.kv_lora_rank], cfg)
    k_rope = _rotate(kv[..., None, cfg.kv_lora_rank:], positions, cfg)[..., 0, :]
    return jnp.concatenate([c, k_rope], axis=-1)


def _page_rows(rows, cfg: KimiK2Config):
    """Latent rows (or absorbed queries) ``[..., Ckv + dr]`` in the pool's
    width: zeros past the latent."""
    pad = cfg.page_width - cfg.latent_width
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)]) if pad else rows


def _up_projection(attn_p, cfg: KimiK2Config):
    """``W_kvb`` as ``(w_k [Ckv, H, dn], w_v [Ckv, H, dv])``."""
    w = attn_p["wkv_b"]["kernel"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim).astype(cfg.dtype)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _attn_out(attn_p, x, o, cfg: KimiK2Config):
    """Per-head outputs ``o [..., H, dv]`` through ``W_o`` into the float32
    residual."""
    o = o.reshape(o.shape[:-2] + (cfg.num_attention_heads * cfg.v_head_dim,))
    return x + _dense(attn_p["wo"], o, cfg).astype(jnp.float32)


def _ffn(layer_p, x, cfg: KimiK2Config, live=None):
    """``x [T, D]`` float32 residual -> ``(x + FFN(N2 x), pairs, hit)``."""
    u = _norm(layer_p["norm2"], x, cfg)
    if "mlp" in layer_p:
        zero = jnp.zeros((), jnp.int32)
        return x + L.gated_mlp(layer_p["mlp"], u, compute_dtype=cfg.dtype
                               ).astype(jnp.float32), zero, zero
    out, pairs, hit = routed.expert_ffn(layer_p, u, cfg, live)
    return x + out, pairs, hit


def _embed(params, tokens):
    return L.embedding_lookup(params["embed"], tokens).astype(jnp.float32)


def _logits(params, x, cfg: KimiK2Config):
    return L.lm_head(params["head"], _norm(params["norm_f"], x, cfg),
                     compute_dtype=cfg.dtype)


# --------------------------------------------------------------------- forward
def expanded_attention(q_nope, q_rope, latents, w_k, w_v, mask, scale):
    """Whole timelines, per head: ``q_nope [Q, H, dn]``, ``q_rope [Q, H,
    dr]``, ``latents [T, Ckv + dr]``, ``mask [Q, T]`` -> ``[Q, H, dv]``."""
    ckv, dt = w_k.shape[0], q_nope.dtype
    c, k_rope = latents[:, :ckv].astype(dt), latents[:, ckv:].astype(dt)
    k_nope = jnp.einsum("tc,chd->thd", c, w_k,
                        preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("tc,chd->thd", c, w_v,
                   preferred_element_type=jnp.float32).astype(dt)
    s = (jnp.einsum("qhd,thd->hqt", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhr,tr->hqt", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    p = jax.nn.softmax(pa_ops.apply_mask(s, mask[None]), axis=-1).astype(dt)
    return jnp.einsum("hqt,thd->qhd", p, v,
                      preferred_element_type=jnp.float32).astype(dt)


def absorbed_attention(q_nope, q_rope, latents, w_k, w_v, mask, scale):
    """The same attention with ``W_kvb`` taken into the query and the
    output, in the latent space: what the decode step computes through the
    pool. Shapes as :func:`expanded_attention`."""
    ckv, dt = w_k.shape[0], q_nope.dtype
    q_lat = jnp.concatenate([
        jnp.einsum("qhd,chd->qhc", q_nope, w_k,
                   preferred_element_type=jnp.float32).astype(dt), q_rope], -1)
    s = jnp.einsum("qhw,tw->hqt", q_lat, latents.astype(dt),
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(pa_ops.apply_mask(s, mask[None]), axis=-1).astype(dt)
    o_lat = jnp.einsum("hqt,tc->qhc", p, latents[:, :ckv].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
    return jnp.einsum("qhc,chd->qhd", o_lat, w_v,
                      preferred_element_type=jnp.float32).astype(dt)


def forward(params, tokens, cfg: KimiK2Config):
    """``tokens [B, S]`` -> float32 logits ``[B, S, V]``, every sequence
    whole: expanded attention under a causal mask, no cache."""
    b, s = tokens.shape
    positions = jnp.arange(s)
    causal = positions[None, :] <= positions[:, None]
    x = _embed(params, tokens)
    for i in range(cfg.num_hidden_layers):
        lp = params[f"layers_{i}"]
        w_k, w_v = _up_projection(lp["attn"], cfg)

        def one(xr, lp=lp, w_k=w_k, w_v=w_v):
            u = _norm(lp["norm1"], xr, cfg)
            q_nope, q_rope = _queries(lp["attn"], u, positions, cfg)
            o = expanded_attention(q_nope, q_rope,
                                   _latents(lp["attn"], u, positions, cfg),
                                   w_k, w_v, causal, cfg.softmax_scale)
            return _attn_out(lp["attn"], xr, o, cfg)

        x = jnp.stack([one(x[r]) for r in range(b)])
        x = _ffn(lp, x.reshape(b * s, -1), cfg)[0].reshape(b, s, -1)
    return _logits(params, x, cfg)


# ------------------------------------------------------------------ paged cache
def init_paged_cache(cfg: KimiK2Config, n_pages: int, page_len: int,
                     dtype: Any = None):
    """One leaf a layer, ``[n_pages, page_len, page_width]`` (``kv_lora_rank
    + qk_rope_head_dim`` columns in whole lane tiles): no head axis, and no
    second pool (the latent is key and value alike). Both programs write
    rows in place through the page table and read the leaf as it lies."""
    shape = (n_pages, page_len, cfg.page_width)
    return {"kv": [jnp.zeros(shape, dtype or cfg.dtype)
                   for _ in range(cfg.num_hidden_layers)]}


def _sample(logits, counters, samp):
    if samp is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from autodist_tpu.serve.sampling import sample_tokens

    return sample_tokens(logits, counters, samp)


def _with_facts(tokens, pairs, hit):
    """The program's one int32 vector: its tokens, then ``STEP_FACTS``."""
    return jnp.concatenate([tokens.astype(jnp.int32),
                            jnp.stack([pairs, hit]).astype(jnp.int32)])


def forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                page_table, cfg: KimiK2Config, samp=None,
                                return_logits: bool = False):
    """One chunk of one row's prompt: ``tokens [1, C]`` at positions
    ``[start, start + C)`` (any ``start``: a shared prefix may end inside a
    page). Each layer writes the chunk's latent rows through ``page_table
    [P]`` and attends with the latents expanded, over the pool's earlier
    positions and its own. Positions at or past ``length`` are padding:
    they choose no expert, and what they write lies past every mask until a
    decode step writes it again.

    Returns ``([next_token, moe_pairs, moe_experts_hit], cache)``: the
    token after position ``length - 1`` (meaningful on the prompt's last
    chunk) and the chunk's two facts."""
    b, c = tokens.shape
    page_len = cache["kv"][0].shape[1]
    pos = start + jnp.arange(c)
    page_of = page_table[jnp.minimum(pos // page_len, page_table.shape[0] - 1)]
    off = pos % page_len
    live = pos < length
    x = _embed(params, tokens)[0]
    pools = list(cache["kv"])
    pairs = hit = jnp.zeros((), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        lp = params[f"layers_{i}"]
        u = _norm(lp["norm1"], x, cfg)
        q_nope, q_rope = _queries(lp["attn"], u, pos, cfg)
        rows = _page_rows(_latents(lp["attn"], u, pos, cfg), cfg)
        pools[i] = pools[i].at[page_of, off].set(rows.astype(pools[i].dtype))
        w_k, w_v = _up_projection(lp["attn"], cfg)
        o = pa_ops.mla_paged_prefill_attention(
            q_nope, q_rope, pools[i], page_table, pos, w_k, w_v,
            rope_width=cfg.qk_rope_head_dim, scale=cfg.softmax_scale, block=max(cfg.prefill_chunk, page_len))
        x, p, e = _ffn(lp, _attn_out(lp["attn"], x, o, cfg), cfg, live)
        pairs, hit = pairs + p, hit + e
    cache = dict(cache, kv=pools)
    if return_logits:
        return _logits(params, x[None], cfg), cache
    frontier = jnp.clip(length - 1 - start, 0, c - 1)
    logits = _logits(params, x[frontier][None], cfg)
    counters = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    return _with_facts(_sample(logits, counters, samp), pairs, hit), cache


def forward_paged_decode_step(params, tokens, positions, cache, page_tables,
                              cfg: KimiK2Config, samp=None,
                              return_logits: bool = False):
    """One decode step over every row: ``tokens [B]`` at ``positions [B]``
    through ``page_tables [B, P]``. Each layer writes the token's latent
    row in place and attends in the latent space: the key projection
    absorbed into the query (``dn -> Ckv`` a head), every head against the
    one shared row a position, the value projection applied to the summed
    latents (``Ckv -> dv``); the pool is read once for both. A row that is
    not decoding carries position 0 (a decoding row's is at least its
    prompt's length): it chooses no expert.

    Returns ``([next_token [B], moe_pairs, moe_experts_hit], cache)``."""
    page_len = cache["kv"][0].shape[1]
    rows = jnp.arange(tokens.shape[0])
    page_of = page_tables[rows, positions // page_len]
    off = positions % page_len
    live = positions > 0
    x = _embed(params, tokens)
    pools = list(cache["kv"])
    pairs = hit = jnp.zeros((), jnp.int32)
    for i in range(cfg.num_hidden_layers):
        lp = params[f"layers_{i}"]
        u = _norm(lp["norm1"], x, cfg)
        q_nope, q_rope = _queries(lp["attn"], u, positions, cfg)
        lat = _page_rows(_latents(lp["attn"], u, positions, cfg), cfg)
        pools[i] = pools[i].at[page_of, off].set(lat.astype(pools[i].dtype))
        w_k, w_v = _up_projection(lp["attn"], cfg)
        q_lat = _page_rows(jnp.concatenate([
            jnp.einsum("bhd,chd->bhc", q_nope, w_k,
                       preferred_element_type=jnp.float32).astype(cfg.dtype),
            q_rope], axis=-1), cfg)
        o_lat = pa_ops.mla_paged_decode_attention(
            q_lat, pools[i], page_tables, positions,
            value_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
            impl=routed.resolve(cfg.paged_attention_impl, "gather"))
        o = jnp.einsum("bhc,chd->bhd", o_lat, w_v,
                       preferred_element_type=jnp.float32).astype(cfg.dtype)
        x, p, e = _ffn(lp, _attn_out(lp["attn"], x, o, cfg), cfg, live)
        pairs, hit = pairs + p, hit + e
    cache = dict(cache, kv=pools)
    logits = _logits(params, x, cfg)
    if return_logits:
        return logits, cache
    tokens_out = _sample(logits, positions.astype(jnp.int32) + 1, samp)
    return _with_facts(tokens_out, pairs, hit), cache


def decode_model(cfg: KimiK2Config, eos_id: Optional[int] = None):
    """The serving adapter: the paged surface, the statement of the cache
    (a plain timeline of latent pages) and the facts its programs append.
    No ``verify_paged``, and no int8 pages: both are refused when the
    engine is built (ROADMAP.md Queue 2)."""
    from autodist_tpu.serve.engine import DecodeModel

    if cfg.kv_quant:
        raise serve_pages.CacheFeatureRefused(
            "int8 pages over a latent pool: a latent row is normalised and "
            "shared by every head, and no scale plane is defined for it "
            "(ROADMAP.md Queue 2)")
    return DecodeModel(
        init_paged_cache=lambda n_pages, page_len: init_paged_cache(
            cfg, n_pages, page_len),
        prefill_chunk=lambda params, tokens, start, length, cache, table,
            samp=None: forward_paged_prefill_chunk(
                params, tokens, start, length, cache, table, cfg, samp=samp),
        decode_paged=lambda params, tokens, positions, cache, tables,
            samp=None: forward_paged_decode_step(
                params, tokens, positions, cache, tables, cfg, samp=samp),
        eos_id=eos_id,
        max_len=cfg.max_position_embeddings,
        cache_layout=cfg.cache_layout,
        step_facts=STEP_FACTS,
        steps_fact="moe_steps",
    )
