"""Nemotron-H (``model_type: nemotron_h``; huggingface.co/nvidia/
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``): a hybrid stack of
Mamba-2 mixers, routed expert layers and a few grouped-KV attention layers.

The layers follow ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E``
an expert layer, ``*`` attention. Each block is ``x <- x + mixer(N(x))``,
``N`` RMSNorm without offset; a final norm and an untied head. No biases
but the convolution's. Residual sums, norm statistics, router scores, the
recurrent state and the logits are float32; leaves are held in
``param_dtype`` and matrix products run in ``dtype`` with float32
accumulation.

**Mamba-2** (Dao and Gu, arXiv 2405.21060). ``[z | xBC | dt] = u W_in``;
``xBC = SiLU(causal depthwise conv(xBC))`` (kernel ``conv_kernel``, a bias);
``xBC -> x [H, P], B [G, N], C [G, N]``; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``. For head ``h`` of group ``g = h // (H / G)``: ``S_t =
exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D_h x_t``; ``y
<- N_G(y * SiLU(z))`` (RMSNorm in ``G`` groups, the gate before the norm);
out ``y W_out``. The width is ``mamba_num_heads x mamba_head_dim``.

**Experts**: the routed layer Kimi K2 uses (``models/routed.py``), relu²
experts (``W_down relu(W_up u)^2``) and a shared expert of that form. A chip
holds experts ``[first, first + count)`` (``experts_held``).

**Attention**: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads (query head ``h`` reads KV head ``h //
(Hq / Hkv)``), scale ``head_dim^-1/2``, **no rotary embedding** (the
published modeling code applies none).

Renderings: :func:`forward` over whole sequences, the recurrence a position
at a time (tests); and the two serving programs that :func:`decode_model`
hands to :class:`~autodist_tpu.serve.InferenceEngine`. **What a slot
carries** beside its pages is a Mamba layer's state ``[H, P, N]`` (float32)
and the last ``conv_kernel - 1`` rows of ``xBC`` (:func:`init_slot_state`).
A prefill chunk runs the state-space dual form over blocks of
``chunk_size`` positions from the row's carried state (zeros where the
chunk starts the prompt); a decode step updates every live row's state in
place through ``ops/ssm.py``. Attention writes its KV heads to one
lane-dense page leaf a layer for keys and one for values, ``[n_pages,
page_len, Hkv x head_dim]``, and attends through ``paged_attention`` with
each KV head's query heads folded into its query axis. Both programs end
their token vector with the facts Kimi's state (``moe_pairs``,
``moe_experts_hit``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.models import routed
from autodist_tpu.ops import paged_attention as pa_ops
from autodist_tpu.ops import ssm as ssm_ops
from autodist_tpu.serve import pages as serve_pages

STEP_FACTS = ("moe_pairs", "moe_experts_hit")
LAYER_KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass
class NemotronHConfig:
    """The source's keys, and what the program chooses."""

    vocab_size: int = 131072          # rows of the embedding and head held
    hidden_size: int = 2688
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128       # what the router scores over
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    # (first, count): the routed experts this chip holds; None is all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16           # compute dtype of the matmuls
    # gather | kernel | auto (the Mosaic kernel on a TPU, plain jnp off it)
    paged_attention_impl: str = "auto"
    page_len: int = 128
    prefill_chunk: int = 512
    kv_quant: bool = False              # int8 pages: refused
    expert_act: str = "relu2"          # models/routed.py EXPERT_ACTS

    def __post_init__(self):
        bad = set(self.hybrid_override_pattern) - set(LAYER_KINDS)
        if bad:
            raise ValueError(f"hybrid_override_pattern holds {sorted(bad)}; "
                             f"this program builds {sorted(LAYER_KINDS)}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def kinds(self):
        return [LAYER_KINDS[c] for c in self.hybrid_override_pattern]

    def layers_of(self, kind: str):
        return [i for i, k in enumerate(self.kinds) if k == kind]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def kv_width(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def cache_layout(self) -> serve_pages.CacheLayout:
        return serve_pages.CacheLayout(
            page_len=self.page_len, prefill_chunk=self.prefill_chunk)


# ---------------------------------------------------------------------- params
def dt_bias_init(rng, cfg: NemotronHConfig):
    """``softplus^-1(dt)``, ``dt`` log-uniform in ``[time_step_min,
    time_step_max]`` and floored at ``time_step_floor`` (the published
    initialisation)."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
    dt = jnp.exp(jax.random.uniform(rng, (cfg.mamba_num_heads,)) * (hi - lo) + lo)
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def init_params(rng, cfg: NemotronHConfig) -> Dict[str, Any]:
    d, di, conv = cfg.hidden_size, cfg.d_inner, cfg.conv_dim
    h = cfg.mamba_num_heads
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, d, stddev=1.0),
        "norm_f": L.rmsnorm_init(d),
        "head": {"kernel": L.normal(keys[1], (d, cfg.vocab_size), d ** -0.5)},
    }
    for i, kind in enumerate(cfg.kinds):
        k = jax.random.split(keys[i + 2], 8)
        layer: Dict[str, Any] = {"norm": L.rmsnorm_init(d)}
        if kind == "mamba":
            layer["mixer"] = {
                "in_proj": {"kernel": L.normal(k[0], (d, di + conv + h), d ** -0.5)},
                "conv": {"kernel": L.normal(k[1], (cfg.conv_kernel, conv),
                                            cfg.conv_kernel ** -0.5),
                         "bias": L.normal(k[2], (conv,), 0.1)},
                "dt_bias": dt_bias_init(k[3], cfg),
                "A_log": jnp.log(jax.random.uniform(k[4], (h,), minval=1.0,
                                                    maxval=16.0)),
                "D": jnp.ones((h,)),
                "norm": L.rmsnorm_init(di),
                "out_proj": {"kernel": L.normal(k[5], (di, d), di ** -0.5)},
            }
        elif kind == "experts":
            f, fs, n_held = (cfg.moe_intermediate_size,
                             cfg.moe_shared_expert_intermediate_size, cfg.held[1])
            layer["router"] = {
                "kernel": L.normal(k[0], (d, cfg.n_routed_experts), d ** -0.5),
                "bias": jnp.zeros((cfg.n_routed_experts,))}
            layer["experts"] = {"up": L.normal(k[1], (n_held, d, f), d ** -0.5),
                                "down": L.normal(k[2], (n_held, f, d), f ** -0.5)}
            layer["shared"] = {"up": {"kernel": L.normal(k[3], (d, fs), d ** -0.5)},
                               "down": {"kernel": L.normal(k[4], (fs, d), fs ** -0.5)}}
        else:
            hq = cfg.num_attention_heads * cfg.head_dim
            layer["attn"] = {
                "wq": {"kernel": L.normal(k[0], (d, hq), d ** -0.5)},
                "wk": {"kernel": L.normal(k[1], (d, cfg.kv_width), d ** -0.5)},
                "wv": {"kernel": L.normal(k[2], (d, cfg.kv_width), d ** -0.5)},
                "wo": {"kernel": L.normal(k[3], (hq, d), hq ** -0.5)}}
        params[f"layers_{i}"] = layer
    return params


# ---------------------------------------------------------------------- pieces
def _norm(p, x, cfg: NemotronHConfig):
    return L.rmsnorm(p, x, cfg.layer_norm_epsilon)


def _dense(p, x, cfg: NemotronHConfig):
    return L.dense(p, x, compute_dtype=cfg.dtype)


def _embed(params, tokens):
    return L.embedding_lookup(params["embed"], tokens).astype(jnp.float32)


def _logits(params, x, cfg: NemotronHConfig):
    return L.lm_head(params["head"], _norm(params["norm_f"], x, cfg),
                     compute_dtype=cfg.dtype)


def _in_proj(mix, u, cfg: NemotronHConfig):
    """``u [..., D]`` -> ``(z, xBC, dt)`` in the compute type."""
    zxbcdt = _dense(mix["in_proj"], u, cfg)
    di, conv = cfg.d_inner, cfg.conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + conv], zxbcdt[..., di + conv:]


def _conv_act(mix, window, cfg: NemotronHConfig):
    """``window [..., K, conv]`` (oldest first) -> ``SiLU(sum_k w_k
    window_k + bias)``, float32, elementwise (no product at the chip's
    default precision)."""
    w = mix["conv"]["kernel"].astype(jnp.float32)
    y = (window.astype(jnp.float32) * w).sum(-2) + mix["conv"]["bias"].astype(jnp.float32)
    return jax.nn.silu(y)


def _split_xbc(xbc, cfg: NemotronHConfig):
    """``[..., conv]`` -> ``x [..., H, P]``, ``B, C [..., G, N]``."""
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    return (xbc[..., :di].reshape(lead + (cfg.mamba_num_heads, cfg.mamba_head_dim)),
            xbc[..., di:di + gn].reshape(lead + (cfg.n_groups, cfg.ssm_state_size)),
            xbc[..., di + gn:].reshape(lead + (cfg.n_groups, cfg.ssm_state_size)))


def _dt(mix, dt_raw):
    return jax.nn.softplus(dt_raw.astype(jnp.float32)
                           + mix["dt_bias"].astype(jnp.float32))


def _a(mix):
    return -jnp.exp(mix["A_log"].astype(jnp.float32))


def _mamba_out(mix, y, z, cfg: NemotronHConfig):
    """``y [..., H, P]`` float32 gated by ``z``, normalised in ``G`` groups,
    through ``W_out``: float32 ``[..., D]``."""
    lead = y.shape[:-2]
    h = y.reshape(lead + (cfg.d_inner,)) * jax.nn.silu(z.astype(jnp.float32))
    g = h.reshape(lead + (cfg.n_groups, -1))
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.layer_norm_epsilon)
    h = g.reshape(lead + (cfg.d_inner,)) * mix["norm"]["weight"].astype(jnp.float32)
    return _dense(mix["out_proj"], h, cfg).astype(jnp.float32)


def _by_head(v, heads: int):
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_chunk(x, dt, a, b, c, d, state, block: int):
    """The state-space dual form over ``T`` positions from ``state``: ``x
    [T, H, P]``, ``dt [T, H]``, ``a, d [H]``, ``b, c [T, G, N]``, ``state
    [H, P, N]`` (float32 throughout, products at the highest precision) ->
    ``(y [T, H, P], state after the last position)``. Blocks of ``block``
    positions, one after another: inside a block every pair ``s <= t``
    weighs ``C_t . B_s`` by the decay between them, and the state carried
    in reaches each position through its decay from the block's start. A
    position with ``dt = 0`` and ``x = 0`` leaves the state as it was."""
    t, heads, p = x.shape
    mm = lambda spec, *ops: jnp.einsum(spec, *ops, precision=_HIGHEST)  # noqa: E731
    tri = jnp.tril(jnp.ones((block, block), bool))

    def one(s0, blk):
        xb, dtb, bb, cb = blk
        acum = jnp.cumsum(dtb * a[None, :], axis=0)                     # [L, H]
        seg = jnp.where(tri[..., None], acum[:, None, :] - acum[None, :, :], -jnp.inf)
        cbg = mm("tgn,sgn->tsg", cb, bb)                                # [L, L, G]
        w = (jnp.repeat(cbg, heads // cbg.shape[-1], axis=-1)
             * jnp.exp(seg) * dtb[None, :, :])                          # [t, s, H]
        ch = _by_head(cb, heads)                                        # [L, H, N]
        y = (mm("tsh,shp->thp", w, xb)
             + jnp.exp(acum)[..., None] * mm("hpn,thn->thp", s0, ch)
             + d[None, :, None] * xb)
        to_end = jnp.exp(acum[-1][None, :] - acum) * dtb                # [L, H]
        s1 = (jnp.exp(acum[-1])[:, None, None] * s0
              + mm("sh,shp,shn->hpn", to_end, xb, _by_head(bb, heads)))
        return s1, y

    n = t // block
    split = lambda v: v.reshape((n, block) + v.shape[1:])  # noqa: E731
    state, y = jax.lax.scan(one, state, (split(x), split(dt), split(b), split(c)))
    return y.reshape(t, heads, p), state


def scan_positions(x, dt, a, b, c, d, state):
    """The recurrence a position at a time (``forward``'s rendering): shapes
    as :func:`ssd_chunk`."""
    heads = x.shape[1]

    def step(s, inp):
        xt, dtt, bt, ct = inp
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[..., None] * _by_head(bt, heads)[:, None, :])
        y = jnp.einsum("hpn,hn->hp", s, _by_head(ct, heads), precision=_HIGHEST)
        return s, y + d[:, None] * xt

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


def _attend_folded(q, kv_heads: int):
    """``q [..., Hq, Dh]`` -> ``[..., Hq / Hkv, Hkv, Dh]``: each KV head's
    query heads as queries of its own, the shape the paged kernel takes
    (``[rows, queries, heads, Dh]``)."""
    lead, hq, dh = q.shape[:-2], q.shape[-2], q.shape[-1]
    q = q.reshape(lead + (kv_heads, hq // kv_heads, dh))
    return jnp.swapaxes(q, -3, -2)


def _unfold(o):
    """The inverse of :func:`_attend_folded`, flattened: ``[..., Hq Dh]``."""
    o = jnp.swapaxes(o, -3, -2)
    return o.reshape(o.shape[:-3] + (o.shape[-3] * o.shape[-2] * o.shape[-1],))


def _qkv(attn_p, u, cfg: NemotronHConfig):
    q = _dense(attn_p["wq"], u, cfg).reshape(
        u.shape[:-1] + (cfg.num_attention_heads, cfg.head_dim))
    return q, _dense(attn_p["wk"], u, cfg), _dense(attn_p["wv"], u, cfg)


def _ffn(lp, x, cfg: NemotronHConfig, live=None):
    out, pairs, hit = routed.expert_ffn(lp, _norm(lp["norm"], x, cfg), cfg, live)
    return x + out, pairs, hit


# --------------------------------------------------------------------- forward
def grouped_attention(q, k, v, mask, kv_heads: int):
    """Plain grouped attention over whole timelines: ``q [Q, Hq, Dh]``, ``k,
    v [T, Hkv Dh]``, ``mask [Q, T]`` -> ``[Q, Hq, Dh]``."""
    t, dh = k.shape[0], q.shape[-1]
    kh = k.reshape(t, kv_heads, dh)
    vh = v.reshape(t, kv_heads, dh)
    qg = q.reshape(q.shape[0], kv_heads, -1, dh)                      # [Q, Hkv, r, Dh]
    s = jnp.einsum("qgrd,tgd->grqt", qg, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(dh))
    p = jax.nn.softmax(pa_ops.apply_mask(s, mask[None, None]), axis=-1).astype(q.dtype)
    o = jnp.einsum("grqt,tgd->qgrd", p, vh, preferred_element_type=jnp.float32)
    return o.reshape(q.shape).astype(q.dtype)


def forward(params, tokens, cfg: NemotronHConfig):
    """``tokens [B, S]`` -> float32 logits ``[B, S, V]``, every sequence
    whole: the Mamba recurrence a position at a time, attention under a
    causal mask, no cache."""
    b, s = tokens.shape
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    x = _embed(params, tokens)
    for i, kind in enumerate(cfg.kinds):
        lp = params[f"layers_{i}"]
        if kind == "experts":
            x = _ffn(lp, x.reshape(b * s, -1), cfg)[0].reshape(b, s, -1)
            continue
        u = _norm(lp["norm"], x, cfg)
        if kind == "attention":
            q, k, v = _qkv(lp["attn"], u, cfg)
            o = jnp.stack([grouped_attention(q[r], k[r], v[r], causal,
                                             cfg.num_key_value_heads)
                           for r in range(b)])
            x = x + _dense(lp["attn"]["wo"], o.reshape(b, s, -1), cfg).astype(jnp.float32)
            continue
        mix = lp["mixer"]
        z, xbc, dt_raw = _in_proj(mix, u, cfg)
        k = cfg.conv_kernel
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        window = jnp.stack([padded[:, j:j + s] for j in range(k)], axis=-2)
        xs, bs, cs = _split_xbc(_conv_act(mix, window, cfg), cfg)
        dt = _dt(mix, dt_raw)
        zero = jnp.zeros((cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size))
        y = jnp.stack([scan_positions(xs[r], dt[r], _a(mix), bs[r], cs[r],
                                      mix["D"].astype(jnp.float32), zero)[0]
                       for r in range(b)])
        x = x + _mamba_out(mix, y, z, cfg)
    return _logits(params, x, cfg)


# ----------------------------------------------------------- cache and state
def init_paged_cache(cfg: NemotronHConfig, n_pages: int, page_len: int,
                     dtype: Any = None):
    """Keys and values of each attention layer, one lane-dense leaf each,
    ``[n_pages, page_len, Hkv x head_dim]``; Mamba and expert layers have
    no pages."""
    shape = (n_pages, page_len, cfg.kv_width)
    n = len(cfg.layers_of("attention"))
    return {"k": [jnp.zeros(shape, dtype or cfg.dtype) for _ in range(n)],
            "v": [jnp.zeros(shape, dtype or cfg.dtype) for _ in range(n)]}


def init_slot_state(cfg: NemotronHConfig, n_slots: int):
    """What a slot carries beside its pages, a leaf a Mamba layer: the
    recurrent state ``[n_slots, H, P, N]`` float32 and the convolution's
    tail, the last ``conv_kernel - 1`` rows of ``xBC`` ``[n_slots, K - 1,
    conv]`` in the compute type."""
    n = len(cfg.layers_of("mamba"))
    ssm = (n_slots, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
    conv = (n_slots, cfg.conv_kernel - 1, cfg.conv_dim)
    return {"ssm": [jnp.zeros(ssm, jnp.float32) for _ in range(n)],
            "conv": [jnp.zeros(conv, cfg.dtype) for _ in range(n)]}


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
                                page_table, cfg: NemotronHConfig, samp=None,
                                return_logits: bool = False, *, state, slot):
    """One chunk of one row's prompt: ``tokens [1, C]`` at positions
    ``[start, start + C)``, the row's slot ``slot`` of ``state``. Positions
    at or past ``length`` are padding: they choose no expert, leave the
    state as it was (``dt = 0``, ``x = 0``) and what attention writes for
    them lies past every mask until a decode step writes it again.

    A Mamba layer starts from the slot's carried state and convolution
    tail, **or from zeros where ``start == 0``** (a slot that served
    another request carries its state: the program never reads it for a
    new prompt), runs the chunk in the dual form over blocks of
    ``chunk_size``, and writes back the state after the last real position
    and that position's last ``K - 1`` rows of ``xBC`` (from the carried
    tail where the chunk holds fewer).

    Returns ``([next_token, moe_pairs, moe_experts_hit], cache, state)``."""
    b, c = tokens.shape
    page_len = cache["k"][0].shape[1]
    pos = start + jnp.arange(c)
    page_of = page_table[jnp.minimum(pos // page_len, page_table.shape[0] - 1)]
    off = pos % page_len
    live = pos < length
    n_real = jnp.clip(length - start, 0, c)
    fresh = start == 0
    block = math.gcd(c, cfg.chunk_size)
    impl = routed.resolve(cfg.paged_attention_impl, "gather")
    x = _embed(params, tokens)[0]
    ks, vs = list(cache["k"]), list(cache["v"])
    ssm, conv = list(state["ssm"]), list(state["conv"])
    pairs = hit = jnp.zeros((), jnp.int32)
    a_i = m_i = 0
    for i, kind in enumerate(cfg.kinds):
        lp = params[f"layers_{i}"]
        if kind == "experts":
            x, p, e = _ffn(lp, x, cfg, live)
            pairs, hit = pairs + p, hit + e
            continue
        u = _norm(lp["norm"], x, cfg)
        if kind == "attention":
            q, k, v = _qkv(lp["attn"], u, cfg)
            ks[a_i] = ks[a_i].at[page_of, off].set(k.astype(ks[a_i].dtype))
            vs[a_i] = vs[a_i].at[page_of, off].set(v.astype(vs[a_i].dtype))
            group = cfg.num_attention_heads // cfg.num_key_value_heads
            qf = _attend_folded(q, cfg.num_key_value_heads)            # [C, r, Hkv, Dh]
            o = pa_ops.paged_prefill_attention(
                qf.reshape((c * group,) + qf.shape[2:]), ks[a_i], vs[a_i],
                page_table, jnp.repeat(pos, group), impl=impl)
            o = _unfold(o.reshape(qf.shape))
            x = x + _dense(lp["attn"]["wo"], o, cfg).astype(jnp.float32)
            a_i += 1
            continue
        mix = lp["mixer"]
        z, xbc, dt_raw = _in_proj(mix, u, cfg)
        tail = jax.lax.dynamic_index_in_dim(conv[m_i], slot, 0, keepdims=False)
        s0 = jax.lax.dynamic_index_in_dim(ssm[m_i], slot, 0, keepdims=False)
        tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
        s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
        k = cfg.conv_kernel
        full = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=0)   # [K-1+C, conv]
        window = jnp.stack([full[j:j + c] for j in range(k)], axis=-2)
        xs, bs, cs = _split_xbc(_conv_act(mix, window, cfg), cfg)
        dt = jnp.where(live[:, None], _dt(mix, dt_raw), 0.0)
        xs = jnp.where(live[:, None, None], xs, 0.0)
        y, s1 = ssd_chunk(xs, dt, _a(mix), bs, cs, mix["D"].astype(jnp.float32),
                          s0, block)
        ssm[m_i] = jax.lax.dynamic_update_index_in_dim(ssm[m_i], s1, slot, 0)
        conv[m_i] = jax.lax.dynamic_update_index_in_dim(
            conv[m_i], jax.lax.dynamic_slice_in_dim(full, n_real, k - 1), slot, 0)
        x = x + _mamba_out(mix, y, z, cfg)
        m_i += 1
    cache = dict(cache, k=ks, v=vs)
    state = dict(state, ssm=ssm, conv=conv)
    if return_logits:
        return _logits(params, x[None], cfg), cache, state
    frontier = jnp.clip(length - 1 - start, 0, c - 1)
    logits = _logits(params, x[frontier][None], cfg)
    counters = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    return _with_facts(_sample(logits, counters, samp), pairs, hit), cache, state


def forward_paged_decode_step(params, tokens, positions, cache, page_tables,
                              cfg: NemotronHConfig, samp=None,
                              return_logits: bool = False, *, state):
    """One decode step over every row: ``tokens [B]`` at ``positions [B]``
    through ``page_tables [B, P]``. A row that is not decoding carries
    position 0 (a decoding row's is at least its prompt's length): it
    chooses no expert and its state and convolution tail stay as they
    were, for a row mid-prefill carries its prompt's state there. Each Mamba
    layer shifts the live rows' tails and updates their states in place
    (``ops/ssm.py``); each attention layer writes the token's keys and
    values in place and attends with each KV head's query heads as its
    queries.

    Returns ``([next_token [B], moe_pairs, moe_experts_hit], cache,
    state)``."""
    n = tokens.shape[0]
    page_len = cache["k"][0].shape[1]
    rows = jnp.arange(n)
    page_of = page_tables[rows, positions // page_len]
    off = positions % page_len
    live = positions > 0
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    attn_impl = routed.resolve(cfg.paged_attention_impl, "gather")
    x = _embed(params, tokens)
    ks, vs = list(cache["k"]), list(cache["v"])
    ssm, conv = list(state["ssm"]), list(state["conv"])
    pairs = hit = jnp.zeros((), jnp.int32)
    a_i = m_i = 0
    for i, kind in enumerate(cfg.kinds):
        lp = params[f"layers_{i}"]
        if kind == "experts":
            x, p, e = _ffn(lp, x, cfg, live)
            pairs, hit = pairs + p, hit + e
            continue
        u = _norm(lp["norm"], x, cfg)
        if kind == "attention":
            q, k, v = _qkv(lp["attn"], u, cfg)
            ks[a_i] = ks[a_i].at[page_of, off].set(k.astype(ks[a_i].dtype))
            vs[a_i] = vs[a_i].at[page_of, off].set(v.astype(vs[a_i].dtype))
            o = pa_ops.paged_verify_attention(
                _attend_folded(q, cfg.num_key_value_heads), ks[a_i], vs[a_i],
                page_tables, jnp.broadcast_to(positions[:, None], (n, group)),
                impl=attn_impl)
            x = x + _dense(lp["attn"]["wo"], _unfold(o), cfg).astype(jnp.float32)
            a_i += 1
            continue
        mix = lp["mixer"]
        z, xbc, dt_raw = _in_proj(mix, u, cfg)
        window = jnp.concatenate([conv[m_i], xbc[:, None].astype(conv[m_i].dtype)],
                                 axis=1)                               # [B, K, conv]
        conv[m_i] = jnp.where(live[:, None, None], window[:, 1:], conv[m_i])
        xs, bs, cs = _split_xbc(_conv_act(mix, window, cfg), cfg)
        y, ssm[m_i] = ssm_ops.ssm_state_update(
            ssm[m_i], xs, _dt(mix, dt_raw), _a(mix), mix["D"].astype(jnp.float32),
            bs, cs, live)
        x = x + _mamba_out(mix, y, z, cfg)
        m_i += 1
    cache = dict(cache, k=ks, v=vs)
    state = dict(state, ssm=ssm, conv=conv)
    logits = _logits(params, x, cfg)
    if return_logits:
        return logits, cache, state
    tokens_out = _sample(logits, positions.astype(jnp.int32) + 1, samp)
    return _with_facts(tokens_out, pairs, hit), cache, state


def serving_params(params, cfg: NemotronHConfig):
    """The tree the paged programs read (``DecodeModel.serving_params``):
    the routed experts' width padded with zeros to a multiple of 128. At
    1,856 the TPU lays ``up`` ``[E, D, F]`` with ``D`` minor (less padding)
    where the grouped product reads ``F`` minor, so every program run
    would re-lay all held experts out; past ``F`` a zero column of ``W_up``
    and a zero row of ``W_down`` add nothing (``relu(0)^2 = 0``)."""
    pad = -cfg.moe_intermediate_size % 128
    out = dict(params)
    for i in cfg.layers_of("experts"):
        e = params[f"layers_{i}"]["experts"]
        out[f"layers_{i}"] = dict(params[f"layers_{i}"], experts={
            "up": jnp.pad(e["up"], ((0, 0), (0, 0), (0, pad))),
            "down": jnp.pad(e["down"], ((0, 0), (0, pad), (0, 0)))})
    return out


def decode_model(cfg: NemotronHConfig, eos_id: Optional[int] = None):
    """The serving adapter: the paged surface, the statement of the cache
    (a plain timeline of grouped-KV pages), the per-slot state and the
    facts its programs append. No ``verify_paged`` and no int8 pages; the
    engine refuses prefix sharing and speculation over a model with
    per-slot state (ROADMAP.md M4)."""
    from autodist_tpu.serve.engine import DecodeModel

    if cfg.kv_quant:
        raise serve_pages.CacheFeatureRefused(
            "int8 pages beside per-slot recurrent state: no scale plane is "
            "defined for this model's pages (ROADMAP.md M4)")
    return DecodeModel(
        init_paged_cache=lambda n_pages, page_len: init_paged_cache(
            cfg, n_pages, page_len),
        prefill_chunk=lambda params, tokens, start, length, cache, table,
            samp=None, *, state, slot: forward_paged_prefill_chunk(
                params, tokens, start, length, cache, table, cfg, samp=samp,
                state=state, slot=slot),
        decode_paged=lambda params, tokens, positions, cache, tables,
            samp=None, *, state: forward_paged_decode_step(
                params, tokens, positions, cache, tables, cfg, samp=samp,
                state=state),
        eos_id=eos_id,
        max_len=cfg.max_position_embeddings,
        cache_layout=cfg.cache_layout,
        step_facts=STEP_FACTS,
        steps_fact="moe_steps",
        slot_state=lambda n_slots: init_slot_state(cfg, n_slots),
        serving_params=lambda params: serving_params(params, cfg),
    )
