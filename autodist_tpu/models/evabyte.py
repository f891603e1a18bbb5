"""EvaByte: a byte-level decoder whose attention keeps a window of exact
keys and one summary per chunk of everything before it (EVA, Zheng et al.,
"Efficient Attention via Control Variates", arXiv 2302.04542).

The block is pre-norm with RMSNorm (unit offset, a stated epsilon), rotary
positions over the whole head, a gated SiLU MLP, no biases, an embedding
and a head of their own; the head holds ``num_pred_heads`` next-offset
predictions side by side and the first ``vocab_size`` columns are the next
byte's. Residual sums and logits are float32, matmuls in ``cfg.dtype``
with float32 accumulation, every softmax in float32.

Attention of token ``i`` is ONE softmax over two kinds of entry: the exact
(rotated) keys of its own window ``i // window`` up to itself, and the
summary pair of every ``chunk`` of every earlier window,
``k~_j = sum_n softmax_n(s k_n.mu) k_n``, ``v~_j = sum_n softmax_n(s
k_n.phi) v_n`` over the chunk's own keys and values, with two learned
vectors ``mu, phi`` per head and layer. A token never sees a summary of
its own window and never an exact key of an earlier one.

Three renderings of the same mathematics: :func:`forward` over whole
sequences (dense masks; tests and any later training), and the two serving
programs over the paged cache, :func:`forward_paged_prefill_chunk` and
:func:`forward_paged_decode_step`, which :func:`decode_model` hands to
:class:`~autodist_tpu.serve.InferenceEngine`. The cache is a ring of
``window // chunk`` pages a row for the open window and one summary row per
closed chunk behind it (``serve/pages.py`` :class:`CacheLayout`); the
attention over it lives in ``ops/paged_attention.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.ops import paged_attention as pa_ops
from autodist_tpu.serve import pages as serve_pages


@dataclass
class EvaByteConfig:
    """The source's keys (huggingface.co/EvaByte/EvaByte ``config.json``),
    and what the program chooses."""

    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    norm_add_unit_offset: bool = True
    rope_theta: float = 100000.0
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    dtype: Any = jnp.bfloat16           # compute dtype of the matmuls
    # gather | kernel | auto (the Mosaic kernel on a TPU, gather off it)
    paged_attention_impl: str = "auto"
    # Prefill chunk the serving programs ask the engine for: a multiple of
    # chunk_size that divides window_size, so no chunk straddles a window.
    # 1,024 by the sweep of 128..2,048 on one v5e (PERF.md section 6, PR 30):
    # the most bytes a second and the shortest prefill, at the price of the
    # longest gap between a decoding row's bytes that still repeats (a tick
    # that carries a chunk is 64 ms; at 512 the sweep read 46 ms against
    # 73 ms, for 5% fewer bytes a second and a tail that swung 2.6% from
    # run to run). An operator who wants the shorter gap passes
    # ``prefill_chunk=`` to the engine.
    prefill_chunk: int = 1024

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_attention_heads == 0
        return self.hidden_size // self.num_attention_heads

    @property
    def cache_layout(self) -> serve_pages.CacheLayout:
        return serve_pages.CacheLayout(
            page_len=self.chunk_size, window=self.window_size,
            prefill_chunk=min(self.prefill_chunk, self.window_size),
            page_axis=0)


# ---------------------------------------------------------------------- params
def init_params(rng, cfg: EvaByteConfig) -> Dict[str, Any]:
    d, h, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, d),
        "norm_f": L.rmsnorm_init(d, cfg.norm_add_unit_offset),
        "head": L.dense_init(keys[1], d, cfg.vocab_size * cfg.num_pred_heads,
                             use_bias=False),
    }
    for i in range(cfg.num_hidden_layers):
        k = jax.random.split(keys[i + 2], 7)
        attn = {w: L.dense_init(k[j], d, d, use_bias=False)
                for j, w in enumerate(("wq", "wk", "wv", "wo"))}
        # the released initialiser: normal, clipped to +-1, times s
        for j, w in ((4, "mu"), (5, "phi")):
            attn[w] = jnp.clip(jax.random.normal(k[j], (h, hd)), -1.0, 1.0) \
                * hd ** -0.5
        params[f"layers_{i}"] = {
            "norm1": L.rmsnorm_init(d, cfg.norm_add_unit_offset),
            "attn": attn,
            "norm2": L.rmsnorm_init(d, cfg.norm_add_unit_offset),
            "mlp": L.gated_mlp_init(k[6], d, cfg.intermediate_size),
        }
    return params


# ---------------------------------------------------------------------- pieces
def _norm(p, x, cfg: EvaByteConfig):
    return L.rmsnorm(p, x, cfg.rms_norm_eps, cfg.norm_add_unit_offset)


def _qkv(attn_p, h, positions, cfg: EvaByteConfig):
    """``h [..., D]`` at ``positions [...]`` -> rotated q, rotated k and v,
    each ``[..., H, hd]`` in the compute dtype."""
    shape = h.shape[:-1] + (cfg.num_attention_heads, cfg.head_dim)
    q, k, v = (L.dense(attn_p[w], h, compute_dtype=cfg.dtype).reshape(shape)
               for w in ("wq", "wk", "wv"))
    return (L.rope(q, positions, cfg.rope_theta),
            L.rope(k, positions, cfg.rope_theta), v)


def _rest_of_block(block_params, x, o, cfg: EvaByteConfig):
    """Attention's output ``o [..., D]`` into the float32 residual, then
    the gated MLP."""
    x = x + L.dense(block_params["attn"]["wo"], o,
                    compute_dtype=cfg.dtype).astype(jnp.float32)
    h = _norm(block_params["norm2"], x, cfg)
    return x + L.gated_mlp(block_params["mlp"], h,
                           compute_dtype=cfg.dtype).astype(jnp.float32)


def _embed(params, tokens):
    return L.embedding_lookup(params["embed"], tokens).astype(jnp.float32)


def _next_byte_logits(params, x, cfg: EvaByteConfig):
    """Float32 logits of the next byte: head 0 of the full-width head."""
    return L.lm_head(params["head"], _norm(params["norm_f"], x, cfg),
                     cfg.vocab_size, compute_dtype=cfg.dtype)


# --------------------------------------------------------------------- forward
def _dense_eva_attention(q, k, v, mu, phi, cfg: EvaByteConfig):
    """Whole sequences, dense masks: ``q, k, v [B, S, H, hd]`` -> ``[B, S,
    H, hd]``. Every chunk is summarised, whether or not anyone sees it."""
    b, s, h, hd = q.shape
    w, c = cfg.window_size, cfg.chunk_size
    n_chunks = -(-s // c)
    pad = n_chunks * c - s

    def chunked(x):                                   # [B, J, H, c, hd]
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(b, n_chunks, c, h, hd).transpose(0, 1, 3, 2, 4)

    ks, vs = pa_ops.chunk_summaries(chunked(k), chunked(v), mu, phi)
    pos = jnp.arange(s)
    exact = (pos[:, None] // w == pos[None, :] // w) & (pos[None, :] <= pos[:, None])
    summary = jnp.arange(n_chunks)[None, :] < (pos[:, None] // w) * (w // c)
    scale = hd ** -0.5
    logits = jnp.concatenate([
        jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32),
        jnp.einsum("bqhd,bjhd->bhqj", q, ks.astype(q.dtype),
                   preferred_element_type=jnp.float32)], axis=-1) * scale
    mask = jnp.concatenate([exact, summary], axis=-1)
    probs = jax.nn.softmax(pa_ops.apply_mask(logits, mask), axis=-1).astype(q.dtype)
    return (jnp.einsum("bhqk,bkhd->bqhd", probs[..., :s], v,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhqj,bjhd->bqhd", probs[..., s:], vs.astype(q.dtype),
                         preferred_element_type=jnp.float32)).astype(q.dtype)


def forward(params, tokens, cfg: EvaByteConfig, pred_heads: bool = False):
    """``tokens [B, S]`` -> float32 next-byte logits ``[B, S, V]`` (with
    ``pred_heads`` all ``num_pred_heads`` of them, ``[B, S, P, V]``)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(params, tokens)
    for i in range(cfg.num_hidden_layers):
        bp = params[f"layers_{i}"]
        q, k, v = _qkv(bp["attn"], _norm(bp["norm1"], x, cfg), positions, cfg)
        o = _dense_eva_attention(q, k, v, bp["attn"]["mu"], bp["attn"]["phi"], cfg)
        x = _rest_of_block(bp, x, o.reshape(b, s, cfg.hidden_size), cfg)
    if not pred_heads:
        return _next_byte_logits(params, x, cfg)
    full = L.lm_head(params["head"], _norm(params["norm_f"], x, cfg),
                     compute_dtype=cfg.dtype)
    return full.reshape(b, s, cfg.num_pred_heads, cfg.vocab_size)


# ------------------------------------------------------------------ paged cache
def init_paged_cache(cfg: EvaByteConfig, n_pages: int, page_len: int,
                     dtype: Any = None):
    """One leaf a layer and kind, ``[n_pages, H, page_len, hd]``: heads
    major, as ``eva_paged_attention`` contracts a page, so neither program
    slices a stacked pool or transposes a page. A ring page holds
    ``page_len`` positions' rotated keys (or values); a summary page holds
    ``page_len`` chunks' summaries, the same shape."""
    if page_len != cfg.chunk_size:
        raise ValueError(f"a page is one chunk of {cfg.chunk_size} positions; "
                         f"page_len={page_len} was asked for")
    shape = (n_pages, cfg.num_attention_heads, page_len, cfg.head_dim)
    dtype = dtype or cfg.dtype
    return {"k": [jnp.zeros(shape, dtype) for _ in range(cfg.num_hidden_layers)],
            "v": [jnp.zeros(shape, dtype) for _ in range(cfg.num_hidden_layers)]}


def _impl(cfg: EvaByteConfig) -> str:
    if cfg.paged_attention_impl != "auto":
        return cfg.paged_attention_impl
    return "kernel" if jax.default_backend() == "tpu" else "gather"


def _sample(logits, counters, samp):
    if samp is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from autodist_tpu.serve.sampling import sample_tokens

    return sample_tokens(logits, counters, samp)


def forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                page_table, cfg: EvaByteConfig, samp=None,
                                return_logits: bool = False):
    """One chunk of one row's prompt: ``tokens [1, C]`` at positions
    ``[start, start + C)``, ``C`` a multiple of the chunk that divides the
    window (``start`` a multiple of ``C``), so the chunk lies in one window
    and covers whole pages. Each layer writes the chunk's rotated keys and
    values to its ring pages, summarises each of its ``C / chunk`` chunks
    into their summary rows, and attends: ring entries up to each query,
    and the summaries of the windows behind. Positions at or past
    ``length`` are padding: what they write lies past every count until a
    decode step writes it again.

    Returns ``(next_token [1], cache)``: the token after position
    ``length - 1`` (meaningful on the prompt's last chunk)."""
    b, c = tokens.shape
    w, pl_ = cfg.window_size, cfg.chunk_size
    ring_pages = w // pl_
    n_chunk_pages = c // pl_
    pos = start + jnp.arange(c)
    ring_ids = page_table[(start % w) // pl_ + jnp.arange(n_chunk_pages)]
    # The chunk's summaries are whole summary pages, or a run of rows in one
    # (the chunk is aligned to its own length): pages are only ever written
    # whole, indexed by page alone, so that the pool keeps the one layout
    # the kernel reads and no program copies it.
    first_chunk = start // pl_
    n_sum_pages = max(1, n_chunk_pages // pl_)
    sum_ids = page_table[ring_pages + first_chunk // pl_ + jnp.arange(n_sum_pages)]

    def paged(t):                           # [C, H, hd] -> [C/pl, H, pl, hd]
        return t.reshape(-1, pl_, *t.shape[1:]).transpose(0, 2, 1, 3)

    def with_summaries(pool, rows):         # rows [C/pl, H, hd] float32
        rows = rows.astype(pool.dtype)
        if n_chunk_pages >= pl_:
            return pool.at[sum_ids].set(paged(rows))
        page = jax.lax.dynamic_update_slice_in_dim(
            pool[sum_ids[0]], rows.transpose(1, 0, 2), first_chunk % pl_, axis=1)
        return pool.at[sum_ids].set(page[None])

    x = _embed(params, tokens)
    ks_cache, vs_cache = list(cache["k"]), list(cache["v"])
    for i in range(cfg.num_hidden_layers):
        bp = params[f"layers_{i}"]
        q, k, v = _qkv(bp["attn"], _norm(bp["norm1"], x[0], cfg), pos, cfg)
        kp, vp = paged(k), paged(v)
        k_sum, v_sum = pa_ops.chunk_summaries(
            kp, vp, bp["attn"]["mu"], bp["attn"]["phi"])
        ck = with_summaries(ks_cache[i].at[ring_ids].set(kp), k_sum)
        cv = with_summaries(vs_cache[i].at[ring_ids].set(vp), v_sum)
        ks_cache[i], vs_cache[i] = ck, cv
        o = pa_ops.eva_paged_attention(
            q.transpose(1, 0, 2)[None], ck, cv, page_table[None], pos[None],
            ring_pages=ring_pages, window=w, impl=_impl(cfg))
        o = o[0].transpose(1, 0, 2).reshape(b, c, cfg.hidden_size)
        x = _rest_of_block(bp, x, o, cfg)
    cache = dict(cache, k=ks_cache, v=vs_cache)
    frontier = jnp.clip(length - 1 - start, 0, c - 1)
    logits = _next_byte_logits(params, x[jnp.arange(b), frontier], cfg)
    if return_logits:
        return _next_byte_logits(params, x, cfg), cache
    counters = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    return _sample(logits, counters, samp), cache


def forward_paged_decode_step(params, tokens, positions, cache, page_tables,
                              cfg: EvaByteConfig, samp=None,
                              return_logits: bool = False):
    """One decode step over every row: ``tokens [B]`` at ``positions [B]``
    through ``page_tables [B, ring + summary pages]``. Each layer writes
    the token's rotated key and value to ring entry ``(p % window) //
    chunk``, row ``p % chunk``; where that completes a chunk (``p % chunk
    == chunk - 1``) it summarises the page it has just filled into summary
    row ``p // chunk`` (elsewhere the summary goes to the scratch page); and
    attends over ``p % window + 1`` ring entries and ``(p // window) *
    (window // chunk)`` summaries. A window boundary is nothing special:
    position ``k * window`` writes ring entry 0 and sees the summaries of
    the window that has just closed.

    Returns ``(next_token [B], cache)``."""
    b = tokens.shape[0]
    w, pl_ = cfg.window_size, cfg.chunk_size
    ring_pages = w // pl_
    rows = jnp.arange(b)
    ring_id = page_tables[rows, (positions % w) // pl_]
    off = positions % pl_
    chunk_no = positions // pl_
    sum_id = jnp.where(off == pl_ - 1,
                       page_tables[rows, ring_pages + chunk_no // pl_],
                       serve_pages.SCRATCH_PAGE)
    # Pages are read and written whole, indexed by page alone (a write of
    # one row through [page, :, row] would have XLA lay the pool out rows
    # major, and copy it to and from the layout the kernel reads).
    at_row = jnp.arange(pl_)[None, None, :, None]
    here = at_row == off[:, None, None, None]                 # [B,1,pl,1]
    summed = at_row == (chunk_no % pl_)[:, None, None, None]

    def write(pool, ids, mask, value):      # value [B, H, hd] into one row
        page = jnp.where(mask, value[:, :, None, :].astype(pool.dtype), pool[ids])
        return pool.at[ids].set(page), page

    x = _embed(params, tokens)
    ks_cache, vs_cache = list(cache["k"]), list(cache["v"])
    for i in range(cfg.num_hidden_layers):
        bp = params[f"layers_{i}"]
        q, k, v = _qkv(bp["attn"], _norm(bp["norm1"], x, cfg), positions, cfg)
        ck, k_page = write(ks_cache[i], ring_id, here, k)
        cv, v_page = write(vs_cache[i], ring_id, here, v)
        k_sum, v_sum = pa_ops.chunk_summaries(
            k_page, v_page, bp["attn"]["mu"], bp["attn"]["phi"])
        ck, _ = write(ck, sum_id, summed, k_sum)
        cv, _ = write(cv, sum_id, summed, v_sum)
        ks_cache[i], vs_cache[i] = ck, cv
        o = pa_ops.eva_paged_attention(
            q[:, :, None], ck, cv, page_tables, positions[:, None],
            ring_pages=ring_pages, window=w, impl=_impl(cfg))
        x = _rest_of_block(bp, x, o.reshape(b, cfg.hidden_size), cfg)
    cache = dict(cache, k=ks_cache, v=vs_cache)
    logits = _next_byte_logits(params, x, cfg)
    if return_logits:
        return logits, cache
    return _sample(logits, positions.astype(jnp.int32) + 1, samp), cache


def decode_model(cfg: EvaByteConfig, eos_id: Optional[int] = None):
    """The serving adapter: the paged surface and the statement of the
    cache. No ``verify_paged``: speculative verification over a ring is
    not carried (``serve/spec.py`` refuses)."""
    from autodist_tpu.serve.engine import DecodeModel

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
    )
