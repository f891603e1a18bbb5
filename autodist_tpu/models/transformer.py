"""Transformer language model — the flagship workload.

Covers the reference's BERT benchmark slot (``/root/reference/examples/
benchmark/bert.py:40-49`` + ``utils/modeling/**``) as a compact pure-JAX
transformer: causal (GPT-style next-token) or bidirectional (BERT-style MLM)
loss, tied input/output embeddings, pre-norm blocks.

TPU-first choices:
- compute in bfloat16 (params fp32, matmuls bf16) — MXU-native;
- attention impl selectable: ``dot`` (XLA fused), ``flash`` (pallas kernel,
  :mod:`autodist_tpu.ops.flash_attention`), ``ring`` (sequence-parallel ring
  attention, :mod:`autodist_tpu.parallel.ring_attention`), or the default
  ``auto`` — ``flash`` at and above the measured crossover sequence length
  (``docs/measured/flash_crossover.json`` via
  :mod:`autodist_tpu.ops.crossover`), ``dot`` below it;
- optional ``jax.checkpoint`` per block (remat trades FLOPs for HBM);
- static shapes everywhere; the layer stack is a Python loop over identical
  blocks so XLA can pipeline it.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from autodist_tpu.models import layers as L
from autodist_tpu.models.spec import ModelSpec, register_model
from autodist_tpu.ops import paged_attention as pa_ops


@dataclass
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 512
    causal: bool = True                 # False => BERT-style MLM
    dtype: Any = jnp.bfloat16           # compute dtype (params stay fp32)
    # auto = measured-crossover selection (dot below, flash at/above the
    # seq length recorded in docs/measured/flash_crossover.json); explicit
    # dot | flash | ring | ulysses always honored.
    attention_impl: str = "auto"
    # Serving-path attention over the paged KV pool: gather (materialize the
    # timeline, XLA-fused attend — the pre-PR-20 programs, bit-preserved) |
    # kernel (pallas page-walking online softmax, ops/paged_attention.py) |
    # auto (measured crossover per shape, docs/measured/paged_crossover.json
    # via ops/crossover.py; always gather off-TPU).
    paged_attention_impl: str = "auto"
    # int8 KV pages with per-position/per-head f32 scales: quantize on
    # scatter, dequantize in the gather/kernel. ~3.76x effective pool
    # capacity at fp32/D=64 (68 bytes vs 256 per head-row); streams drift
    # within the documented logit bound (docs/serving.md § quantized pages).
    kv_quant: bool = False
    remat: bool = False
    mlm_mask_token: int = 0             # [MASK] id for the MLM objective
    # LayerNorm epsilon of every norm in the model (GPT-2 publishes 1e-5;
    # the default is what this model always ran with).
    layer_norm_eps: float = 1e-6

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads

    def param_count(self) -> int:
        d, f, v, l_ = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        # 4 attn kernels + 2 mlp kernels + attn biases + mlp biases + 2 LNs
        per_layer = 4 * d * d + 2 * d * f + 4 * d + (f + d) + 4 * d
        return v * d + self.max_seq_len * d + l_ * per_layer + 2 * d

    def flops_per_example(self, seq_len: Optional[int] = None) -> float:
        """fwd+bwd FLOPs per sequence: 3x forward; forward = 2*P*s matmul
        FLOPs + attention 4*s^2*d per layer."""
        s = seq_len or self.max_seq_len
        fwd = 2.0 * self.param_count() * s + 4.0 * self.num_layers * s * s * self.d_model
        return 3.0 * fwd


# ---------------------------------------------------------------------- params
def init_params(rng, cfg: TransformerConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, cfg.num_layers + 2)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, cfg.d_model),
        "pos_embed": L.embedding_init(keys[1], cfg.max_seq_len, cfg.d_model),
        "ln_f": L.layernorm_init(cfg.d_model),
    }
    for i in range(cfg.num_layers):
        k = jax.random.split(keys[i + 2], 6)
        params[f"layers_{i}"] = {
            "ln1": L.layernorm_init(cfg.d_model),
            "attn": {
                "wq": L.dense_init(k[0], cfg.d_model, cfg.d_model),
                "wk": L.dense_init(k[1], cfg.d_model, cfg.d_model),
                "wv": L.dense_init(k[2], cfg.d_model, cfg.d_model),
                "wo": L.dense_init(k[3], cfg.d_model, cfg.d_model),
            },
            "ln2": L.layernorm_init(cfg.d_model),
            "mlp": {
                "fc1": L.dense_init(k[4], cfg.d_model, cfg.d_ff),
                "fc2": L.dense_init(k[5], cfg.d_ff, cfg.d_model),
            },
        }
    return params


# --------------------------------------------------------------------- forward
def _dot_attention(q, k, v, causal: bool):
    """Plain fused attention: softmax(QK^T/sqrt(d))V, fp32 softmax."""
    head_dim = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        logits = pa_ops.apply_mask(logits, mask)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attention(q, k, v, cfg: TransformerConfig):
    impl = cfg.attention_impl
    if impl == "auto":
        # Measured-crossover auto-selection: flash at/above the recorded
        # breakeven seq (block-aligned), dot below — so the default hot
        # path is the Pallas kernel exactly where the sweep shows it wins.
        from autodist_tpu.ops.crossover import resolve_attention_impl

        impl = resolve_attention_impl(impl, q.shape[1])
    if impl == "dot":
        return _dot_attention(q, k, v, cfg.causal)
    if impl == "flash":
        from autodist_tpu.ops.flash_attention import (
            flash_attention, kernel_supports)

        if not kernel_supports(q.shape[1], k.shape[1]):
            # "auto" never lands here (it only resolves to flash on aligned
            # sequences), so this is an explicit config the kernel cannot
            # honor — the op's reference fallback would silently train a
            # different program than the one configured.
            raise ValueError(
                f"attention_impl='flash' needs equal sequence lengths that "
                f"are a multiple of 128; got q={q.shape[1]} k={k.shape[1]}. "
                f"Use attention_impl='auto' or 'dot' for this shape.")
        return flash_attention(q, k, v, causal=cfg.causal)
    if impl == "ring":
        from autodist_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=cfg.causal)
    if impl == "ulysses":
        from autodist_tpu.parallel.ring_attention import ulysses_attention

        return ulysses_attention(q, k, v, causal=cfg.causal)
    raise ValueError(f"unknown attention_impl {cfg.attention_impl!r}")


def _attn_part(block_params, x, attend, cfg: TransformerConfig):
    """The attention half of the one layer body, over ``x [..., D]``
    whatever its leading dims: pre-norm, the three projections split into
    heads ``[..., H, head_dim]``, ``o, carry = attend(q, k, v)``, the
    output projection and the residual. ``attend`` is all that differs
    between the programs (training: :func:`_attention`; a paged program:
    the write through its page table and its paged read, the written cache
    as ``carry``). Returns ``(x, carry)``."""
    lead = x.shape[:-1]
    h = L.layernorm(block_params["ln1"], x, cfg.layer_norm_eps)
    attn_p = block_params["attn"]
    q = L.dense(attn_p["wq"], h, compute_dtype=cfg.dtype)
    k = L.dense(attn_p["wk"], h, compute_dtype=cfg.dtype)
    v = L.dense(attn_p["wv"], h, compute_dtype=cfg.dtype)
    heads = lead + (cfg.num_heads, cfg.head_dim)
    o, carry = attend(q.reshape(heads), k.reshape(heads), v.reshape(heads))
    o = o.reshape(lead + (cfg.d_model,))
    return x + L.dense(attn_p["wo"], o, compute_dtype=cfg.dtype), carry


def _mlp_part(block_params, x, cfg: TransformerConfig):
    """The MLP half of the one layer body: pre-norm, ``fc1``, GELU,
    ``fc2``, residual."""
    h = L.layernorm(block_params["ln2"], x, cfg.layer_norm_eps)
    h = L.dense(block_params["mlp"]["fc1"], h, compute_dtype=cfg.dtype)
    h = jax.nn.gelu(h)
    h = L.dense(block_params["mlp"]["fc2"], h, compute_dtype=cfg.dtype)
    return x + h


def _train_attend(cfg: TransformerConfig):
    """Training's ``attend``: attention over the sequence itself, nothing
    carried."""
    return lambda q, k, v: (_attention(q, k, v, cfg), None)


def _block(block_params, x, cfg: TransformerConfig):
    x, _ = _attn_part(block_params, x, _train_attend(cfg), cfg)
    return _mlp_part(block_params, x, cfg)


#: Tokens up to which :func:`_columns` reads a column a token: a column
#: touches ``D / 8`` tiles, about 1/800 of what re-laying a 50,257-row table
#: out costs, and the slices are unrolled into the program.
_COLUMN_TOKENS = 256


def _columns(table, ids):
    """``table[:, ids]`` moved to ``ids.shape + (D,)``, for a table held
    ``[D, V]``. A serving program's few tokens take one dynamic slice each,
    which reads a column where it lies (clamped where a gather would
    fill); more take one gather, which re-lays the table out first."""
    flat = ids.reshape(-1)
    if flat.shape[0] > _COLUMN_TOKENS:
        return jnp.moveaxis(jnp.take(table, ids, axis=1), 0, -1)
    cols = [jax.lax.dynamic_slice_in_dim(table, flat[j], 1, axis=1)
            for j in range(flat.shape[0])]
    return jnp.concatenate(cols, axis=1).T.reshape(ids.shape + table.shape[:1])


def _embed(params, tokens, cfg: TransformerConfig, positions=None):
    """Token + learned position embedding in the compute dtype;
    ``positions`` None is the sequence's own ``0..S-1``. A tree that holds
    the tied table as the head reads it (``head``, :func:`serving_params`)
    gives a token's row from its column."""
    if "head" in params:
        x = _columns(params["head"], tokens)
    else:
        x = L.embedding_lookup(params["embed"], tokens)
    if positions is None:
        positions = jnp.arange(tokens.shape[-1])
    return (x.astype(cfg.dtype)
            + L.embedding_lookup(params["pos_embed"], positions).astype(cfg.dtype))


def _head(params, x, cfg: TransformerConfig):
    """Tied output embedding: one big ``[.., D] x [D, V]`` matmul on the
    MXU, in the compute dtype; a tree that holds ``head`` is read as it
    lies, training's transposes the embedding."""
    head = params["head"] if "head" in params else params["embed"]["embedding"].T
    return x.astype(cfg.dtype) @ head.astype(cfg.dtype)


def serving_params(params, cfg: TransformerConfig):
    """The tree the paged programs read (``DecodeModel.serving_params``):
    every leaf whose only use there is behind ``.astype(cfg.dtype)`` cast
    to it once — the projections' kernels and biases (``L.dense``) and both
    embeddings (:func:`_embed` casts after the lookup, :func:`_head` casts
    the table). The LayerNorm leaves stay as given: ``L.layernorm``
    multiplies float32 by them. The same values reach every product, at
    half the bytes.

    The tied table is held once, as the head's product reads it: ``head``
    ``[D, V]`` in place of ``embed``. On the TPU ``[V, D]`` lies with V
    minor at these widths, as the product wants it, so a gather of its
    rows re-lays all of it out on every call; :func:`_embed` reads a
    token's column instead."""
    def cast(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(cfg.dtype), tree)

    out = {k: v for k, v in params.items() if k != "embed"}
    out["head"] = params["embed"]["embedding"].T.astype(cfg.dtype)
    out["pos_embed"] = cast(params["pos_embed"])
    for i in range(cfg.num_layers):
        block = params[f"layers_{i}"]
        out[f"layers_{i}"] = dict(block, attn=cast(block["attn"]),
                                  mlp=cast(block["mlp"]))
    return out


def forward(params, tokens, cfg: TransformerConfig):
    """tokens [B, S] int32 -> logits [B, S, V] (fp32)."""
    x = _embed(params, tokens, cfg)
    block = partial(_block, cfg=cfg)
    if cfg.remat:
        block = jax.checkpoint(block)
    for i in range(cfg.num_layers):
        x = block(params[f"layers_{i}"], x)
    x = L.layernorm(params["ln_f"], x, cfg.layer_norm_eps)
    return _head(params, x, cfg).astype(jnp.float32)


def loss_fn(params, batch, cfg: TransformerConfig):
    if cfg.causal:
        # Run attention on the full (block-aligned) sequence and shift the
        # logits, not the inputs — trimming to s-1 would break the flash
        # kernel's block alignment and silently fall back to O(s^2) attention.
        tokens = batch["tokens"]
        logits = forward(params, tokens, cfg)
        return L.softmax_xent(logits[:, :-1], tokens[:, 1:])
    # MLM: corrupt masked positions with [MASK], predict the original ids.
    mask = batch["mlm_mask"]
    inputs = jnp.where(mask.astype(bool), cfg.mlm_mask_token, batch["tokens"])
    logits = forward(params, inputs, cfg)
    mask = mask.astype(jnp.float32)  # 1 where masked
    per_tok = L.per_token_xent(logits, batch["labels"]) * mask
    return per_tok.sum() / jnp.maximum(mask.sum(), 1.0)


# --------------------------------------------------------- paged KV decode
def init_paged_kv_cache(cfg: TransformerConfig, n_pages: int, page_len: int,
                        dtype: Any = None,
                        quantized: Optional[bool] = None) -> Dict[str, Any]:
    """Paged decode cache: ONE pool of fixed-size KV pages shared by every
    concurrent request — per projection a leaf a layer, ``[n_pages,
    page_len, heads * head_dim]``. Which pages hold which request's
    timeline is the engine's page tables (``serve/pages.py``); the leaves
    are donated through the compiled serving programs and rewritten in
    place, so steady-state serving allocates nothing and slot utilization
    no longer depends on guessing a length distribution (the vLLM
    rendering of GSPMD's static-annotation premise, docs/serving.md).

    Heads x head_dim lie together on the last axis because that is the
    form the TPU compiler keeps row-major by itself: a leaf whose last dim
    is a head_dim of 64 it holds with the page dim minor, and re-lays the
    whole pool out around every write and every kernel call (PERF.md § 6,
    PR 31). A layer's write touches that layer's leaf alone, and the paged
    kernel reads ``(1, page_len, heads * head_dim)`` blocks of it as it
    lies (``ops/paged_attention.py``).

    With ``cfg.kv_quant`` (or ``quantized=True``) the pages hold int8 with
    f32 per-(page, position, head) scale leaves ``[n_pages, page_len,
    heads]`` alongside — the page dim leads there too, so the engine's
    sharding, COW page copy, and byte pricing pick the scales up without
    special cases.
    """
    if quantized is None:
        quantized = bool(getattr(cfg, "kv_quant", False))
    shape = (n_pages, page_len, cfg.num_heads * cfg.head_dim)

    def leaves(shape, dtype):
        return [jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)]

    if quantized:
        sshape = (n_pages, page_len, cfg.num_heads)
        return {"k": leaves(shape, jnp.int8),
                "v": leaves(shape, jnp.int8),
                "k_scale": leaves(sshape, jnp.float32),
                "v_scale": leaves(sshape, jnp.float32)}
    dtype = dtype or cfg.dtype
    return {"k": leaves(shape, dtype), "v": leaves(shape, dtype)}


def _own_leaves(cache):
    """The cache with leaf lists of its own: the programs below replace a
    layer's leaf as they go and must not write into the caller's lists."""
    return {name: list(leaves) for name, leaves in cache.items()}


def _paged_scatter(cache, layer, page_of, off, k, v):
    """Write one program's k/v rows ``[..., H, D]`` into layer ``layer``'s
    leaves through the page table indices (``page_of`` and ``off`` shaped
    like the rows' leading dims) — quantize-on-scatter when the cache
    carries int8 pages (scales land in the matching ``*_scale`` leaves),
    plain dtype cast otherwise."""
    def write(name, rows):
        leaf = cache[name][layer]
        cache[name][layer] = leaf.at[page_of, off].set(rows.astype(leaf.dtype))

    def lanes(rows):                       # [..., H, D] -> [..., H * D]
        return rows.reshape(rows.shape[:-2] + (-1,))

    if "k_scale" in cache:
        kq, ks = pa_ops.quantize_kv(k)
        vq, vs = pa_ops.quantize_kv(v)
        write("k", lanes(kq))
        write("v", lanes(vq))
        write("k_scale", ks)
        write("v_scale", vs)
    else:
        write("k", lanes(k))
        write("v", lanes(v))
    return cache


def _paged_layers(params, x, cache, page_of, off, attention, tables,
                  positions, cfg: TransformerConfig):
    """The layer stack and the final norm of a paged program over ``x [B,
    ..., D]``. Each layer is the one body (:func:`_attn_part`,
    :func:`_mlp_part`) around an ``attend`` that scatters the rows' k/v
    through ``(page_of, off)`` and reads with ``attention``, the one of
    ``ops/paged_attention.py``'s three entry points the program calls
    with its ``tables`` and ``positions``. The kernel-vs-gather choice is
    made once here at trace time — static, so the engine's compiled-program
    pins (two serving programs, three with speculative verification) never
    fork on it; the math itself lives in ops/paged_attention.py only.
    Returns ``(x, cache)``: the cache is threaded through ``attend`` as a
    value, never written behind the body's back."""
    from autodist_tpu.ops.crossover import resolve_paged_impl

    cache = _own_leaves(cache)
    impl = resolve_paged_impl(
        cfg.paged_attention_impl, x.shape[0], tables.shape[-1],
        cache["k"][0].shape[1], cfg.num_heads)
    for i in range(cfg.num_layers):
        def attend(q, k, v, i=i, cache=cache):
            # The rows take the leading dims of their table indices: the
            # chunk program's lead with a batch dim of 1 its indices lack.
            q, k, v = (t.reshape(page_of.shape + t.shape[-2:])
                       for t in (q, k, v))
            cache = _paged_scatter(cache, i, page_of, off, k, v)
            ks, vs = ((cache["k_scale"][i], cache["v_scale"][i])
                      if "k_scale" in cache else (None, None))
            return attention(
                q, cache["k"][i], cache["v"][i], tables, positions,
                k_scale=ks, v_scale=vs, impl=impl,
                compute_dtype=cfg.dtype), cache

        block_params = params[f"layers_{i}"]
        x, cache = _attn_part(block_params, x, attend, cfg)
        x = _mlp_part(block_params, x, cfg)
    return L.layernorm(params["ln_f"], x, cfg.layer_norm_eps), cache


def _pick(logits, counters, samp):
    """The token a paged program ends with: the greedy argmax, or with
    ``samp`` (the per-slot sampling arrays, serve/sampling.py) the
    counter-keyed sample, ``counters`` being the emitted tokens' absolute
    positions; ``temperature<=0`` rows still return the argmax bit-exact."""
    if samp is None:
        return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    from autodist_tpu.serve.sampling import sample_tokens

    return sample_tokens(logits, counters, samp)


def forward_paged_prefill_chunk(params, tokens, start, length, cache,
                                page_table, cfg: TransformerConfig,
                                samp=None):
    """One chunk of a paged prefill: the SINGLE compiled prefill program.

    ``tokens [1, C]`` are prompt positions ``[start, start + C)`` (padded
    past ``length``); each layer writes the chunk's k/v through
    ``page_table [P]`` and its queries attend causally over the gathered
    timeline — previously prefilled chunks included, so any prompt length
    runs as ``ceil(len / C)`` invocations of this one program, interleaved
    with decode steps by the batcher.

    Pad positions (``>= length``) write garbage into the request's own
    FUTURE timeline slots (decode overwrites each before its position
    enters any mask) or, past the table's real pages, into the reserved
    scratch page — never into another request's pages. The engine
    guarantees ``start + C <= max_len`` (``max_len`` is rounded to a
    multiple of the chunk), so ``pos // page_len`` never leaves the table.

    Returns ``(next_token [1], cache)``; the token is the argmax at
    position ``length - 1``, meaningful only on the chunk containing it
    (the host uses the final chunk's value — prefill emits the first
    generated token). With ``samp``
    (the per-slot sampling arrays, serve/sampling.py) the token is the
    counter-keyed sample at absolute position ``length`` instead —
    identical on every chunk, so the host's final-chunk read is
    unchanged; ``temperature<=0`` rows still return the argmax bit-exact.
    """
    b, c = tokens.shape
    page_len = cache["k"][0].shape[1]
    pos = start + jnp.arange(c)                                   # [C] absolute
    page_of = page_table[pos // page_len]                         # [C]
    off = pos % page_len
    # Clamp the positional-embedding lookup only: pad positions may sit past
    # the table (their k/v land in scratch) but must still embed in-range.
    x = _embed(params, tokens, cfg, jnp.minimum(pos, cfg.max_seq_len - 1))
    x, cache = _paged_layers(params, x, cache, page_of, off,
                             pa_ops.paged_prefill_attention, page_table, pos,
                             cfg)
    frontier = jnp.clip(length - 1 - start, 0, c - 1)
    logits = _head(params, x[jnp.arange(b), frontier], cfg)       # [1, V]
    # The emitted token's absolute position is `length` (prompt occupies
    # 0..length-1) — the same counter on every chunk of this prompt.
    counters = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    return _pick(logits, counters, samp), cache


def forward_paged_decode_step(params, tokens, positions, cache, page_tables,
                              cfg: TransformerConfig, samp=None,
                              return_logits: bool = False):
    """One incremental decode step over every decode row: the SINGLE
    compiled decode program for all active requests.

    ``tokens [B] int32`` is each row's current token (B == slot count),
    ``positions [B]`` its absolute timeline index; ``page_tables [B, P]``
    maps each row's timeline onto pool pages (idle rows carry all-scratch
    tables and compute finite garbage the engine ignores). Each layer
    scatters the token's k/v through the row's table and attends over the
    gathered timeline under ``j <= positions[b]`` — the incremental
    equivalent of the causal forward's row ``positions[b]``, so one
    program serves any mix of request lengths.

    Returns ``(next_token [B] int32, cache)``.
    """
    page_len = cache["k"][0].shape[1]
    rows = jnp.arange(tokens.shape[0])
    page_of = page_tables[rows, positions // page_len]            # [B]
    off = positions % page_len
    x = _embed(params, tokens, cfg,
               jnp.minimum(positions, cfg.max_seq_len - 1))
    x, cache = _paged_layers(params, x, cache, page_of, off,
                             pa_ops.paged_decode_attention, page_tables,
                             positions, cfg)
    logits = _head(params, x, cfg)
    if return_logits:
        # Drift-probe path (tests / selftest only — never compiled by the
        # engine, so the program pins don't see it): expose the fp32
        # logits next to the token for quant-vs-fp oracle comparison.
        return (_pick(logits, None, None), logits.astype(jnp.float32), cache)
    # The incoming token sits at `positions`; the emitted token's
    # absolute position — the draw counter — is `positions + 1`.
    return _pick(logits, positions.astype(jnp.int32) + 1, samp), cache


def forward_paged_verify(params, tokens, positions, cache, page_tables,
                         cfg: TransformerConfig, samp=None):
    """Speculative-decode verification: the SINGLE compiled target-model
    program per spec round — the batched generalization of
    :func:`forward_paged_prefill_chunk` (every decode row at once, each
    with its own start position) crossed with the decode step's per-row
    page tables.

    ``tokens [B, K1]`` is each row's pending token followed by its K
    draft proposals (``K1 == K + 1``); ``positions [B]`` the row's
    current timeline position (the pending token's write slot);
    ``page_tables [B, P]`` as in :func:`forward_paged_decode_step`. Each
    layer scatters all K1 tokens' k/v through the row's table at
    positions ``positions[b] + j`` and the query at offset ``j`` attends
    causally over the gathered timeline (``t <= positions[b] + j``) —
    exactly the context plain greedy decode would have seen token by
    token, so the per-position argmaxes ARE the plain-greedy stream and
    greedy acceptance is lossless by construction (docs/serving.md §
    speculative decode).

    Safety: positions at or past the static table width (a draft window
    hanging off the timeline ceiling near ``max_new_tokens``) clamp to
    the scratch page — like pad entries, their garbage is excluded by
    every position mask (page 0 is the reserved scratch page,
    ``serve/pages.py``); rows not in decode (idle/prefilling) ride along
    against all-scratch tables and are ignored by the host.

    Returns ``(accept [B], out_tokens [B, K1], cache)`` — pure on-device
    accept/reject: ``out_tokens[b, j]`` is the target's greedy token
    after the prefix through ``tokens[b, j]``, and ``accept[b]`` counts
    the leading draft proposals that match it (0..K). The engine emits
    ``out_tokens[b, :accept[b] + 1]`` — the accepted prefix plus the
    target's own bonus/correction token — which is bit-identical to what
    plain greedy decode would have produced.
    """
    k1 = tokens.shape[1]
    page_len = cache["k"][0].shape[1]
    n_tables = page_tables.shape[1]
    rows_pos = positions[:, None] + jnp.arange(k1)[None, :]       # [B, K1]
    pidx = rows_pos // page_len
    # Past the static table width -> the reserved scratch page (0): the
    # same "finite garbage the masks exclude" contract as pad entries.
    page_of = jnp.where(
        pidx < n_tables,
        jnp.take_along_axis(page_tables, jnp.minimum(pidx, n_tables - 1),
                            axis=1),
        0)                                                        # [B, K1]
    off = rows_pos % page_len
    emb_pos = jnp.minimum(rows_pos, cfg.max_seq_len - 1)
    # The draft is a DIFFERENT model: a proposal outside the target's
    # vocab is legal input here. Clamp the EMBEDDING read only —
    # jnp.take's out-of-bounds fill is NaN, and one NaN k/v row would
    # poison every query through 0 * NaN in the masked attention sum.
    # Acceptance below compares the RAW proposals, so a clamped
    # out-of-vocab id can never falsely match the target's argmax.
    emb_ids = jnp.clip(tokens, 0, cfg.vocab_size - 1)
    x = _embed(params, emb_ids, cfg, emb_pos)
    x, cache = _paged_layers(params, x, cache, page_of, off,
                             pa_ops.paged_verify_attention, page_tables,
                             rows_pos, cfg)
    # out[b, j] is the token emitted after the prefix through
    # tokens[b, j] — absolute position rows_pos + 1, the same counter
    # plain decode uses for that position, so a coupled sample here IS
    # the plain stochastic stream's token and the accept count below
    # stays lossless for any draft (serve/sampling.py § coupling).
    out = _pick(_head(params, x, cfg), rows_pos.astype(jnp.int32) + 1, samp)
    # Accept/reject on device: count the leading proposals that match
    # the target's own (argmax or coupled-sample) token per position.
    match = (tokens[:, 1:] == out[:, :-1]).astype(jnp.int32)      # [B, K]
    accept = jnp.cumprod(match, axis=1).sum(axis=1).astype(jnp.int32)
    return accept, out, cache


def decode_model(cfg: TransformerConfig, eos_id: Optional[int] = None):
    """The transformer's serving adapter — the pure paged-cache functions
    bound to one config, in the shape
    :class:`autodist_tpu.serve.InferenceEngine` consumes (see
    serve/engine.py DecodeModel)."""
    from autodist_tpu.serve.engine import DecodeModel

    return DecodeModel(
        init_paged_cache=lambda n_pages, page_len, quantized=None:
            init_paged_kv_cache(cfg, n_pages, page_len, quantized=quantized),
        prefill_chunk=lambda params, tokens, start, length, cache, table,
            samp=None: forward_paged_prefill_chunk(
                params, tokens, start, length, cache, table, cfg, samp=samp),
        decode_paged=lambda params, tokens, positions, cache, tables,
            samp=None: forward_paged_decode_step(
                params, tokens, positions, cache, tables, cfg, samp=samp),
        verify_paged=lambda params, tokens, positions, cache, tables,
            samp=None: forward_paged_verify(
                params, tokens, positions, cache, tables, cfg, samp=samp),
        eos_id=eos_id,
        max_len=cfg.max_seq_len,
        serving_params=partial(serving_params, cfg=cfg),
    )


# ------------------------------------------------------------------- modelspec
@register_model("transformer")
def transformer_lm(**overrides) -> ModelSpec:
    cfg = TransformerConfig(**overrides)

    def example_batch(batch_size: int):
        s = cfg.max_seq_len
        tokens = (jnp.arange(batch_size * s, dtype=jnp.int32).reshape(batch_size, s)
                  % cfg.vocab_size)
        if cfg.causal:
            return {"tokens": tokens}
        mask = (jnp.arange(s) % 7 == 0).astype(jnp.int32)
        return {
            "tokens": tokens,
            "labels": tokens,
            "mlm_mask": jnp.broadcast_to(mask, (batch_size, s)),
        }

    return ModelSpec(
        name="transformer",
        init=lambda rng: init_params(rng, cfg),
        loss_fn=lambda p, b: loss_fn(p, b, cfg),
        example_batch=example_batch,
        apply=lambda p, tokens: forward(p, tokens, cfg),
        config=cfg,
        flops_per_example=cfg.flops_per_example(),
    )


@register_model("bert_base")
def bert_base(**overrides) -> ModelSpec:
    """BERT-base MLM pretraining config (the reference's BERT benchmark slot,
    examples/benchmark/bert.py)."""
    kw = dict(
        vocab_size=30522, num_layers=12, d_model=768, num_heads=12,
        d_ff=3072, max_seq_len=128, causal=False,
    )
    kw.update(overrides)
    spec = transformer_lm(**kw)
    spec.name = "bert_base"
    return spec


@register_model("bert_large")
def bert_large(**overrides) -> ModelSpec:
    """BERT-large uncased — the exact model the reference's published
    benchmark pretrains (docs/usage/performance.md:7, bert_config.json in
    examples/benchmark/utils: L=24, H=1024, A=16)."""
    kw = dict(
        vocab_size=30522, num_layers=24, d_model=1024, num_heads=16,
        d_ff=4096, max_seq_len=128, causal=False,
    )
    kw.update(overrides)
    spec = transformer_lm(**kw)
    spec.name = "bert_large"
    return spec
