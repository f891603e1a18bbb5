"""Paged attention for TPU — the ONE home for softmax-over-pages math.

Every serving-path attention over the paged KV pool lives here (enforced by
``tools/check_patterns.py`` rule 12): the gather reference implementations the
compiled programs shipped with since PR 12, and the pallas kernel that walks
each row's page table directly in HBM, a group of pages a grid step — online
softmax per group (Dao et al., arXiv 2205.14135, rendered over pages instead
of contiguous K blocks), the position mask folded into the block loop, no
materialized ``[B, P * page_len, H, D]`` timeline. How many pages make a
group is ``paged_blocking``'s, a pure function of the call's shapes; the
row's ``reach`` (its last query position + 1, a scalar-prefetch operand
beside the table) tells which groups to skip whole, with no copy issued;
the first group is always live, because slot 0 is admitted by every query
and seeds the running maximum with a finite logit. The heads are never
sliced out of the lanes: a block-diagonal query meets the lane-dense pages
in one MXU product (``_paged_kernel``). Three entry points match the engine's
compiled programs: decode step (one query per row), spec verify (K+1 queries
per row), and prefill-chunk (one row, C queries). A second kernel,
``eva_paged_attention``, attends over a window ring and chunk summaries (two
table segments with their own valid counts, one softmax; bottom of the file).

Underneath either impl sits optional int8 KV quantization with per-position
per-head scales (``quantize_kv`` / ``dequantize_kv``): pages store int8 plus
an f32 scale row, quantize-on-scatter happens in the model forwards,
dequantize happens on gather or inside the kernel block loop. At
``head_dim=64`` a KV position costs 68 bytes/head (64 int8 + 4 scale) vs 256
f32 (3.76x) or 128 bf16 (1.88x) — the effective-capacity math the analyzer
and selftest assert.

Correctness contract (tests/test_paged_kernel.py, serve --selftest):
- quant OFF: kernel token streams bit-identical to the gather path (the
  gather path itself is bit-identical to the pre-kernel programs — the
  einsum spellings below are verbatim);
- quant ON: logit drift vs the fp oracle bounded (documented in
  docs/serving.md), draft and verify run against the SAME quantized pages so
  spec-decode losslessness is preserved.

Impl selection is measured, not assumed: ``autodist_tpu.ops.crossover.
resolve_paged_impl`` picks kernel-vs-gather per (batch, table width, heads)
shape from the recorded sweep in ``docs/measured/paged_crossover.json``.

On CPU the kernel runs in pallas interpret mode (the tier-1 parity suite
exercises the same kernel logic the TPU compiles).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The masking constant every forward path shares. -1e30 is kept verbatim for
# f32 logits (bit-identity with the pre-hoist programs); non-f32 logits get a
# finite value well inside the dtype's range — a literal -1e30 overflows
# float16 to -inf and makes fully-masked rows NaN (inf - inf) instead of
# uniform, which is the footgun this helper retires.
NEG_INF = -1e30


def mask_value(dtype: Any = jnp.float32) -> float:
    """The additive-mask fill value for logits of ``dtype``."""
    dtype = jnp.dtype(dtype)
    if dtype in (jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)):
        return NEG_INF
    # Half of the finite minimum: representable, and far enough below any
    # real logit that softmax still zeroes the masked entries.
    return float(jnp.finfo(dtype).min) / 2.0


def position_mask(timeline: int, positions):
    """``True`` where timeline slot ``t <= positions[...]``.

    ``positions`` is ``[B]`` (decode), ``[C]`` (prefill-chunk absolute
    positions) or ``[B, K1]`` (verify rows); the mask gains a trailing
    timeline axis: ``positions.shape + (timeline,)``. Pad/scratch timeline
    slots always sit at or past a request's capacity — strictly above any
    live position — so this one comparison is the whole safety story for
    garbage pages (serve/pages.py SCRATCH_PAGE).
    """
    return jnp.arange(timeline) <= positions[..., None]


def apply_mask(logits, mask):
    """Fill ``~mask`` with the dtype-safe mask value (mask pre-broadcast)."""
    return jnp.where(mask, logits, mask_value(logits.dtype))


# ------------------------------------------------------------ quantization
def quantize_kv(x):
    """Symmetric int8 quantization over the head_dim axis.

    ``x [..., H, D]`` -> ``(int8 [..., H, D], f32 scale [..., H])`` with
    ``scale = amax(|x|) / 127`` per (position, head) row. All-zero rows keep
    scale 0 (dequantizes to exact zeros). Pure function of the input —
    deterministic, so failover re-prefill reproduces identical pages and the
    journal-replay bit-identity contract survives quantization.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x32 / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype: Any = jnp.float32):
    """Inverse of :func:`quantize_kv`: ``int8 * scale`` cast to ``dtype``."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# ------------------------------------------------------- gather reference
def _paged_gather(cache_layer, page_tables):
    """Gather one layer's KV timeline(s) by page index.

    ``cache_layer [n_pages, page_len, H * D]`` (or ``[n_pages, page_len, H]``
    for a scale plane); ``page_tables`` is ``[P]`` (one request) or ``[B, P]``
    (the decode batch). Returns the gathered timeline
    ``[..., P * page_len, ...]``. Pad entries point at the scratch page —
    finite garbage the caller's position mask excludes.
    """
    page_len = cache_layer.shape[1]
    tail = cache_layer.shape[2:]
    gathered = cache_layer[page_tables]          # [..., P, page_len, ...]
    return gathered.reshape(
        page_tables.shape[:-1] + (page_tables.shape[-1] * page_len,) + tail)


def _gather_timeline(pages, scale, page_tables, compute_dtype, heads: int):
    """Materialize the timeline ``[..., T, H, D]`` in ``compute_dtype``,
    dequantizing if ``scale`` is present. Pages lie with heads x head_dim
    on one axis; only the gathered timeline (small) is split into heads."""
    g = _paged_gather(pages, page_tables)
    g = g.reshape(g.shape[:-1] + (heads, g.shape[-1] // heads))
    if scale is None:
        return g.astype(compute_dtype)
    s = _paged_gather(scale, page_tables)
    return dequantize_kv(g, s, compute_dtype)


# ------------------------------------------------------------ pallas kernel
def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


#: What the page blocks of a grid step may take of VMEM (``paged_blocking``):
#: both streams' groups in the pipeline's two buffers and once more as the
#: operands the body makes of them. The block-diagonal query, the scores
#: and the accumulator come on top (4 MB for a chunk of 16 at 25 heads of
#: 64), all inside the 16 MiB a kernel may take unasked: a call that asks
#: for more (``vmem_limit_bytes``) has XLA's prefetch slice whole pool
#: leaves into fast memory ahead of it (PERF.md section 6, PR 35).
_VMEM_BUDGET = 10 << 20


@functools.lru_cache(maxsize=None)
def paged_blocking(n_q: int, n_tables: int, page_len: int, lanes: int,
                   itemsize: int, vmem_budget: int = _VMEM_BUDGET):
    """``(pages a grid step, queries a tile)`` of ``paged_attention``.

    One query a row (a decode step) is its own tile; more go in tiles of up
    to 16, whole sublane tiles of the operands' type (16 rows where the
    pages are bfloat16, 8 otherwise). A step walks the largest divisor of
    the table width that keeps it at or under 128 keys (one MXU tile of
    keys) and whose page blocks fit ``vmem_budget``: the step's fixed cost,
    the running maximum, the sum and the accumulator's rescale are paid
    once for the group, and past 128 keys a step costs in proportion to
    its keys, masked or not, so a larger group only computes more of what
    no query sees (PERF.md section 6, PR 35). A table width that nothing
    larger divides walks a page a step. Pure in what the call can see
    (queries a row, table width, the pages' shape and type), so the host
    counts the same groups: :func:`paged_group_counts`."""
    tile = 16 if itemsize == 2 else 8
    q_tile = 1 if n_q == 1 else min(-(-n_q // tile) * tile, 16)
    # a stream's element: twice in the pipeline's buffers, once as the
    # body's operand (int8: as float32, and its scale spread over the lanes)
    operand = {1: 8, 2: 2}.get(itemsize, 4)
    per_page = page_len * lanes * 2 * (2 * itemsize + operand)
    most = max(1, min(128 // page_len, vmem_budget // per_page, n_tables))
    group = max(g for g in range(1, most + 1) if n_tables % g == 0)
    return group, q_tile


def paged_group_counts(reach, n_q: int, n_tables: int, page_len: int,
                       lanes: int, itemsize: int):
    """``(groups, live groups)`` of one ``paged_attention`` call over rows
    whose queries are ``n_q`` consecutive positions ending one short of
    ``reach[b]`` (host integers): the page groups of the rows' tables, a
    query tile each, and those of them at or under a query's position,
    which are the ones the kernel computes. Every tile's first is live."""
    group, q_tile = paged_blocking(n_q, n_tables, page_len, lanes, itemsize)
    n_groups = n_tables // group
    span = group * page_len
    n_tiles = -(-n_q // q_tile)
    live = 0
    for r in reach:
        for i in range(n_tiles):
            # the tile's last query; the pad repeats the row's last
            end = min(int(r), int(r) - n_q + (i + 1) * q_tile)
            live += min(n_groups, max(1, -(-end // span)))
    return n_groups * n_tiles * len(reach), live


def _paged_kernel(tables_ref, reach_ref, qpos_ref, q_ref, *rest,
                  page_len: int, group: int, heads: int, quantized: bool,
                  scale: float):
    """One (row, query tile, page group) program: stream the row's pages
    ``group`` at a time, online softmax a group.

    Grid is ``(B, Q / tile, groups)`` with the group dimension minor: for
    a fixed row and tile the groups run in order and carry float32 (m, l,
    acc) in VMEM (init at g == 0, finalize at the last g). The group
    dimension ends at the last group any query of the call sees (a bound
    read on the device, not a shape), at most ``P / group``. The k/v
    BlockSpec index maps read ``tables_ref`` (scalar prefetch), one
    ``[page_len, H * D]`` block a page as the pool holds it, so traffic
    scales with the live table and no ``[B, P * page_len, H, D]`` timeline
    is made.

    **Groups past the tile's last position cost nothing.** ``reach_ref[b,
    i]`` is the largest query position of row ``b``'s tile ``i`` + 1 (the
    second scalar-prefetch operand). Under a longer row of the same call,
    a group that starts at or past it is skipped whole, and its index maps
    return the pages of the tile's last live group, which the pipeline
    holds already, so no copy is issued.
    Group 0 is always live: positions are >= 0, so slot 0 is admitted by
    every query and seeds ``m`` with a finite logit; inside a live group a
    masked entry then contributes exp(NEG_INF - m) == 0 (its page, scratch
    or not, must hold finite values, as serve/pages.py SCRATCH_PAGE
    promises). An idle row (all-scratch table, position 0) walks one group.

    **No head is sliced out of the lanes.** The group's keys ``[T, H * D]``
    meet a block-diagonal query ``[H * Q, H * D]`` (row ``h * Q + q`` holds
    query ``q``'s head ``h`` in lanes ``[h * D, (h + 1) * D)`` and zeros
    elsewhere; built once a tile): ``s[H * Q, T]`` is one MXU product over
    all lanes, ``p @ V`` gives ``[H * Q, H * D]``, and the output is its
    block diagonal, read once a tile by a mask and a sum over the heads'
    row blocks. That spends ``H`` times the products the math needs on an
    MXU that is otherwise idle and moves no lane. Operands are bfloat16
    where pages and query both are (products exact in float32, the
    probabilities cast as the gather rendering casts them), float32
    otherwise; int8 pages are scaled to float32 before either product.
    """
    n_kv = 2 * group
    k_refs, v_refs = rest[:group], rest[group:n_kv]
    if quantized:
        ks_refs, vs_refs = rest[n_kv:n_kv + group], rest[n_kv + group:2 * n_kv]
        rest = rest[2 * n_kv:]
    else:
        rest = rest[n_kv:]
    o_ref, qbd_ref, m_ref, l_ref, acc_ref = rest
    bi, qi, gi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows, lanes = qbd_ref.shape
    n_q, d = rows // heads, lanes // heads
    span = group * page_len
    mm = qbd_ref.dtype

    def diagonal(n):
        # [heads * n, lanes]: True where a row's head is the lane's head
        head = jax.lax.broadcasted_iota(jnp.int32, (heads * n, lanes), 0) // n
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads * n, lanes), 1)
        return (lane >= head * d) & (lane < (head + 1) * d)

    @pl.when(gi == 0)
    def _init():
        q = q_ref[0].astype(jnp.float32)                       # [Q, H * D]
        tiled = (jnp.broadcast_to(q, (rows, lanes)) if n_q == 1
                 else jnp.concatenate([q] * heads, axis=0))
        qbd_ref[...] = jnp.where(diagonal(n_q), tiled, 0.0).astype(mm)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def timeline(page_refs, scale_refs, spread):
        # [T, H * D]: the group's pages end to end, int8 ones scaled
        blk = jnp.concatenate([r[0].astype(mm) for r in page_refs], axis=0)
        if not quantized:
            return blk
        sc = jnp.concatenate([r[0] for r in scale_refs], axis=0)   # [T, H]
        return blk * jnp.dot(sc, spread, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)

    @pl.when(gi * span < reach_ref[bi, qi])
    def _attend():
        # [H, H * D]: a head's scale over the head's lanes
        spread = diagonal(1).astype(jnp.float32) if quantized else None
        k = timeline(k_refs, ks_refs if quantized else None, spread)
        v = timeline(v_refs, vs_refs if quantized else None, spread)
        s = jax.lax.dot_general(
            qbd_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H * Q, T]
        t_abs = gi * span + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 1)
        s = jnp.where(t_abs <= qpos_ref[0, 0], s, NEG_INF)     # [H * Q, 1]
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + pexp.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pexp.astype(mm), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(gi == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        out = jnp.where(diagonal(n_q),
                        acc_ref[...] / jnp.where(l == 0.0, 1.0, l), 0.0)
        o_ref[0] = out.reshape(heads, n_q, lanes).sum(axis=0).astype(
            o_ref.dtype)


def _kernel_attention(q4, k_pages, v_pages, page_tables, q_positions,
                      k_scale, v_scale, interpret: Optional[bool]):
    """Dispatch the unified kernel: ``q4 [B, Q, H, D]``, pages ``[n_pages,
    page_len, H * D]``, ``page_tables [B, P]``, ``q_positions [B, Q]``
    absolute positions per query. Returns ``[B, Q, H, D]`` in the query
    dtype."""
    if interpret is None:
        interpret = _should_interpret()
    return _kernel_call(q4, k_pages, v_pages, page_tables, q_positions,
                        k_scale, v_scale, interpret=interpret)


# Under a jit of its own, so that a program's layers share one trace and
# one lowering of the call: inline, 48 layers' calls cost a serving process
# 16 s of set-up on the chip's host (PERF.md section 6, PR 35). XLA inlines
# the call; the instruction keeps its name.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_call(q4, k_pages, v_pages, page_tables, q_positions, k_scale,
                 v_scale, *, interpret: bool):
    b, n_q, h, d = q4.shape
    page_len = k_pages.shape[1]
    n_tables = page_tables.shape[1]
    quantized = k_scale is not None
    mm = (jnp.bfloat16 if q4.dtype == k_pages.dtype == jnp.bfloat16
          else jnp.float32)
    group, q_tile = paged_blocking(n_q, n_tables, page_len, h * d,
                                   k_pages.dtype.itemsize)
    n_groups = n_tables // group
    span = group * page_len
    # Whole tiles of queries: the pad repeats the row's last query (no
    # tile's ``reach`` grows by it) and is cut off the output.
    pad = -n_q % q_tile
    n_tiles = (n_q + pad) // q_tile
    rows = h * q_tile
    qpos = jnp.pad(q_positions.astype(jnp.int32), ((0, 0), (0, pad)),
                   mode="edge").reshape(b, n_tiles, q_tile)
    q3 = jnp.pad(q4.reshape(b, n_q, h * d), ((0, 0), (0, pad), (0, 0)),
                 mode="edge")
    tables = page_tables.astype(jnp.int32)
    reach = qpos.max(axis=2) + 1                               # [B, tiles]
    # a position a row of the block-diagonal query: row h * Q + q is query q
    qpos_rows = jnp.tile(qpos, (1, 1, h))[..., None]    # [B, tiles, H * Q, 1]

    def page_index(j):
        def index(bi, qi, gi, t, reach):
            # a skipped group keeps the tile's last live group's pages
            last = jax.lax.div(reach[bi, qi] - 1, span)
            return (t[bi, jnp.minimum(gi, last) * group + j], 0, 0)
        return index

    def page_specs(width):
        return [pl.BlockSpec((1, page_len, width), page_index(j))
                for j in range(group)]

    q_spec = pl.BlockSpec((1, q_tile, h * d), lambda bi, qi, gi, *_: (bi, qi, 0))
    in_specs = [pl.BlockSpec((1, 1, rows, 1),
                             lambda bi, qi, gi, *_: (bi, qi, 0, 0)),
                q_spec] + page_specs(h * d) * 2
    operands = [qpos_rows, q3] + [k_pages] * group + [v_pages] * group
    if quantized:
        in_specs += page_specs(h) * 2
        operands += [k_scale] * group + [v_scale] * group

    # the grid ends at the call's last live group: no row walks further
    n_live = jnp.minimum(n_groups, jax.lax.div(reach.max() + span - 1, span))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tiles, n_live),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, h * d), mm),                # block-diagonal q
            pltpu.VMEM((rows, 1), jnp.float32),           # m
            pltpu.VMEM((rows, 1), jnp.float32),           # l
            pltpu.VMEM((rows, h * d), jnp.float32),       # acc
        ],
    )
    kernel = functools.partial(
        _paged_kernel, page_len=page_len, group=group, heads=h,
        quantized=quantized, scale=1.0 / (d ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(tables, reach, *operands)
    return out[:, :n_q].reshape(b, n_q, h, d)


def _check_impl(impl: str) -> None:
    if impl not in ("gather", "kernel"):
        raise ValueError(
            f"unknown paged attention impl {impl!r} (gather|kernel; resolve "
            "'auto' via autodist_tpu.ops.crossover.resolve_paged_impl first)")


# ------------------------------------------------------------- entry points
def paged_decode_attention(q, k_pages, v_pages, page_tables, positions, *,
                           k_scale=None, v_scale=None, impl: str = "gather",
                           compute_dtype: Any = None,
                           interpret: Optional[bool] = None):
    """Decode-step attention: ``q [B, H, D]`` (one query per row),
    ``k_pages, v_pages [n_pages, page_len, H * D]`` one layer's pool (int8
    pages: ``k_scale, v_scale [n_pages, page_len, H]`` beside them),
    ``page_tables [B, P]``, ``positions [B]``. Returns ``[B, H, D]``.

    ``impl='gather'`` is the verbatim pre-kernel program (einsum spellings
    preserved so pre-existing streams stay bit-identical); ``'kernel'``
    streams pages through the pallas block loop.
    """
    _check_impl(impl)
    compute_dtype = compute_dtype or q.dtype
    if impl == "kernel":
        out = _kernel_attention(q[:, None], k_pages, v_pages, page_tables,
                                positions[:, None], k_scale, v_scale,
                                interpret)
        return out[:, 0]
    head_dim = q.shape[-1]
    timeline = page_tables.shape[1] * k_pages.shape[1]
    ck = _gather_timeline(k_pages, k_scale, page_tables, compute_dtype,
                          q.shape[-2])
    cv = _gather_timeline(v_pages, v_scale, page_tables, compute_dtype,
                          q.shape[-2])
    mask = position_mask(timeline, positions)                     # [B, T]
    logits = jnp.einsum("bhd,bthd->bht", q, ck).astype(jnp.float32)
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    logits = apply_mask(logits, mask[:, None, :])
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bthd->bhd", probs, cv)


def paged_prefill_attention(q, k_pages, v_pages, page_table, positions, *,
                            k_scale=None, v_scale=None, impl: str = "gather",
                            compute_dtype: Any = None,
                            interpret: Optional[bool] = None):
    """Prefill-chunk attention: ``q [C, H, D]`` (one row's chunk),
    ``page_table [P]``, ``positions [C]`` absolute. Returns ``[C, H, D]``."""
    _check_impl(impl)
    compute_dtype = compute_dtype or q.dtype
    if impl == "kernel":
        out = _kernel_attention(q[None], k_pages, v_pages, page_table[None],
                                positions[None], k_scale, v_scale, interpret)
        return out[0]
    head_dim = q.shape[-1]
    timeline = page_table.shape[0] * k_pages.shape[1]
    ck = _gather_timeline(k_pages, k_scale, page_table, compute_dtype,
                          q.shape[-2])
    cv = _gather_timeline(v_pages, v_scale, page_table, compute_dtype,
                          q.shape[-2])
    mask = position_mask(timeline, positions)                     # [C, T]
    logits = jnp.einsum("chd,thd->hct", q, ck).astype(jnp.float32)
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    logits = apply_mask(logits, mask[None, :, :])
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("hct,thd->chd", probs, cv)


def paged_verify_attention(q, k_pages, v_pages, page_tables, rows_pos, *,
                           k_scale=None, v_scale=None, impl: str = "gather",
                           compute_dtype: Any = None,
                           interpret: Optional[bool] = None):
    """Spec-verify attention: ``q [B, K1, H, D]`` (pending token + K drafts
    per row), ``page_tables [B, P]``, ``rows_pos [B, K1]`` absolute query
    positions. Returns ``[B, K1, H, D]``."""
    _check_impl(impl)
    compute_dtype = compute_dtype or q.dtype
    if impl == "kernel":
        return _kernel_attention(q, k_pages, v_pages, page_tables, rows_pos,
                                 k_scale, v_scale, interpret)
    head_dim = q.shape[-1]
    timeline = page_tables.shape[1] * k_pages.shape[1]
    ck = _gather_timeline(k_pages, k_scale, page_tables, compute_dtype,
                          q.shape[-2])
    cv = _gather_timeline(v_pages, v_scale, page_tables, compute_dtype,
                          q.shape[-2])
    mask = position_mask(timeline, rows_pos)                      # [B, K1, T]
    logits = jnp.einsum("bqhd,bthd->bhqt", q, ck).astype(jnp.float32)
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    logits = apply_mask(logits, mask[:, None, :, :])
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bthd->bqhd", probs, cv)


# ------------------------------------------------ window ring + chunk summaries
# A cache whose timeline is not one page per ``page_len`` positions (EVA,
# arXiv 2302.04542): each row's table is ``[ring | summaries]``. The ring
# holds the exact keys of the open window (position ``p`` at slot
# ``p % window``), a summary page holds ``page_len`` chunk summaries
# (chunk ``j`` at row ``j``, one chunk = ``page_len`` positions). Pages are
# laid out heads-major, ``[n_pages, H, page_len, D]`` a layer, which is how
# the kernel below contracts them: no transpose in either program. This is a
# kernel of its own beside ``paged_attention`` (whose text, and so the
# programs of the models that call it, stay as they were): it masks two
# segments with their own valid counts under one softmax, walks several
# pages a grid step (8 for a decode step, 64 for a chunk of queries, which
# it takes a tile at a time), and multiplies bfloat16 operands.
def eva_entry_counts(positions, window: int, chunk: int):
    """``(exact, summaries)`` a query at ``positions`` sees: the keys of
    its own window up to itself, and every chunk of every earlier window."""
    return positions % window + 1, (positions // window) * (window // chunk)


def chunk_summaries(k, v, mu, phi):
    """One summary pair per chunk: ``k, v [..., H, c, D]`` (a chunk's
    rotated keys and its values, as a page holds them), ``mu, phi [H, D]``.
    ``k~ = sum_n softmax_n(s k_n.mu) k_n``, ``v~ = sum_n softmax_n(s
    k_n.phi) v_n``, both softmaxes and sums in float32. Returns float32
    ``[..., H, D]`` twice."""
    hi = jax.lax.Precision.HIGHEST
    scale = k.shape[-1] ** -0.5
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def pool(x, w):
        logits = jnp.einsum("...hnd,hd->...hn", k32, w.astype(jnp.float32),
                            precision=hi) * scale
        return jnp.einsum("...hn,...hnd->...hd",
                          jax.nn.softmax(logits, axis=-1), x, precision=hi)

    return pool(k32, mu), pool(v32, phi)


def _eva_limits(q_positions, ring_pages: int, page_len: int, window: int):
    """Per query, in table-entry order (entry ``t`` of page ``e`` is
    ``e * page_len + t``): the first masked ring entry and the first masked
    summary entry."""
    exact, n_sum = eva_entry_counts(q_positions, window, page_len)
    return exact, n_sum + ring_pages * page_len


def _eva_gather_attention(q4, k_pages, v_pages, page_tables, q_positions,
                          ring_pages, window):
    """The materialize-then-attend rendering: ``q4 [B, H, Q, D]``."""
    b, h, n_q, d = q4.shape
    page_len = k_pages.shape[2]
    n_tables = page_tables.shape[1]

    def timeline(pages):
        g = pages[page_tables]                      # [B, P, H, L, D]
        return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(
            b, h, n_tables * page_len, d)

    lim_ring, lim_sum = _eva_limits(q_positions, ring_pages, page_len, window)
    t = jnp.arange(n_tables * page_len)
    is_ring = t < ring_pages * page_len
    admit = jnp.where(is_ring, t < lim_ring[..., None], t < lim_sum[..., None])
    ck = timeline(k_pages).astype(q4.dtype)
    # An entry no query of the row sees may hold anything (a recycled page,
    # a summary whose window is open): 0 x NaN must not reach the sum.
    seen = admit.any(axis=1)[:, None, :, None]                    # [B,1,T,1]
    cv = jnp.where(seen, timeline(v_pages), 0).astype(q4.dtype)
    logits = jnp.einsum("bhqd,bhtd->bhqt", q4, ck,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    logits = apply_mask(logits, admit[:, None])
    probs = jax.nn.softmax(logits, axis=-1).astype(q4.dtype)
    return jnp.einsum("bhqt,bhtd->bhqd", probs, cv,
                      preferred_element_type=jnp.float32).astype(q4.dtype)


def _eva_kernel(tables_ref, ring_max_ref, sum_max_ref, tile_lo_ring_ref,
                tile_hi_ring_ref, tile_lo_sum_ref, tile_hi_sum_ref,
                lim_ring_ref, lim_sum_ref, q_ref, *rest, page_len: int,
                ring_pages: int, group: int, n_groups: int, q_tile: int,
                scale: float):
    """One (row, head block, page group) program. The page groups of a row
    run in order, ring first, and carry float32 (m, l, acc) in VMEM. A
    group past every query's valid count is skipped whole: its index map
    points at the scratch page and nothing is computed. Inside a group the
    queries go a tile of ``q_tile`` at a time: a tile none of whose queries
    reaches the group is skipped (a chunk's early queries against the
    ring's later keys), and only a tile the counts cut through builds a
    mask."""
    k_refs, v_refs = rest[:group], rest[group:2 * group]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * group:]
    bi, gi = pl.program_id(0), pl.program_id(2)

    @pl.when(gi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    span = group * page_len
    base = gi * span
    is_ring = gi * group < ring_pages
    # ring entry 0 is every query's (its own window starts there), so the
    # first group is live for every tile and seeds ``m`` with a finite logit
    reach = jnp.where(is_ring, ring_max_ref[bi], sum_max_ref[bi])

    @pl.when(base < reach)
    def _attend():
        k = jnp.concatenate([r[0] for r in k_refs], axis=1)  # [Hb, T, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        t_col = base + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        v = jnp.where((t_col < reach)[None], v, jnp.zeros_like(v))

        def tile(rows, masked):
            q = q_ref[0, :, rows, :]                         # [Hb, Tq, D]
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale  # [Hb, Tq, T]
            if masked:
                t_abs = base + jax.lax.broadcasted_iota(
                    jnp.int32, (q_tile, span), 1)
                limit = jnp.where(is_ring, lim_ring_ref[0, rows],
                                  lim_sum_ref[0, rows])      # [Tq, 1]
                s = jnp.where((t_abs < limit)[None], s, NEG_INF)
            m = m_ref[:, rows]                               # [Hb, Tq, 1]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[:, rows] = alpha * l_ref[:, rows] + pexp.sum(
                axis=-1, keepdims=True)
            acc_ref[:, rows] = acc_ref[:, rows] * alpha + jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)          # [Hb, Tq, D]
            m_ref[:, rows] = m_new

        for j in range(q_ref.shape[2] // q_tile):
            rows = slice(j * q_tile, (j + 1) * q_tile)
            lo = jnp.where(is_ring, tile_lo_ring_ref[bi, j],
                           tile_lo_sum_ref[bi, j])
            hi = jnp.where(is_ring, tile_hi_ring_ref[bi, j],
                           tile_hi_sum_ref[bi, j])
            whole = base + span <= lo
            pl.when(whole)(functools.partial(tile, rows, False))
            pl.when((base < hi) & jnp.logical_not(whole))(
                functools.partial(tile, rows, True))

    @pl.when(gi == n_groups - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _eva_blocking(h: int, n_q: int, d: int, n_tables: int, ring_pages: int,
                  page_len: int):
    """``(pages a grid step, heads a grid step, queries a tile)``. A step
    walks as many pages as divide both segments, up to 128 entries for a
    handful of queries (one MXU tile of keys) and up to 1,024 for a chunk
    of them: the running maximum, the sum and the accumulator's rescaling
    are paid once a step, and with them a chunk's attention costs 0.57 ms
    for every 128 entries it sees at 128 a step, 0.40 at 512 and 0.34 at
    1,024 (16 calls at 32 heads of 128 on one v5e; PERF.md section 6, PR
    30). Queries go in tiles of up to 256, and a step takes as many heads
    as keep a tile's float32 scores and the accumulator within 4 MB each
    (two heads a step read a third slower than four)."""
    keys = 128 if n_q <= 16 else 1024
    group = math.gcd(math.gcd(ring_pages, n_tables - ring_pages),
                     max(1, keys // page_len))
    q_tile = math.gcd(n_q, 256)
    hb = h
    while hb % 2 == 0 and max(
            hb * q_tile * group * page_len, hb * n_q * max(d, 128)) * 4 > (4 << 20):
        hb //= 2
    return group, hb, q_tile


def _eva_kernel_attention(q4, k_pages, v_pages, page_tables, q_positions,
                          ring_pages, window, interpret):
    if interpret is None:
        interpret = _should_interpret()
    b, h, n_q, d = q4.shape
    page_len = k_pages.shape[2]
    n_tables = page_tables.shape[1]
    group, hb, q_tile = _eva_blocking(
        h, n_q, d, n_tables, ring_pages, page_len)
    n_groups = n_tables // group
    lim_ring, lim_sum = _eva_limits(q_positions.astype(jnp.int32),
                                    ring_pages, page_len, window)
    ring_max = lim_ring.max(axis=1)
    sum_max = lim_sum.max(axis=1)
    # per tile of queries, the least and the largest count in each segment
    ring_t = lim_ring.reshape(b, n_q // q_tile, q_tile)
    sum_t = lim_sum.reshape(b, n_q // q_tile, q_tile)
    tile_lims = (ring_t.min(axis=2), ring_t.max(axis=2),
                 sum_t.min(axis=2), sum_t.max(axis=2))

    def page_spec(j):
        def index(bi, hi, gi, t, rmax, smax, *_):
            e = gi * group + j
            reach = jnp.where(e < ring_pages, rmax[bi], smax[bi])
            return (jnp.where(e * page_len < reach, t[bi, e], 0), hi, 0, 0)

        return pl.BlockSpec((1, hb, page_len, d), index)

    lim_spec = pl.BlockSpec((1, n_q, 1), lambda bi, hi, gi, *_: (bi, 0, 0))
    q_spec = pl.BlockSpec((1, hb, n_q, d), lambda bi, hi, gi, *_: (bi, hi, 0, 0))
    pages = [page_spec(j) for j in range(group)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(b, h // hb, n_groups),
        in_specs=[lim_spec, lim_spec, q_spec] + pages + pages,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, n_q, 1), jnp.float32),   # m
            pltpu.VMEM((hb, n_q, 1), jnp.float32),   # l
            pltpu.VMEM((hb, n_q, d), jnp.float32),   # acc
        ],
    )
    kernel = functools.partial(
        _eva_kernel, page_len=page_len, ring_pages=ring_pages, group=group,
        n_groups=n_groups, q_tile=q_tile, scale=d ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, n_q, d), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="eva_paged_attention",
    )(page_tables.astype(jnp.int32), ring_max, sum_max, *tile_lims,
      lim_ring[..., None], lim_sum[..., None], q4,
      *([k_pages] * group), *([v_pages] * group))


def eva_paged_attention(q, k_pages, v_pages, page_tables, q_positions, *,
                        ring_pages: int, window: int, impl: str = "gather",
                        interpret: Optional[bool] = None):
    """Attention over a window ring and chunk summaries, one softmax:
    ``q [B, H, Q, D]`` (``Q`` 1 for a decode step over ``B`` rows; a
    prefill chunk is ``B`` 1 with ``Q`` queries of one window),
    ``k_pages, v_pages [n_pages, H, page_len, D]`` one layer's pool,
    ``page_tables [B, ring_pages + summary pages]``, ``q_positions [B, Q]``
    absolute. A query at ``p`` sees ring entries ``0..p % window`` and the
    first ``(p // window) * (window // page_len)`` summary rows; what lies
    past a row's counts is never read into the sum, whatever it holds.
    Returns ``[B, H, Q, D]`` in the query's type."""
    _check_impl(impl)
    if impl == "kernel":
        return _eva_kernel_attention(q, k_pages, v_pages, page_tables,
                                     q_positions, ring_pages, window,
                                     interpret)
    return _eva_gather_attention(q, k_pages, v_pages, page_tables,
                                 q_positions, ring_pages, window)


# ------------------------------------------------ latent pages, no head axis
# A cache whose page holds one row a position for all heads (latent
# attention: DeepSeek-V2, arXiv 2405.04434): ``[n_pages, page_len, W]`` a
# layer, the first ``value_width`` columns the normalised latent (key and
# value alike, through two per-head projections), then one rotated key
# shared by every head, then zeros up to whole lane tiles (a query's columns
# there are zero too). Two paths over the one pool. A decode step absorbs
# the projections into the query and the output and attends in the latent
# space, every query head against the one shared ``W``-wide key: the kernel
# below, the opposite shape of ``paged_attention`` (many query heads of one
# position a row, one key head, one pool that is key and value at once and
# is read once for both). A chunk of queries expands the latents of the keys
# it sees into per-head keys and values, a block of positions at a time.
def latent_blocking(n_tables: int, page_len: int, keys: int = 1024) -> int:
    """Pages a grid step of ``mla_paged_attention``: the largest divisor of
    the table width that keeps a step at or under ``keys`` positions."""
    most = max(1, min(keys // page_len, n_tables))
    return max(g for g in range(1, most + 1) if n_tables % g == 0)


def _mla_gather_attention(q, pages, page_tables, positions, value_width,
                          scale):
    lat = _paged_gather(pages, page_tables)                     # [B, T, W]
    logits = jnp.einsum("bhw,btw->bht", q, lat,
                        preferred_element_type=jnp.float32) * scale
    mask = position_mask(lat.shape[1], positions)               # [B, T]
    probs = jax.nn.softmax(apply_mask(logits, mask[:, None, :]), axis=-1)
    return jnp.einsum("bht,btc->bhc", probs.astype(q.dtype),
                      lat[..., :value_width],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _mla_kernel(tables_ref, reach_ref, q_ref, *rest, page_len: int,
                group: int, value_width: int, scale: float):
    """One (row, page group) program: the row's latent pages ``group`` at a
    time, each read once and used as key (all ``W`` columns) and as value
    (the first ``value_width``); online softmax a group in float32. Groups
    past the row's position are skipped whole and their index maps return
    the last live group's pages, so no copy is issued; the grid ends at the
    call's last live group (``_paged_kernel`` has the same two devices)."""
    page_refs, (o_ref, m_ref, l_ref, acc_ref) = rest[:group], rest[group:]
    bi, gi = pl.program_id(0), pl.program_id(1)
    span = group * page_len
    heads = q_ref.shape[1]

    @pl.when(gi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(gi * span < reach_ref[bi])
    def _attend():
        kv = jnp.concatenate([r[0] for r in page_refs], axis=0)  # [T, W]
        s = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale          # [H, T]
        t_abs = gi * span + jax.lax.broadcasted_iota(
            jnp.int32, (heads, span), 1)
        s = jnp.where(t_abs < reach_ref[bi], s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = alpha * l_ref[...] + pexp.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pexp.astype(kv.dtype), kv[:, :value_width],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(gi == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "value_width", "scale", "keys", "interpret"))
def _mla_kernel_call(q, pages, page_tables, positions, *, value_width: int,
                     scale: float, keys: int, interpret: bool):
    b, h, w = q.shape
    page_len = pages.shape[1]
    n_tables = page_tables.shape[1]
    group = latent_blocking(n_tables, page_len, keys)
    span = group * page_len
    reach = positions.astype(jnp.int32) + 1                       # [B]

    def page_index(j):
        def index(bi, gi, t, reach):
            last = jax.lax.div(reach[bi] - 1, span)
            return (t[bi, jnp.minimum(gi, last) * group + j], 0, 0)
        return index

    n_live = jnp.minimum(n_tables // group,
                         jax.lax.div(reach.max() + span - 1, span))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_live),
        in_specs=[pl.BlockSpec((1, h, w), lambda bi, gi, *_: (bi, 0, 0))]
        + [pl.BlockSpec((1, page_len, w), page_index(j)) for j in range(group)],
        out_specs=pl.BlockSpec((1, h, value_width),
                               lambda bi, gi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),                  # m
            pltpu.VMEM((h, 1), jnp.float32),                  # l
            pltpu.VMEM((h, value_width), jnp.float32),        # acc
        ],
    )
    kernel = functools.partial(_mla_kernel, page_len=page_len, group=group,
                               value_width=value_width, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_paged_attention",
    )(page_tables.astype(jnp.int32), reach, q, *([pages] * group))


def mla_paged_decode_attention(q, pages, page_tables, positions, *,
                               value_width: int, scale: float,
                               impl: str = "gather", keys: int = 1024,
                               interpret: Optional[bool] = None):
    """Decode-step attention in the latent space: ``q [B, H, W]`` (each
    head's query with the key projection absorbed, then its rotated part),
    ``pages [n_pages, page_len, W]`` one layer's pool, ``page_tables [B,
    P]``, ``positions [B]``. Returns ``[B, H, value_width]``: per head the
    softmax-weighted sum of the latents, for the value projection to take
    out of the latent space. ``scale`` multiplies the float32 scores."""
    _check_impl(impl)
    if impl == "kernel":
        if interpret is None:
            interpret = _should_interpret()
        return _mla_kernel_call(q, pages, page_tables, positions,
                                value_width=value_width, scale=float(scale),
                                keys=keys, interpret=interpret)
    return _mla_gather_attention(q, pages, page_tables, positions,
                                 value_width, scale)


def mla_paged_prefill_attention(q_nope, q_rope, pages, page_table, positions,
                                w_k, w_v, *, rope_width: int, scale: float,
                                block: int = 512):
    """A chunk's attention with the latents expanded: ``q_nope [C, H, dn]``,
    ``q_rope [C, H, dr]`` (rotated) at absolute ``positions [C]``; ``pages
    [n_pages, page_len, W]`` one layer's pool (``W >= Ckv + dr``: the latent,
    the rotated key of ``rope_width`` columns, zeros) through ``page_table
    [P]`` (the chunk's own rows written already); ``w_k [Ckv, H, dn]``,
    ``w_v [Ckv, H, dv]`` the two halves of the latent's up-projection. A
    block of ``block`` positions at a time the latents become per-head keys
    and values and meet the queries under one online softmax (float32);
    blocks past the chunk's last position are not walked, so a chunk pays
    for the context it has. Returns ``[C, H, dv]`` in the queries' type."""
    c, h, _ = q_nope.shape
    page_len = pages.shape[1]
    n_tables = page_table.shape[0]
    ckv = w_k.shape[0]
    group = latent_blocking(n_tables, page_len, block)
    span = group * page_len
    dt = q_nope.dtype
    pos = positions.astype(jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(page_table, j * group, group)
        lat = pages[ids].reshape(span, -1).astype(dt)             # [T, W]
        k_nope = jnp.einsum("tc,chd->thd", lat[:, :ckv], w_k.astype(dt),
                            preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("tc,chd->thd", lat[:, :ckv], w_v.astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        s = (jnp.einsum("qhd,thd->hqt", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhr,tr->hqt", q_rope, lat[:, ckv:ckv + rope_width],
                          preferred_element_type=jnp.float32)) * scale
        t_abs = j * span + jnp.arange(span, dtype=jnp.int32)
        s = jnp.where(t_abs[None, None, :] <= pos[None, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        pexp = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + pexp.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hqt,thd->hqd", pexp.astype(dt), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    n_live = jnp.minimum(n_tables // group,
                         jax.lax.div(pos.max() + span, span))
    init = (jnp.full((h, c), NEG_INF, jnp.float32),
            jnp.zeros((h, c), jnp.float32),
            jnp.zeros((h, c, w_v.shape[2]), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_live, body, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(1, 0, 2).astype(dt)
