"""Flash attention for TPU: pallas forward + backward kernels, custom VJP.

Online-softmax attention (Dao et al., arXiv 2205.14135) laid out for the TPU
memory hierarchy. Backward is the standard two-kernel scheme (dK/dV gridded
over K blocks, dQ over Q blocks) with the forward logsumexp saved as residual.

What enters the MXU, and in which dtype. All seven products of the three
kernels take their operands in the dtype the inputs arrive in (bfloat16 in a
bfloat16 model, float32 in the CPU tests) and give a float32 result
(``preferred_element_type``): forward ``q k^T`` and ``p v``; dK/dV ``k q^T``,
``p^T dO``, ``v dO^T``, ``ds^T q``; dQ ``q k^T``, ``dO v^T``, ``ds k``. ``p``
and ``ds`` are computed in float32 and cast to the input dtype just before
their products. What stays float32: the scale (applied to the float32
scores, and once to the finished dK and dQ), the running max and sum, ``exp``,
``ds = p * (dp - delta)``, every accumulator, ``lse`` and ``delta``.

The causal mask is built only in the tiles the diagonal crosses: each kernel
runs one loop over the tiles wholly below it with a body that has no iota,
compare or select, and one over the crossed tiles with a body that has; tiles
wholly above are never visited.

``lse`` and ``delta`` are [batch*heads, 1, seq], the sequence along the lanes,
read and written as [1, block] rows. dK/dV computes its tiles transposed
([block_k, block_q]) so that a row broadcasts along the sublanes as it is;
the forward and dQ, whose statistics are per query row, turn a row into a
column (or back) once per head and grid step, outside the tile loop.

Tiles and grid come from the shape (:func:`_tiles`, :func:`_heads_per_step`):
square tiles, the largest 128-multiple up to 512 that divides the sequence
and fits VMEM beside the operands in their dtype, and as many heads a grid
step as make about 1,024 rows of work. An explicit ``block_q`` / ``block_k``
from a caller is honoured as it is.

Layout contract: q, k, v are [batch, seq, heads, head_dim] (the transformer's
natural shape); internally folded to [batch*heads, seq, head_dim].

On CPU the kernels run in pallas interpret mode (tests exercise the same
kernel logic). Direct callers with a sequence the kernel cannot take
(:func:`kernel_supports`) get the jnp reference instead, logged once on a
TPU backend; the transformer's explicit ``attention_impl="flash"`` raises
at trace time rather than fall back.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from autodist_tpu.utils import logging

_NEG_INF = -1e30
_LANES = 128


def mha_reference(q, k, v, causal: bool = False):
    """jnp reference implementation ([B,S,H,D] layout), fp32 softmax."""
    head_dim = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(head_dim).astype(jnp.float32)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------- tiles
#: What a Mosaic kernel may hold in VMEM unless it asks for more (the scoped
#: limit of a v5e; the same on the other generations this repo has met).
_VMEM_BYTES = 16 * 1024 * 1024
#: Largest tile edge. The MXU streams the rows of one operand past 128-wide
#: pieces of the other, and loading a piece costs about 128 rows' worth, so a
#: tile wants many rows; past 512 the part of a diagonal tile that lies above
#: the diagonal costs more than the longer streams save (chip sweep, PR 28).
_MAX_TILE = 512
#: Rows a grid step should take over all its heads, against the step's own
#: fixed cost (about a third of a microsecond).
_STEP_ROWS = 1024
#: Float32 copies of the score tile alive at once in a loop body
#: (s, p, dp, ds; the bfloat16 casts are half ones).
_LIVE_TILES = 4


def _step_bytes(heads: int, tile: int, seq: int, head_dim: int,
                itemsize: int) -> int:
    """VMEM one grid step needs: per head two whole-sequence operands and four
    blocks of ``tile`` rows (dK/dV, the widest: q and dO whole, k, v, dK, dV
    blocked), each double-buffered by the pipeline, plus the float32 tiles of
    one loop body."""
    operands = 2 * heads * (2 * seq + 4 * tile) * head_dim * itemsize
    return operands + _LIVE_TILES * tile * tile * 4


def _tiles(seq: int, head_dim: int, dtype) -> tuple:
    """(block_q, block_k) for a call that names none, from what the kernel can
    see: the largest 128-multiple that divides ``seq``, does not pass
    ``_MAX_TILE`` and, with one head's operands in this dtype beside it, fits
    the VMEM a kernel gets. Square: a tile the diagonal crosses is then
    crossed corner to corner."""
    itemsize = jnp.dtype(dtype).itemsize
    for tile in range(min(_MAX_TILE, seq) // _LANES * _LANES, _LANES, -_LANES):
        if seq % tile == 0 and _step_bytes(1, tile, seq, head_dim,
                                           itemsize) <= _VMEM_BYTES:
            return tile, tile
    return _LANES, _LANES


def _heads_per_step(bh: int, tile: int, seq: int, head_dim: int,
                    itemsize: int) -> int:
    """Heads one grid step takes: about ``_STEP_ROWS`` rows of work, no more
    than VMEM holds, and a divisor of ``bh``."""
    fits = [g for g in range(1, max(1, _STEP_ROWS // tile) + 1)
            if bh % g == 0
            and _step_bytes(g, tile, seq, head_dim, itemsize) <= _VMEM_BYTES]
    return max(fits, default=1)


def _mm(a, b, contract):
    """One MXU product: operands as they are, float32 result."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b


def _turn(vec, n, axis):
    """A [1, n] row into an [n, 1] column (``axis=1``) or back (``axis=0``)
    without a relayout Mosaic may refuse: keep the diagonal of the vector's
    broadcast and sum along ``axis``. Once per grid step and head, never per
    tile."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.where(eye, vec, 0.0).sum(axis=axis, keepdims=True)


def _run_tiles(tile, carry, plain, masked):
    """``tile(i, carry, masked)`` over two index ranges: ``plain`` with a body
    that has no mask, ``masked`` (None when not causal) with one that has."""
    for bounds, is_masked in ((plain, False), (masked, True)):
        if bounds is not None:
            carry = jax.lax.fori_loop(
                *bounds, functools.partial(tile, masked=is_masked), carry)
    return carry


def _k_tiles_of_q_block(q_start, block_q: int, block_k: int, seq_k: int,
                        causal: bool):
    """For the forward and dQ, whose grid step owns a block of queries:
    ``(plain, crossed, diag)``. K tiles ``plain`` lie wholly below the
    diagonal, ``crossed`` (None when not causal) are crossed by it, the rest
    lie wholly above and are never visited; a crossed tile keeps
    ``diag >= k_start - q_start``, which is ``q_pos >= k_pos``."""
    if not causal:
        return (0, seq_k // block_k), None, None
    n_full = (q_start + 1) // block_k
    diag = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
    return ((0, n_full), (n_full, (q_start + block_q - 1) // block_k + 1), diag)


# ------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float):
    """One (group of heads, q-block) program: stream K/V tiles, online softmax.

    MXU: ``q @ k.T`` and ``p @ v`` with the operands in the input dtype (``p``
    is cast to it just before its product), float32 results. VPU: the scale,
    the running max and sum, ``exp`` and the accumulator in float32. The
    causal mask is built only in the tiles the diagonal crosses; tiles wholly
    below it run a body without iota, compare and select. ``lse`` leaves as a
    [1, block_q] row of the lane-dense [bh, 1, seq] array.
    """
    heads, block_q, head_dim = q_ref.shape
    q_start = pl.program_id(1) * block_q
    plain, crossed, diag = _k_tiles_of_q_block(
        q_start, block_q, block_k, k_ref.shape[1], causal)

    def head(g, _):
        q = q_ref[g]                                   # [bq, d], input dtype

        def tile(kb, carry, masked):
            m, l, acc = carry
            k_start = pl.multiple_of(kb * block_k, block_k)
            kblk = k_ref[g, pl.ds(k_start, block_k), :]
            vblk = v_ref[g, pl.ds(k_start, block_k), :]
            s = _mm(q, kblk, _NT) * scale              # [bq, bk] fp32
            if masked:
                s = jnp.where(diag >= k_start - q_start, s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(axis=1, keepdims=True)
            acc_new = acc * alpha + _mm(p.astype(vblk.dtype), vblk, _NN)
            return m_new, l_new, acc_new

        carry = (jnp.full((block_q, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((block_q, 1), jnp.float32),
                 jnp.zeros((block_q, head_dim), jnp.float32))
        m, l, acc = _run_tiles(tile, carry, plain, crossed)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[g] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[g] = _turn(m + jnp.log(l_safe), block_q, axis=0)
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


# ------------------------------------------------------------------ backward
def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float):
    """One (group of heads, k-block) program: accumulate dK, dV over Q tiles.

    Everything is computed transposed, [block_k, block_q], so that ``lse`` and
    ``delta`` are read as the [1, block_q] rows they are stored as and no
    product needs a transposed tile: ``k @ q.T``, ``v @ do.T``, ``p.T @ do``,
    ``ds.T @ q``. MXU operands in the input dtype (``p`` and ``ds`` cast just
    before their products), float32 results; ``exp``, ``ds = p * (dp - delta)``
    and the two accumulators float32; the scale goes on the float32 ``s`` and
    once on the finished ``dk``.
    """
    heads, block_k, head_dim = k_ref.shape
    seq_q = q_ref.shape[1]
    num_qb = seq_q // block_q
    k_start = pl.program_id(1) * block_k
    if causal:
        # Q tiles before k_start // block_q lie wholly above the diagonal and
        # are never visited; up to first_full the diagonal crosses them; from
        # there on they lie wholly below it.
        first_full = jnp.minimum(
            (k_start + block_k - 1 + block_q - 1) // block_q, num_qb)
        crossed = (k_start // block_q, first_full)
        # q_pos >= k_pos  <=>  col - row >= k_start - q_start
        diag = (jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
                - jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0))
    else:
        first_full, crossed = 0, None

    def head(g, _):
        kblk = k_ref[g]                                # [bk, d], input dtype
        vblk = v_ref[g]

        def tile(qb, carry, masked):
            dk, dv = carry
            q_start = pl.multiple_of(qb * block_q, block_q)
            q = q_ref[g, pl.ds(q_start, block_q), :]
            do = do_ref[g, pl.ds(q_start, block_q), :]
            lse = lse_ref[g, :, pl.ds(q_start, block_q)]      # [1, bq]
            delta = delta_ref[g, :, pl.ds(q_start, block_q)]
            st = _mm(kblk, q, _NT) * scale             # [bk, bq] fp32
            if masked:
                st = jnp.where(diag >= k_start - q_start, st, _NEG_INF)
            pt = jnp.exp(st - lse)
            dv = dv + _mm(pt.astype(do.dtype), do, _NN)
            dpt = _mm(vblk, do, _NT)                   # [bk, bq]
            dst = pt * (dpt - delta)
            dk = dk + _mm(dst.astype(q.dtype), q, _NN)
            return dk, dv

        zero = jnp.zeros((block_k, head_dim), jnp.float32)
        dk, dv = _run_tiles(tile, (zero, zero), (first_full, num_qb), crossed)
        dk_ref[g] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, block_k: int, causal: bool, scale: float):
    """One (group of heads, q-block) program: accumulate dQ over K tiles.

    ``q @ k.T``, ``do @ v.T`` and ``ds @ k`` with operands in the input dtype
    (``ds`` cast just before its product), float32 results; ``lse`` and
    ``delta`` arrive as [1, block_q] rows and are turned into columns once per
    head, outside the tile loop.
    """
    heads, block_q, head_dim = q_ref.shape
    q_start = pl.program_id(1) * block_q
    plain, crossed, diag = _k_tiles_of_q_block(
        q_start, block_q, block_k, k_ref.shape[1], causal)

    def head(g, _):
        q = q_ref[g]
        do = do_ref[g]
        lse = _turn(lse_ref[g], block_q, axis=1)       # [bq, 1]
        delta = _turn(delta_ref[g], block_q, axis=1)

        def tile(kb, dq, masked):
            k_start = pl.multiple_of(kb * block_k, block_k)
            kblk = k_ref[g, pl.ds(k_start, block_k), :]
            vblk = v_ref[g, pl.ds(k_start, block_k), :]
            s = _mm(q, kblk, _NT) * scale
            if masked:
                s = jnp.where(diag >= k_start - q_start, s, _NEG_INF)
            p = jnp.exp(s - lse)
            dp = _mm(do, vblk, _NT)
            ds = p * (dp - delta)
            return dq + _mm(ds.astype(kblk.dtype), kblk, _NN)

        dq = _run_tiles(tile, jnp.zeros((block_q, head_dim), jnp.float32),
                        plain, crossed)
        dq_ref[g] = (dq * scale).astype(dq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, heads, head, 0)


# ---------------------------------------------------------------- dispatcher
def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _fold_heads(x):
    # [b, s, h, d] -> [b*h, s, d]
    b, s, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)


def _unfold_heads(x, b, h):
    bh, s, d = x.shape
    return jnp.transpose(x.reshape(b, h, s, d), (0, 2, 1, 3))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention(
    q, k, v,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Flash attention, [B, S, H, D] in/out. Differentiable (custom VJP).

    ``block_q`` / ``block_k`` left at None are chosen from the shape
    (:func:`_tiles`); a caller's explicit value is honoured as it is.
    """
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _specs(heads, d, *kinds):
    """BlockSpecs of a (group of heads, block) grid, by kind: ``("blk", n)``
    an [n, d] block that follows the grid, ``("seq", n)`` the whole [n, d]
    sequence of the group, ``("stat_blk", n)`` / ``("stat_seq", n)`` the same
    two for a [1, n] row of lse or delta."""
    table = {
        "blk": lambda n: pl.BlockSpec((heads, n, d), lambda b, i: (b, i, 0)),
        "seq": lambda n: pl.BlockSpec((heads, n, d), lambda b, i: (b, 0, 0)),
        "stat_blk": lambda n: pl.BlockSpec((heads, 1, n), lambda b, i: (b, 0, i)),
        "stat_seq": lambda n: pl.BlockSpec((heads, 1, n), lambda b, i: (b, 0, 0)),
    }
    return [table[kind](n) for kind, n in kinds]


# The three calls are jitted on their own so that a model's layers share one
# trace of each kernel and one lowering to Mosaic: traced inline, 24 layers
# cost the train cell 6 s of set-up (chip runs, PR 28).
@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _pallas_forward(q3, k3, v3, causal, block_q, block_k, interpret):
    bh, seq, d = q3.shape
    heads = _heads_per_step(bh, block_q, seq, d, q3.dtype.itemsize)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, causal=causal,
                          scale=1.0 / (d ** 0.5)),
        grid=(bh // heads, seq // block_q),
        in_specs=_specs(heads, d, ("blk", block_q), ("seq", seq), ("seq", seq)),
        out_specs=_specs(heads, d, ("blk", block_q), ("stat_blk", block_q)),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out, lse


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _pallas_dkdv(q3, k3, v3, do3, lse, delta, causal, block_q, block_k,
                 interpret):
    bh, seq, d = q3.shape
    heads = _heads_per_step(bh, block_k, seq, d, q3.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_dkdv_kernel, block_q=block_q, causal=causal,
                          scale=1.0 / (d ** 0.5)),
        grid=(bh // heads, seq // block_k),
        in_specs=_specs(heads, d, ("seq", seq), ("blk", block_k),
                        ("blk", block_k), ("seq", seq),
                        ("stat_seq", seq), ("stat_seq", seq)),
        out_specs=_specs(heads, d, ("blk", block_k), ("blk", block_k)),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v3.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def _pallas_dq(q3, k3, v3, do3, lse, delta, causal, block_q, block_k,
               interpret):
    bh, seq, d = q3.shape
    heads = _heads_per_step(bh, block_q, seq, d, q3.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_dq_kernel, block_k=block_k, causal=causal,
                          scale=1.0 / (d ** 0.5)),
        grid=(bh // heads, seq // block_q),
        in_specs=_specs(heads, d, ("blk", block_q), ("seq", seq), ("seq", seq),
                        ("blk", block_q), ("stat_blk", block_q),
                        ("stat_blk", block_q)),
        out_specs=_specs(heads, d, ("blk", block_q))[0],
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q3.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)


def kernel_supports(seq_q: int, seq_k: int,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> bool:
    """True when the pallas kernels take this shape: equal, 128-aligned
    sequence lengths (the TPU tile constraint is last-two block dims
    divisible by (8, 128) or equal to the array dims; checking against the
    *uncapped* 128 keeps odd lengths off the kernel path entirely) that a
    caller's explicit blocks, if any, divide. Derived blocks always do."""
    return (
        seq_q == seq_k
        and seq_q % _LANES == 0
        and all(b is None or seq_q % min(b, seq_q) == 0
                for b in (block_q, block_k))
    )


_fallback_logged = False


def _log_fallback_once(seq_q: int, seq_k: int) -> None:
    """A direct caller asked for the kernel and got the reference: on the
    device that is a different program than the one named, so say so."""
    global _fallback_logged
    if _fallback_logged or jax.default_backend() != "tpu":
        return
    _fallback_logged = True
    logging.warning(
        "flash_attention: seq_q=%d seq_k=%d is not kernel-aligned; running "
        "the jnp reference (O(s^2) logits) instead of the pallas kernel",
        seq_q, seq_k)


def _blocks(seq, head_dim, dtype, block_q, block_k):
    """The caller's blocks capped at the sequence, or the derived ones."""
    derived = _tiles(seq, head_dim, dtype)
    return tuple(d if b is None else min(b, seq)
                 for b, d in zip((block_q, block_k), derived))


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    if interpret is None:
        interpret = _should_interpret()
    b, s, h, d = q.shape
    if not kernel_supports(s, k.shape[1], block_q, block_k):
        _log_fallback_once(s, k.shape[1])
        out = mha_reference(q, k, v, causal)
        return out, (q, k, v, out, None)
    bq, bk = _blocks(s, d, q.dtype, block_q, block_k)
    q3, k3, v3 = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    out3, lse = _pallas_forward(q3, k3, v3, causal, bq, bk, interpret)
    out = _unfold_heads(out3, b, h)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = _should_interpret()
    if lse is None:
        # Reference fallback path: differentiate the reference impl.
        def ref(q_, k_, v_):
            return mha_reference(q_, k_, v_, causal)

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)

    b, s, h, d = q.shape
    bq, bk = _blocks(s, d, q.dtype, block_q, block_k)
    q3, k3, v3 = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    o3, do3 = _fold_heads(out), _fold_heads(g)
    # delta = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it; laid out
    # like lse, [bh, 1, seq], the sequence along the lanes.
    delta = (o3.astype(jnp.float32) * do3.astype(jnp.float32)).sum(-1)[:, None, :]

    dk3, dv3 = _pallas_dkdv(q3, k3, v3, do3, lse, delta, causal, bq, bk, interpret)
    dq3 = _pallas_dq(q3, k3, v3, do3, lse, delta, causal, bq, bk, interpret)
    return (
        _unfold_heads(dq3, b, h),
        _unfold_heads(dk3, b, h),
        _unfold_heads(dv3, b, h),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
