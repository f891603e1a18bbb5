"""Flash attention for TPU: pallas forward + backward kernels, custom VJP.

Online-softmax attention (Dao et al., arXiv 2205.14135) laid out for the TPU
memory hierarchy: queries stream through VMEM in blocks, K/V live in VMEM per
(batch*head) slice, the softmax accumulators stay fp32 while matmuls hit the
MXU in the input dtype. Backward is the standard two-kernel scheme (dkdv
gridded over K blocks, dq over Q blocks) with the forward logsumexp saved as
residual.

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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


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


# ------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, scale: float):
    """One (batch*head, q-block) program: stream K/V blocks, online softmax.

    MXU work stays in the input dtype (bf16 in, fp32 accumulate via
    preferred_element_type); only the softmax stats are fp32. Stats are kept
    [bq, 1]-shaped — 1D vectors force Mosaic relayouts.
    """
    q = q_ref[0]                                       # [bq, d], input dtype
    block_q, head_dim = q.shape
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    qi = pl.program_id(1)
    q_start = qi * block_q

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k_start = kb * block_k
        kblk = k_ref[0, pl.ds(k_start, block_k), :]
        vblk = v_ref[0, pl.ds(k_start, block_k), :]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [bq, bk] fp32
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # Only blocks intersecting the causal triangle: k_start <= q_end.
        last_kb = (q_start + block_q - 1) // block_k + 1
    else:
        last_kb = num_kb
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


# ------------------------------------------------------------------ backward
def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, *, block_q: int, causal: bool, scale: float):
    """One (batch*head, k-block) program: accumulate dK, dV over Q blocks."""
    kblk = k_ref[0].astype(jnp.float32)               # [bk, d]
    vblk = v_ref[0].astype(jnp.float32)
    block_k, head_dim = kblk.shape
    seq_q = q_ref.shape[1]
    num_qb = seq_q // block_q
    ki = pl.program_id(1)
    k_start = ki * block_k

    dk0 = jnp.zeros((block_k, head_dim), jnp.float32)
    dv0 = jnp.zeros((block_k, head_dim), jnp.float32)

    def body(qb, carry):
        dk, dv = carry
        q_start = qb * block_q
        q = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(q_start, block_q), 0]
        delta = delta_ref[0, pl.ds(q_start, block_q), 0]
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # [bq, bk]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [bq, bk]
        ds = p * (dp - delta[:, None])
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    if causal:
        first_qb = k_start // block_q
    else:
        first_qb = 0
    dk, dv = jax.lax.fori_loop(first_qb, num_qb, body, (dk0, dv0))
    # q rows were pre-scaled, so dk = ds^T @ (q*scale) is already dL/dK.
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, block_k: int, causal: bool, scale: float):
    """One (batch*head, q-block) program: accumulate dQ over K blocks."""
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    block_q, head_dim = q.shape
    seq_k = k_ref.shape[1]
    num_kb = seq_k // block_k
    qi = pl.program_id(1)
    q_start = qi * block_q

    dq0 = jnp.zeros((block_q, head_dim), jnp.float32)

    def body(kb, dq):
        k_start = kb * block_k
        kblk = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    last_kb = ((q_start + block_q - 1) // block_k + 1) if causal else num_kb
    dq = jax.lax.fori_loop(0, last_kb, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


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
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
):
    """Flash attention, [B, S, H, D] in/out. Differentiable (custom VJP)."""
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _pallas_forward(q3, k3, v3, causal, block_q, block_k, interpret):
    bh, seq_q, head_dim = q3.shape
    seq_k = k3.shape[1]
    scale = 1.0 / (head_dim ** 0.5)
    grid = (bh, seq_q // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=block_k, causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, head_dim), q3.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out, lse


def kernel_supports(seq_q: int, seq_k: int,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> bool:
    """True when the pallas kernels take this shape: equal, block-aligned
    sequence lengths (the TPU tile constraint is last-two block dims
    divisible by (8, 128) or equal to the array dims; checking against the
    *uncapped* 128 keeps odd lengths off the kernel path entirely)."""
    return (
        seq_q == seq_k
        and seq_q % 128 == 0
        and seq_q % min(block_q, seq_q) == 0
        and seq_k % min(block_k, seq_k) == 0
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


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    if interpret is None:
        interpret = _should_interpret()
    b, s, h, d = q.shape
    if not kernel_supports(s, k.shape[1], block_q, block_k):
        _log_fallback_once(s, k.shape[1])
        out = mha_reference(q, k, v, causal)
        return out, (q, k, v, out, None)
    block_q = min(block_q, s)
    block_k = min(block_k, k.shape[1])
    q3, k3, v3 = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    out3, lse = _pallas_forward(q3, k3, v3, causal, block_q, block_k, interpret)
    return _unfold_heads(out3, b, h), (q, k, v, _unfold_heads(out3, b, h), lse)


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
    bq = min(block_q, s)
    bk = min(block_k, s)
    scale = 1.0 / (d ** 0.5)
    q3, k3, v3 = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    o3, do3 = _fold_heads(out), _fold_heads(g)
    bh, seq, _ = q3.shape
    # delta = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it.
    delta = (o3.astype(jnp.float32) * do3.astype(jnp.float32)).sum(-1)[..., None]

    dk3, dv3 = pl.pallas_call(
        functools.partial(_dkdv_kernel, block_q=bq, causal=causal, scale=scale),
        grid=(bh, seq // bk),
        in_specs=[
            pl.BlockSpec((1, seq, d), lambda b_, i: (b_, 0, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b_, i: (b_, i, 0)),    # k block
            pl.BlockSpec((1, bk, d), lambda b_, i: (b_, i, 0)),    # v block
            pl.BlockSpec((1, seq, d), lambda b_, i: (b_, 0, 0)),   # do
            pl.BlockSpec((1, seq, 1), lambda b_, i: (b_, 0, 0)),   # lse
            pl.BlockSpec((1, seq, 1), lambda b_, i: (b_, 0, 0)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b_, i: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)

    dq3 = pl.pallas_call(
        functools.partial(_dq_kernel, block_k=bk, causal=causal, scale=scale),
        grid=(bh, seq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0)),    # q block
            pl.BlockSpec((1, seq, d), lambda b_, i: (b_, 0, 0)),   # k
            pl.BlockSpec((1, seq, d), lambda b_, i: (b_, 0, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0)),    # do block
            pl.BlockSpec((1, bq, 1), lambda b_, i: (b_, i, 0)),    # lse block
            pl.BlockSpec((1, bq, 1), lambda b_, i: (b_, i, 0)),    # delta block
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b_, i: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)

    return (
        _unfold_heads(dq3, b, h),
        _unfold_heads(dk3, b, h),
        _unfold_heads(dv3, b, h),
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)
