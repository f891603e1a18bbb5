"""Operations and bytes that the model *requires*, from its shapes.

Counts of the work the mathematics needs, not of what an implementation
does: causal attention at half of the full square, no recomputation, the
head once where only one position's logits are needed. One multiply-add is
two operations. ``model`` is a configuration file's dict (``n_layer``,
``n_embd``, ``n_head``, ``n_inner``, ``vocab_size``).
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product for every token, the
    tied head left out: 4 d^2 + 2 d f a layer."""
    d, f = model["n_embd"], model["n_inner"]
    return model["n_layer"] * (4 * d * d + 2 * d * f)


def head_flops(model: dict) -> int:
    """One position's logits: d x V multiply-adds."""
    return 2 * model["n_embd"] * model["vocab_size"]


def attention_flops(model: dict, pairs: int) -> int:
    """QK^T and PV over all layers for ``pairs`` query-key pairs (each
    pair: 2 d operations in each of the two products)."""
    return model["n_layer"] * 4 * model["n_embd"] * pairs


def causal_pairs(seq: int) -> int:
    """Query-key pairs under a causal mask over ``seq`` positions."""
    return seq * (seq + 1) // 2


def forward_flops_sequence(model: dict, seq: int, head_positions: int) -> int:
    """Forward pass over one sequence of ``seq`` tokens, logits taken at
    ``head_positions`` of them."""
    return (2 * matmul_params(model) * seq
            + attention_flops(model, causal_pairs(seq))
            + head_flops(model) * head_positions)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward (twice the forward) per trained token, for
    sequences of ``seq`` with a prediction at every position but the last."""
    return 3.0 * forward_flops_sequence(model, seq, seq - 1) / seq


def prefill_flops(model: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens up to its first generated token: only
    the last position needs logits."""
    return forward_flops_sequence(model, prompt, 1)


def decode_flops(model: dict, context: int) -> int:
    """One generated token whose query sees ``context`` keys."""
    return (2 * matmul_params(model) + attention_flops(model, context)
            + head_flops(model))


def decode_kv_bytes(model: dict, context: int, itemsize: int = 2) -> int:
    """Bytes of live keys and values one decode query has to read, over
    all layers (``itemsize`` 2: bfloat16 pages)."""
    return model["n_layer"] * 2 * context * model["n_embd"] * itemsize


def flash_flops(model: dict, rows: int, seq: int, backward: bool) -> int:
    """One layer's attention over ``rows`` sequences under the causal
    mask: two products forward, four backward (dV, dP, dQ, dK)."""
    per = 2 * model["n_embd"] * causal_pairs(seq) * rows
    return per * (4 if backward else 2)


def flash_bytes(model: dict, rows: int, seq: int, backward: bool,
                itemsize: int = 2) -> int:
    """q, k, v read and o written forward; those four, dO read and dq, dk,
    dv written backward."""
    tensor = rows * seq * model["n_embd"] * itemsize
    return tensor * (8 if backward else 4)


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
