"""Operations and bytes against the chip's peaks.

What a model *requires* (its matrix products, its attention, its head; a
kernel's operations and bytes) is its family's to count:
``perfbench/families/<family>.py``. Here is what no family owns: the pairs
a causal mask leaves, and the least time the chip could take for a count.
One multiply-add is two operations.
"""
from __future__ import annotations


def causal_pairs(seq: int) -> int:
    """Query-key pairs under a causal mask over ``seq`` positions."""
    return seq * (seq + 1) // 2


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
