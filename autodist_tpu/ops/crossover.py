"""Measured attention crossovers: when "auto" picks a Pallas kernel.

``examples/benchmark/flash_crossover.py`` sweeps the transformer step with
``attention_impl`` "dot" vs "flash" over sequence lengths on the real
accelerator and records the table in ``docs/measured/flash_crossover.json``.
The shape of that table (TPU v5e, bf16): XLA's fused dot-product attention
wins at short sequences (the flash kernel's block bookkeeping costs more
than the O(s²) logits it avoids materializing), and the Pallas kernel wins
once the logits matrix stops fitting in VMEM — 2× step time at s=4096.
That table predates the present kernel: it was recorded with the flash
kernels of before PR 28 (128 x 128 tiles, float32 operands into the backward
products, the mask built in every tile), on a model of 8-12 heads. The
kernels are since about twice as fast at seq 1,024 (PERF.md § 6, PR 28), so
the true breakeven lies lower than the recorded one; the table is read by the
program and is re-recorded, not edited.

This module turns the table into the ONE decision rule the transformer's
``attention_impl="auto"`` uses: the smallest measured sequence length from
which flash never loses to dot again. Below it, or when the sequence is not
block-aligned (the kernel would fall back to the jnp reference anyway),
"auto" resolves to "dot".

The serving stack's ``paged_attention_impl="auto"`` gets the same treatment:
``examples/benchmark/paged_crossover.py`` sweeps decode steps with the
paged-attention gather vs the page-walking pallas kernel
(ops/paged_attention.py) over (batch, table width, heads) shapes and records
``docs/measured/paged_crossover.json``; :func:`resolve_paged_impl` picks
"kernel" from the smallest timeline at which the kernel never loses for the
nearest recorded (batch, heads) bucket. Off-TPU, "auto" always resolves to
"gather" — interpret-mode pallas is a correctness vehicle, not a fast path.
"""
from __future__ import annotations

import json
import os
from typing import Optional

from autodist_tpu.utils import logging

#: Fallback when no measured table is readable: the v5e-measured breakeven
#: (flash ties dot at s=1024 and wins beyond; docs/measured/
#: flash_crossover.json).
DEFAULT_FLASH_CROSSOVER_SEQ = 1024

#: The flash kernel's block alignment (ops/flash_attention.py falls back to
#: the jnp reference for sequences this doesn't divide).
_FLASH_BLOCK = 128

_cache: dict = {}


def _warn_unreadable(path: str, err: Exception, default: int) -> None:
    """The constant stands in for a table that could not be read — a
    different selection rule than the measured one, so say it. The lookups
    are cached per shape, so this fires once per shape, not per trace."""
    logging.warning(
        "crossover table %s unreadable (%s: %s); using the packaged "
        "default %d", path, type(err).__name__, err, default)


def _measured_path() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "docs", "measured", "flash_crossover.json")


def flash_crossover_seq(path: Optional[str] = None) -> int:
    """Smallest measured seq length from which "flash" never loses to
    "dot" (tokens/sec), per the recorded sweep; the packaged default when
    the file is missing, unreadable, or records no crossover. Cached per
    path — the resolution runs inside model tracing."""
    key = path or "__default__"
    if key in _cache:
        return _cache[key]
    out = DEFAULT_FLASH_CROSSOVER_SEQ
    path = path or _measured_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            rows = json.load(f).get("rows", [])
        by_seq: dict = {}
        for r in rows:
            by_seq.setdefault(int(r["seq"]), {})[str(r["impl"])] = float(
                r["tokens_per_sec"])
        seqs = sorted(s for s, v in by_seq.items()
                      if "dot" in v and "flash" in v)
        for i, s in enumerate(seqs):
            if all(by_seq[t]["flash"] >= by_seq[t]["dot"]
                   for t in seqs[i:]):
                out = s
                break
    except (OSError, ValueError, KeyError, TypeError) as e:
        _warn_unreadable(path, e, out)
    _cache[key] = out
    return out


def resolve_attention_impl(impl: str, seq_len: int) -> str:
    """The ``attention_impl="auto"`` rule: "flash" at and above the
    measured crossover when the sequence is block-aligned (the Pallas
    kernel's own constraint), else "dot". Explicit impls pass through
    untouched — "auto" never overrides a caller's choice."""
    if impl != "auto":
        return impl
    if seq_len >= flash_crossover_seq() and seq_len % _FLASH_BLOCK == 0:
        return "flash"
    return "dot"


# ------------------------------------------------------- paged kernel-vs-gather
#: Fallback paged crossover when no measured table is readable: the timeline
#: width (table pages * page_len) from which the page-walking kernel beats
#: the materialize-then-attend gather (docs/measured/paged_crossover.json).
DEFAULT_PAGED_CROSSOVER_TIMELINE = 1024


def _paged_measured_path() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, "docs", "measured", "paged_crossover.json")


def paged_crossover_timeline(batch: Optional[int] = None,
                             heads: Optional[int] = None,
                             path: Optional[str] = None) -> int:
    """Smallest measured timeline width from which "kernel" never loses to
    "gather" (tokens/sec) for the nearest recorded (batch, heads) bucket;
    the packaged default when the file is missing, unreadable, or records
    no crossover. Cached per (path, batch, heads) — the resolution runs
    inside the serving programs' tracing."""
    key = ("paged", path or "__default__", batch, heads)
    if key in _cache:
        return _cache[key]
    out = DEFAULT_PAGED_CROSSOVER_TIMELINE
    path = path or _paged_measured_path()
    try:
        with open(path, "r", encoding="utf-8") as f:
            rows = json.load(f).get("rows", [])
        # Nearest recorded (batch, heads) bucket: the sweep records a few
        # decode-shaped points, not the full cross product.
        def _dist(r):
            d = 0.0
            if batch is not None and "batch" in r:
                d += abs(float(r["batch"]) - batch)
            if heads is not None and "heads" in r:
                d += abs(float(r["heads"]) - heads)
            return d
        if rows and (batch is not None or heads is not None):
            best = min(_dist(r) for r in rows)
            rows = [r for r in rows if _dist(r) == best]
        by_tl: dict = {}
        for r in rows:
            tl = int(r["table_pages"]) * int(r["page_len"])
            by_tl.setdefault(tl, {})[str(r["impl"])] = float(
                r["tokens_per_sec"])
        tls = sorted(t for t, v in by_tl.items()
                     if "gather" in v and "kernel" in v)
        for i, t in enumerate(tls):
            if all(by_tl[u]["kernel"] >= by_tl[u]["gather"]
                   for u in tls[i:]):
                out = t
                break
    except (OSError, ValueError, KeyError, TypeError) as e:
        _warn_unreadable(path, e, out)
    _cache[key] = out
    return out


def resolve_paged_impl(impl: str, batch: int, table_pages: int,
                       page_len: int, heads: int) -> str:
    """The ``paged_attention_impl="auto"`` rule: "kernel" at and above the
    measured timeline crossover for the nearest recorded (batch, heads)
    shape — on TPU only; off-TPU "auto" is always "gather" (interpret-mode
    pallas is the tier-1 correctness vehicle, ~100x slower than the XLA
    gather). Explicit impls pass through untouched, so tests force the
    kernel on CPU and devices force the gather for A/B sweeps."""
    if impl != "auto":
        return impl
    import jax  # lazy: keep module import free of a backend query

    if jax.default_backend() != "tpu":
        return "gather"
    if table_pages * page_len >= paged_crossover_timeline(batch, heads):
        return "kernel"
    return "gather"
