"""jax.profiler wrappers + compile-artifact dumps (partly a compat shim).

.. note:: The unified observability subsystem lives in
   :mod:`autodist_tpu.obs` now (docs/observability.md carries the span
   model, the reference citations that used to live here, and the export
   formats). This module keeps two things:

   - the ``jax.profiler`` device-timeline wrapper (:func:`trace`) and the
     per-compile HLO dumps (:func:`dump_hlo`, :func:`dump_compiled`) —
     xplane/TensorBoard tooling. A named region inside a trace is a span
     of ``obs.spans``, which lies on the profiler's clock;
   - a **compat shim** for :class:`StepTimer`, which moved to
     :mod:`autodist_tpu.obs.profiler` — import it from there in new code.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import List, Optional

from autodist_tpu import const
from autodist_tpu.const import ENV
# Compat shim: StepTimer's home is the obs subsystem now; this re-export
# keeps the historical `utils.tracing.StepTimer` path working.
from autodist_tpu.obs.profiler import StepTimer  # noqa: F401
from autodist_tpu.utils import logging


# ------------------------------------------------------------------- tracing
@contextlib.contextmanager
def trace(name: str = "trace", trace_dir: Optional[str] = None):
    """Profile everything inside the block; writes a TensorBoard trace.

    Creates ``trace_dir`` (including parents) when missing and yields the
    resolved path, so callers — ``train.py --profile-dir``, the
    measured-wire capture (``obs/attrib.py``) — get the directory the
    device profile actually landed in regardless of whether they named
    one.

    Usage::

        with tracing.trace("step-100") as td:
            state, metrics = step(state, batch)
            jax.block_until_ready(state.params)
        # td -> parse with obs attrib / profile_ops.py --parse
    """
    import jax

    trace_dir = trace_dir or os.path.join(
        const.DEFAULT_TRACE_DIR, f"{name}-{int(time.time())}"
    )
    os.makedirs(trace_dir, exist_ok=True)
    logging.info("profiler trace -> %s", trace_dir)
    with jax.profiler.trace(trace_dir):
        yield trace_dir


# ------------------------------------------------------------------ HLO dump
def dump_hlo(tag: str, stage: str, text: str, hlo_dir: Optional[str] = None) -> str:
    """Write one compile-stage artifact (visualization_util.log_graph analog).

    Stages mirror the reference's numbered snapshots ("0-original",
    "1-after-partition", ...): we use "0-stablehlo" (lowered, pre-XLA) and
    "1-optimized" (post-XLA-passes, what actually runs).
    """
    d = hlo_dir or ENV.SYS_DATA_PATH.val or const.DEFAULT_HLO_DIR
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{tag}-{stage}.txt")
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    logging.debug("dumped HLO %s/%s (%d bytes)", tag, stage, len(text))
    return path


def dump_compiled(tag: str, lowered, compiled=None, hlo_dir: Optional[str] = None) -> List[str]:
    """Dump a jax ``Lowered`` (and optionally ``Compiled``) pair."""
    paths = [dump_hlo(tag, "0-stablehlo", lowered.as_text(), hlo_dir)]
    if compiled is not None:
        try:
            paths.append(dump_hlo(tag, "1-optimized", compiled.as_text(), hlo_dir))
        except Exception as e:  # noqa: BLE001 - optimized text is best-effort
            logging.debug("optimized HLO unavailable: %s", e)
    return paths


