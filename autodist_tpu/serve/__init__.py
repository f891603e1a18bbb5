"""Inference serving (L5b): sharded paged engine + continuous batching.

The training half of the framework ends at a compiled
:class:`~autodist_tpu.kernel.DistributedTrainStep`; this package opens the
inference half of the ROADMAP north star ("serves heavy traffic"): the same
``Strategy``/``ShardingPlan`` substrate compiles a *forward/decode* step
instead of a train step (GSPMD sharding annotations scale to inference
unchanged — arxiv 2105.04663 §6), a continuous batcher keeps the device fed
across requests of ragged lengths, and a thin asyncio front end exposes it.

Layers:

- :mod:`autodist_tpu.serve.pages` — the ONE page-table/pool allocator home
  (fixed-size KV pages, all-or-nothing reservation, scratch-page padding).
- :mod:`autodist_tpu.serve.engine` — :class:`InferenceEngine`: params
  restored from a checkpoint into plan shardings, a jitted one-shot apply,
  and a paged KV-cache decode loop — exactly TWO compiled serving programs
  (one decode over all slot rows + one fixed-size prefill chunk) for any
  request-length mix.
- :mod:`autodist_tpu.serve.batcher` — :class:`ContinuousBatcher`: bounded
  admission queue with backpressure, page-availability admission (typed
  :class:`~autodist_tpu.serve.engine.AdmissionDenied` — retryable pool
  pressure vs never-placeable rejection), chunked prefill interleaved with
  decode, per-request deadlines, page recycling on retirement.
- :mod:`autodist_tpu.serve.server` — asyncio HTTP front end and the
  ``python -m autodist_tpu.serve --selftest`` CPU-sim proof (greedy
  streams bit-identical to the uncached forward's, zero drops, exactly
  2 compiled programs).

- :mod:`autodist_tpu.serve.replica` / :mod:`autodist_tpu.serve.router` —
  the multi-replica control plane: N supervised replicas exporting typed
  readiness (``STARTING``/``READY``/``DRAINING``/``SUSPECT``/``DEAD``)
  through the ft heartbeat transports, fronted by a dependency-free
  :class:`Router` with journaled exactly-once failover (prefix resume,
  bit-identity asserted), straggler-weighted least-loaded routing, and
  rolling drain upgrades (``python -m autodist_tpu.serve
  --selftest-router`` is the CPU proof). The router measures the
  client-visible stream against a declarative SLO
  (:mod:`autodist_tpu.obs.slo` — rolling TTFT/ITL/queue-wait
  percentiles, burn rates, ``slo_report``), feeds the serve-aware
  sentry (SNT007/008/009 demote a latency-sick replica), and tags
  every request's spans with its stable id so ONE chrome trace shows a
  request's full life including a mid-decode failover
  (docs/observability.md § serving).

- :mod:`autodist_tpu.serve.spec` — speculative decode:
  :class:`SpecDecodeEngine` pairs the target with a small draft model
  (same Strategy/ShardingPlan pipeline, shared mesh, its own paged pool
  with incremental extend + rejection rewind) — k proposals per slot per
  round, ONE compiled target verify program with on-device greedy
  accept/reject, **lossless by construction** (streams bit-identical to
  plain greedy for any draft, so failover/journal-replay semantics hold
  unchanged); ``python -m autodist_tpu.serve --selftest-spec`` proves
  bit-identity, >=2x fewer target invocations per token, and zero leaked
  pages over 1k+ accept/reject cycles (docs/serving.md § speculative
  decode).

- :mod:`autodist_tpu.serve.prefix` — copy-on-write prefix sharing: the
  ONE home of the refcounted radix tree keyed by chained token-block
  hash (``tools/check_patterns.py`` rule 9). Matched prompt blocks map
  onto the SAME physical pages (refcount++), only the unmatched suffix
  reserves fresh pages and prefills; divergence is resolved by copying
  at most ONE frontier page (never a shared write); cold refcount-0
  leaves evict LRU under pool pressure — eviction degrades future
  admissions to recompute, never touches a live request's pages. One
  tree spans the spec engine's target AND draft pools, and
  :func:`~autodist_tpu.serve.prefix.block_hashes` feeds the router's
  prefix-affinity tiebreak. ``python -m autodist_tpu.serve
  --selftest-prefix`` is the CPU proof (>=5x cached TTFT p50, >=2x
  admitted concurrency at equal pool bytes, bit-identical streams, zero
  leaked pages — docs/serving.md § prefix sharing).

- :mod:`autodist_tpu.serve.sampling` — the ONE stochastic-sampling home
  (``tools/check_patterns.py`` rule 10): :class:`SamplingParams`
  (temperature / top_k / top_p / seed; temperature=0 IS greedy) ride each
  request from the HTTP edge through admission, slot state, the router
  journal and per-tenant defaults; every draw is a stateless
  counter-based function of ``(request_id, seed, position)`` — a shared
  Gumbel argmax over the temperature-scaled, top-k/top-p-filtered target
  distribution — so failover replay, prefix-cache hits and speculative
  decode (the draft proposes under the SAME noise; verify keeps the
  matching prefix) all reproduce the identical stream bit for bit.
  ``python -m autodist_tpu.serve --selftest-sampling`` is the CPU proof
  (chi-square calibration, seeded replay, spec/prefix/failover
  bit-identity, greedy reduction, 2/5 program pins).

Entry point: ``autodist.build_inference(...)`` (api.py) or
:meth:`InferenceEngine.build` directly.
"""
from autodist_tpu.serve.batcher import (
    Backpressure,
    ContinuousBatcher,
    GenRequest,
    RequestState,
)
from autodist_tpu.serve.engine import (
    AdmissionDenied,
    DecodeModel,
    EngineDeadError,
    InferenceEngine,
    Slot,
)
from autodist_tpu.serve.pages import PagePool, PageTable, build_pool
from autodist_tpu.serve.prefix import (
    PrefixCache,
    block_hashes,
    build_prefix_cache,
)
from autodist_tpu.serve.replica import Replica, ReplicaState
from autodist_tpu.serve.router import Router, RouterConfig
from autodist_tpu.serve.sampling import InvalidSamplingParams, SamplingParams
from autodist_tpu.serve.server import RouterFrontend, ServeFrontend
from autodist_tpu.serve.spec import SpecDecodeEngine

__all__ = [
    "AdmissionDenied",
    "Backpressure",
    "ContinuousBatcher",
    "DecodeModel",
    "EngineDeadError",
    "GenRequest",
    "InferenceEngine",
    "InvalidSamplingParams",
    "PagePool",
    "PageTable",
    "PrefixCache",
    "Replica",
    "ReplicaState",
    "RequestState",
    "Router",
    "RouterConfig",
    "RouterFrontend",
    "SamplingParams",
    "ServeFrontend",
    "Slot",
    "SpecDecodeEngine",
    "block_hashes",
    "build_pool",
    "build_prefix_cache",
]
