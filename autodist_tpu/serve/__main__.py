"""CLI: ``python -m autodist_tpu.serve``.

Six modes:

- ``--selftest``: the zero-hardware single-engine proof (tiny CPU
  transformer; greedy streams bit-identical to the uncached forward's,
  >=64 concurrent mock requests with zero drops, exactly 2 compiled
  serving programs). Run with
  ``JAX_PLATFORMS=cpu``; exits nonzero on any violated bar.
- ``--selftest-router``: the multi-replica control-plane proof
  (docs/serving.md § router): 3 in-process replicas behind the router,
  one killed mid-decode under 64 concurrent requests — every request
  completes exactly once (journal-verified), every delivered stream
  bit-identical to an uninterrupted control run.
- ``--selftest-spec``: the speculative-decode proof (docs/serving.md §
  speculative decode): spec-decode streams bit-identical to plain greedy
  across draft qualities and k in {1,2,4,8}, >=2x fewer target-model
  program invocations per emitted token on the acceptance-friendly
  workload, zero leaked pages after 1k+ accept/reject cycles.
- ``--selftest-prefix``: the COW prefix-sharing proof (docs/serving.md §
  prefix sharing): on a system-prompt-heavy workload at equal pool
  bytes, >=5x cached TTFT p50 and >=2x admitted concurrency vs the
  sharing-off control, every stream bit-identical, refcounts drained to
  zero with zero leaked pages, program pins unchanged (2 plain / 5 spec).
- ``--selftest-sampling``: the stochastic-sampling proof (docs/serving.md
  § stochastic sampling): counter-based draws chi-square-calibrated
  against the filtered softmax, the same ``(request_id, seed)`` replays
  bit-identically, spec-decode streams bit-identical to the plain
  stochastic control across temperature x top_p x k (same-weights,
  divergent AND chaos-garbled drafts), temperature=0 reduces bit-exactly
  to greedy, prefix-cache hit vs cold start bit-identical, mid-decode
  replica kills resume every sampled stream bit-identically, program
  pins unchanged (2 plain / 5 spec).
- server mode (default): serve a zoo model — optionally restoring a
  checkpoint — over the asyncio HTTP front end. With ``--ft-dir`` the
  process runs as a supervised :class:`~autodist_tpu.serve.replica.
  Replica`: typed readiness (``STARTING``/``READY``/``DRAINING``) is
  published through the ft ``FileTransport`` under ``<ft-dir>/heartbeats``
  for a router/supervisor to observe, ``/healthz`` answers 503 until
  READY, and ``POST /drain`` persists undone work for exactly-once
  replay::

      python -m autodist_tpu.serve --model transformer \\
          --model-arg num_layers=2 --checkpoint /tmp/autodist-tpu/checkpoints \\
          --ft-dir /tmp/autodist-tpu/ft --replica-id 0 --port 8476
"""
from __future__ import annotations

import argparse
import asyncio
import sys


def _parse_overrides(pairs):
    out = {}
    for pair in pairs or ():
        k, _, v = pair.partition("=")
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = {"true": True, "false": False}.get(v.lower(), v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m autodist_tpu.serve",
                                 description=__doc__)
    ap.add_argument("--selftest", action="store_true",
                    help="run the CPU-sim serving proof and exit")
    ap.add_argument("--kv-quant", action="store_true",
                    help="with --selftest: serve from int8 KV pages and "
                         "prove the quantized bars instead (>=2x admitted "
                         "concurrency at equal pool bytes vs fp pages, "
                         "zero dropped, logit drift within the documented "
                         "bound, kernel-vs-gather bit-identity, analyzer "
                         "pricing of quantized bytes)")
    ap.add_argument("--selftest-router", action="store_true",
                    help="run the multi-replica router proof (3 replicas, "
                         "one killed mid-decode, exactly-once asserted) "
                         "and exit")
    ap.add_argument("--selftest-spec", action="store_true",
                    help="run the speculative-decode proof (bit-identical "
                         "greedy streams across draft qualities and k in "
                         "{1,2,4,8}, >=2x fewer target-model invocations "
                         "per token, balanced page accounting after 1k+ "
                         "accept/reject cycles) and exit")
    ap.add_argument("--selftest-prefix", action="store_true",
                    help="run the COW prefix-sharing proof (>=5x cached "
                         "TTFT p50 and >=2x admitted concurrency vs "
                         "sharing-off at equal pool bytes, bit-identical "
                         "streams, zero leaked pages, 2/5 program pins) "
                         "and exit")
    ap.add_argument("--selftest-sampling", action="store_true",
                    help="run the stochastic-sampling proof (counter-based "
                         "draws calibrated by chi-square, seeded replay "
                         "and spec/prefix/failover bit-identity across "
                         "temperature x top_p x k, greedy reduction at "
                         "temperature=0, 2/5 program pins) and exit")
    ap.add_argument("--ft-dir", default=None,
                    help="server mode: run as a supervised replica, "
                         "publishing typed readiness through the ft "
                         "FileTransport under <ft-dir>/heartbeats")
    ap.add_argument("--replica-id", type=int, default=0,
                    help="server mode: this replica's id on the ft "
                         "transport (with --ft-dir)")
    ap.add_argument("--trace-out", default=None,
                    help="server mode: flush this process's span part-file "
                         "into DIR at exit (obs/spans.py); replica "
                         "processes of one fleet sharing a DIR (and the "
                         "launcher-exported AUTODIST_TRACE_ID) stitch into "
                         "ONE chrome trace via obs.spans.stitch, exactly "
                         "like launcher/worker part-files")
    ap.add_argument("--requests", type=int, default=64,
                    help="selftest: concurrent mock requests (>=64 proves "
                         "the acceptance bar)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slot rows (default: 32 for the selftest, "
                         "8 in server mode)")
    ap.add_argument("--max-new", type=int, default=12,
                    help="selftest: tokens generated per request")
    ap.add_argument("--page-len", type=int, default=16,
                    help="server mode: KV-cache page length in tokens")
    ap.add_argument("--pages", type=int, default=None,
                    help="server mode: page-pool size override (default: "
                         "sized from ResourceSpec HBM headroom)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="server mode: prefill chunk tokens (default: one "
                         "page)")
    ap.add_argument("--model", default="transformer",
                    help="zoo model name (server mode)")
    ap.add_argument("--model-arg", action="append", metavar="K=V",
                    help="model config override (repeatable)")
    ap.add_argument("--checkpoint", default=None,
                    help="Saver directory or ckpt-N path to restore")
    ap.add_argument("--draft-model", default=None,
                    help="server mode: zoo model name for a speculative-"
                         "decode draft (same transformer family; enables "
                         "the SpecDecodeEngine — docs/serving.md § "
                         "speculative decode)")
    ap.add_argument("--draft-arg", action="append", metavar="K=V",
                    help="draft model config override (repeatable)")
    ap.add_argument("--draft-checkpoint", default=None,
                    help="Saver directory or ckpt-N path for the draft")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per slot per round")
    ap.add_argument("--strategy", default="AllReduce",
                    help="strategy builder name (see autodist_tpu.strategy)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8476)
    args = ap.parse_args(argv)

    if args.selftest:
        from autodist_tpu.serve.server import selftest

        return selftest(n_requests=args.requests,
                        n_slots=args.slots or 32,
                        max_new=args.max_new,
                        kv_quant=args.kv_quant)

    if args.selftest_router:
        from autodist_tpu.serve.router import selftest_router

        return selftest_router(n_requests=args.requests,
                               max_new=args.max_new)

    if args.selftest_spec:
        from autodist_tpu.serve.spec import selftest_spec

        return selftest_spec(max_new=args.max_new)

    if args.selftest_prefix:
        from autodist_tpu.serve.prefix import selftest_prefix

        return selftest_prefix()

    if args.selftest_sampling:
        from autodist_tpu.serve.sampling import selftest_sampling

        return selftest_sampling()

    import os

    if args.ft_dir and "AUTODIST_PROCESS_ID" not in os.environ:
        # Replica part-files (spans, flight records) identify as this
        # replica unless a launcher already pinned a process id — so a
        # stitched fleet trace shows "role <replica-id>" tracks.
        os.environ["AUTODIST_PROCESS_ID"] = str(args.replica_id)
    if args.trace_out:
        from autodist_tpu.obs import spans as obs_spans

        obs_spans.enable_trace_out(args.trace_out)

    import jax

    import autodist_tpu.strategy as S
    from autodist_tpu.api import AutoDist
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from autodist_tpu.models import get_model
    from autodist_tpu.models.transformer import decode_model
    from autodist_tpu.serve.batcher import ContinuousBatcher
    from autodist_tpu.serve.server import ServeFrontend

    spec = get_model(args.model, **_parse_overrides(args.model_arg))
    params = spec.init(jax.random.PRNGKey(0))
    autodist = AutoDist(strategy_builder=S.from_name(args.strategy))
    draft_kwargs = {}
    if args.draft_model:
        draft_spec = get_model(args.draft_model,
                               **_parse_overrides(args.draft_arg))
        draft_kwargs = dict(
            draft_params=draft_spec.init(jax.random.PRNGKey(1)),
            draft_decode_model=decode_model(draft_spec.config),
            draft_checkpoint=args.draft_checkpoint,
            spec_k=args.spec_k,
        )

    def build_engine():
        return autodist.build_inference(
            params,
            apply_fn=spec.apply,
            decode_model=(decode_model(spec.config)
                          if hasattr(spec.config, "num_heads") else None),
            checkpoint=args.checkpoint,
            n_slots=args.slots or 8,
            page_len=args.page_len,
            n_pages=args.pages,
            prefill_chunk=args.prefill_chunk,
            **draft_kwargs,
        )

    # Every server measures its own SLO position (GET /slo renders it;
    # docs/serving.md § SLO runbook) — deployments tune the spec.
    from autodist_tpu.obs.slo import SLOTracker

    slo = SLOTracker()

    if args.ft_dir:
        # Supervised-replica mode: readiness + load travel through the
        # same FileTransport a router/launcher observes; /healthz is 503
        # until the engine is READY.
        from autodist_tpu.ft.heartbeat import FileTransport
        from autodist_tpu.serve.replica import Replica

        replica = Replica(
            args.replica_id, build_engine,
            FileTransport(os.path.join(args.ft_dir, "heartbeats")),
            persist_path=os.path.join(
                args.ft_dir, f"serve_queue-{args.replica_id}.json"),
            slo=slo,
        )
        frontend = ServeFrontend(None, host=args.host, port=args.port,
                                 replica=replica)
    else:
        frontend = ServeFrontend(ContinuousBatcher(build_engine(), slo=slo),
                                 host=args.host, port=args.port)
    # A supervisor stops a replica with SIGTERM; route it through the
    # KeyboardInterrupt path so shutdown unwinds (frontend close, atexit
    # span part-file flush for --trace-out) instead of dying mid-write.
    import signal

    signal.signal(signal.SIGTERM, signal.default_int_handler)
    try:
        asyncio.run(frontend.serve_forever())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
