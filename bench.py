"""Benchmark: train-step throughput + MFU on the local TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
The reference publishes no numeric tables (BASELINE.md), so ``vs_baseline``
is measured MFU / 0.50, the BASELINE.json north-star target (>=50% MFU).

By default the three named workloads run — the flagship BERT-base MLM
(AllReduce strategy, the headline ``metric: bert_base_mfu``), the ResNet-50
image workload and BERT-large (``resnet50_*`` / ``bert_large_*`` extras in
the same line). ``--model bert|resnet|bert_large`` restricts to one.

Every workload runs in its own child process, one after the other: this
parent never imports jax, so the chip belongs to exactly one process at a
time. A child that finds no TPU, or a device_kind the peak table does not
list, exits non-zero and so does the run — there is no CPU fallback and no
number that was not measured by this invocation.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

TARGET_MFU = 0.50      # BASELINE.json north star

# Child exit code for "this machine has no usable TPU": the parent stops at
# once instead of starting the remaining workloads to fail the same way.
RC_NO_TPU = 3


def measure_workload(model_name: str, plan_cache: str = "") -> dict:
    """Train-step throughput for one named workload on the visible devices.

    Returns raw numbers; the caller formats the JSON line. Uses the full
    AutoDist pipeline (AllReduce strategy) — the bench measures the
    framework's production path, not a hand-written loop. With
    ``plan_cache`` set, the strategy comes from the search-based planner
    backed by that persistent cache dir instead (docs/planner.md): the
    first run searches, later runs hit the cache and skip planning
    entirely; hit/miss counts ride the JSON line.
    """
    import jax

    from autodist_tpu.api import AutoDist
    from autodist_tpu.models import get_model
    from autodist_tpu.obs.profiler import peak_flops_for_kind
    import autodist_tpu.strategy as S

    if model_name == "resnet":
        candidate_batches, steps = (128, 256), 20
        spec = get_model("resnet")
        unit_per = "images"
    elif model_name == "bert_large":
        # The exact model the reference's published benchmark pretrains
        # (L=24 H=1024 A=16).
        candidate_batches, steps = (32, 64), 20
        spec = get_model("bert_large")
        unit_per = "tokens"
    else:
        # 256 rides the sweep's per-candidate OOM guard: its MLM logits
        # ([256*128, 30522] bf16 ~ 2 GB + grads) may or may not fit beside
        # the activations on a given chip generation; when it fits it can
        # beat 128 on MXU utilization, and when it OOMs the smaller
        # candidates' results are unaffected.
        candidate_batches, steps = (64, 128, 256), 20
        spec = get_model("bert_base", max_seq_len=128)
        unit_per = "tokens"

    params = spec.init(jax.random.PRNGKey(0))

    # The whole window runs as ONE device program (lax.scan inside
    # step.run) — the hot loop stays on device like the reference's C++
    # session.run loop. The barrier is a host fetch of the last loss: it
    # cannot return before every step of the window has run, and it checks
    # the loss is a number on the way. Batch size is swept (the
    # throughput-vs-batch curve is not monotone on one chip); the best
    # throughput wins.
    plan_stats = {}
    lint_info = {}
    attrib_info = {}

    def _attrib(ad, step, state, batch):
        """``--attrib`` mode: measured-wire attribution (obs/attrib.py) of
        one short captured window BEFORE any timed window, with its own
        JSON line emitted immediately (like ``--lint``): a run killed in a
        timed window still leaves the joined device profile. Returns the
        (possibly donated-and-replaced) state."""
        if os.environ.get("AUTODIST_BENCH_ATTRIB", "") != "1" or attrib_info:
            return state
        try:
            from autodist_tpu.obs import attrib as obs_attrib
            from autodist_tpu.obs import recorder as obs_recorder

            wire, state = obs_attrib.attribute(
                step, state, batch, num_steps=min(steps, 4),
                program=f"bench:{model_name}")
            summary = wire.summary()
            report_path = wire.save(os.path.join(
                tempfile.mkdtemp(prefix=f"{model_name}_attrib_"),
                "measured_wire.json"))
            summary["report"] = report_path
            attrib_info.update({
                "attrib_exposed_comm_fraction": wire.exposed_comm_fraction,
                "attrib_wire_ms_per_step": round(
                    wire.wire_s_per_step * 1e3, 4),
                "attrib_unattributed_large": len(wire.unattributed_large),
                "attrib_buckets": summary["bucket_overlap"],
            })
            obs_recorder.record_event("attrib", critical=False, **summary)
            print(json.dumps({"bench_attrib": summary,
                              "model": model_name}), flush=True)
        except Exception as e:  # noqa: BLE001 - attribution never eats a bench
            attrib_info.update({"attrib_failed": str(e)[:200]})
            print(json.dumps({"bench_attrib": {"failed": str(e)[:200]},
                              "model": model_name}), flush=True)
            # A failure after the capture window ran leaves `state` donated
            # (deleted buffers) — hand the timed windows a fresh state
            # rather than letting the attribution eat the bench after all.
            state = step.init(params)
        return state

    def _lint(ad, step, state, batch):
        """``--lint`` mode: run the static analyzer (shardlint) on the
        compiled program BEFORE any timed window and emit its own JSON
        line immediately — a run killed in a timed window still yields the
        static signal. Opt-in: costs one extra compile of the per-step
        program."""
        if os.environ.get("AUTODIST_BENCH_LINT", "") != "1" or lint_info:
            return
        try:
            from autodist_tpu.analysis import analyze_program, compiled_hlo

            rep = analyze_program(
                step.plan, compiled_hlo(step, state, batch),
                resource_spec=ad.resource_spec, batch=batch,
                program=f"bench:{model_name}")
            # Schedule-pass codes ride their own field: the static OOM /
            # no-overlap verdict prints BEFORE any timed window is
            # attempted, like the wire codes.
            sched_codes = sorted(
                {c for c in rep.codes()
                 if c.startswith("SLO") or c in ("SLM003", "SLH004")})
            verdict = []
            if "SLM003" in sched_codes:
                verdict.append("static-oom")
            if "SLO001" in sched_codes:
                verdict.append("no-overlap")
            lint_info.update({
                "lint_findings": len(rep.findings),
                "lint_errors": len(rep.errors),
                "lint_codes": sorted(set(rep.codes())),
                "lint_sched_codes": sched_codes,
                "lint_sched_verdict": "+".join(verdict) or "ok",
            })
        except Exception as e:  # noqa: BLE001 - lint must never eat a bench
            lint_info.update({"lint_findings": -1,
                              "lint_failed": str(e)[:200]})
        print(json.dumps({"bench_lint": dict(lint_info),
                          "model": model_name}), flush=True)

    def _builder():
        if not plan_cache:
            return S.AllReduce()
        from autodist_tpu.plan import Plan, PlanConfig

        return Plan(PlanConfig(cache_dir=plan_cache))

    def measure(bs):
        AutoDist.reset_default()
        ad = AutoDist(strategy_builder=_builder())
        batch = spec.example_batch(bs)
        step = ad.build(spec.loss_fn, params, batch)
        cache = getattr(ad.strategy_builder, "cache", None)
        if cache is not None:
            for k, v in cache.stats.items():
                plan_stats[k] = plan_stats.get(k, 0) + v
        state = step.init(params)
        _lint(ad, step, state, batch)
        state = _attrib(ad, step, state, batch)
        # Pin the batch in HBM (the "compute" methodology,
        # docs/performance.md): the window then times the device program,
        # not the host feed.
        batch = jax.device_put(batch, step.plan.batch_shardings(batch))
        jax.block_until_ready(batch)
        state, metrics = step.run(state, batch, steps)  # warmup/compile
        float(metrics["loss"][-1])
        # Each trial dispatches M windows back-to-back (run() returns
        # immediately; programs queue and pipeline on the device) with ONE
        # trailing loss fetch as the barrier, then divides by M, so the
        # device never idles on a host round trip between windows.
        m_windows = 8
        trials = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(m_windows):
                state, metrics = step.run(state, batch, steps)
            float(metrics["loss"][-1])
            trials.append((time.perf_counter() - t0) / m_windows)
        dt = sorted(trials)[len(trials) // 2]  # median trial
        return dt, float(metrics["loss"][-1])

    def result_from(results: dict) -> dict:
        batch_size = min(results, key=lambda bs: results[bs][0] / bs)
        dt, last_loss = results[batch_size]
        dev = jax.devices()[0]
        seq = spec.config.max_seq_len if model_name != "resnet" else 1
        examples_per_sec = batch_size * steps / dt
        units_per_sec = examples_per_sec * seq
        flops_per_step = spec.flops_per_example * batch_size
        achieved = flops_per_step * steps / dt
        n_chips = jax.device_count()
        peak_per_chip = peak_flops_for_kind(dev.device_kind)
        return {
            **({"plan_cache": dict(plan_stats)} if plan_cache else {}),
            **lint_info,
            **attrib_info,
            "unit_per": unit_per,
            "mfu": achieved / (peak_per_chip * n_chips),
            "units_per_sec": units_per_sec,
            "achieved": achieved,
            "n_chips": n_chips,
            "batch_size": batch_size,
            "loss": last_loss,
            "seq": seq,
            "device": dev.device_kind,
        }

    results = {}
    best = None
    for bs in candidate_batches:
        try:
            results[bs] = measure(bs)
        except Exception as e:
            # An OOM at a bigger candidate must not eat the result the
            # smaller one already produced.
            print(f"bench[{model_name}]: batch {bs} failed: {e}", file=sys.stderr)
            continue
        # Provisional emit after EVERY candidate: if a bigger candidate
        # hangs and the parent's watchdog kills this child, the parent
        # recovers the last complete line from its stdout
        # (_measure_in_subprocess).
        best = result_from(results)
        print(json.dumps({**best, "provisional_after": bs}), flush=True)
    if not results:
        raise RuntimeError(f"{model_name}: every candidate batch size failed")
    return best


def _format_result(measured: dict, errors: dict) -> dict:
    """The driver-parseable JSON dict from per-workload measurements: the
    headline is ``bert_base_mfu`` whenever BERT measured, else the first
    workload that did; the others ride along as extras."""
    head_name = "bert" if "bert" in measured else next(iter(measured))
    head = measured[head_name]
    metric_base = {"bert": "bert_base_mfu", "bert_large": "bert_large_mfu",
                   "resnet": "resnet50_mfu"}[head_name]
    result = {
        "metric": metric_base,
        "value": round(head["mfu"], 4),
        "unit": "mfu",
        "vs_baseline": round(head["mfu"] / TARGET_MFU, 4),
        f"{head['unit_per']}_per_sec_per_chip": round(
            head["units_per_sec"] / head["n_chips"], 1),
        "achieved_tflops_per_chip": round(
            head["achieved"] / head["n_chips"] / 1e12, 2),
        "device": head["device"],
        "n_chips": head["n_chips"],
        "batch_size": head["batch_size"],
        "loss": round(head["loss"], 4),
    }
    if head_name != "resnet":
        result["seq_len"] = head["seq"]
    for extra_name, prefix in (("resnet", "resnet50"), ("bert", "bert_base"),
                               ("bert_large", "bert_large")):
        if extra_name == head_name or extra_name not in measured:
            continue
        w = measured[extra_name]
        result[f"{prefix}_mfu"] = round(w["mfu"], 4)
        result[f"{prefix}_vs_baseline"] = round(w["mfu"] / TARGET_MFU, 4)
        result[f"{prefix}_{w['unit_per']}_per_sec_per_chip"] = round(
            w["units_per_sec"] / w["n_chips"], 1)
        result[f"{prefix}_batch_size"] = w["batch_size"]
    for name, w in measured.items():
        # A truncated candidate sweep (watchdog note) is otherwise
        # indistinguishable from a complete one.
        if w.get("note"):
            result[f"{name}_note"] = w["note"]
    # Plan-cache accounting (--plan-cache): summed across workloads so a
    # warm run shows its reuse ("hits": N).
    plan_totals = {}
    for w in measured.values():
        for k, v in (w.get("plan_cache") or {}).items():
            plan_totals[k] = plan_totals.get(k, 0) + int(v)
    if plan_totals:
        result["plan_cache"] = plan_totals
    for name, err in errors.items():
        result[f"{name}_error"] = err
    return result


def _last_json_line(out):
    """Parse the last ``{``-prefixed line of (possibly bytes, possibly
    truncated) child stdout; None when nothing parses."""
    if isinstance(out, bytes):
        out = out.decode(errors="replace")
    for line in reversed((out or "").strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue  # killed mid-write: fall back to the previous line
    return None


def _measure_in_subprocess(name: str, timeout_s: float, plan_cache: str = ""):
    """Run one workload in a child process and wait for it to exit.

    One process owns the chip at a time: the parent stays off jax and the
    children run strictly one after another. The watchdog bounds a child
    that hangs at a bigger candidate; what it measured before that is
    recovered from its provisional lines.
    Returns (dict | None, error | None, returncode | None).
    """
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__), "--one", name]
    if plan_cache:
        cmd.extend(["--plan-cache", plan_cache])
    try:
        r = subprocess.run(
            cmd, timeout=timeout_s, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired as e:
        partial = _last_json_line(e.stdout)
        if partial is not None:
            partial["note"] = (
                f"watchdog killed the sweep after {timeout_s:.0f}s; "
                f"result is the last completed candidate")
            return partial, None, None
        return None, f"workload timed out after {timeout_s:.0f}s", None
    if r.stderr:
        sys.stderr.write(r.stderr[-2000:])
    parsed = _last_json_line(r.stdout) if r.returncode == 0 else None
    if parsed is not None:
        return parsed, None, 0
    return (None, f"workload exited rc={r.returncode} with no result",
            r.returncode)


def _serve_decode_bench(n_requests: int = 48, max_new: int = 10,
                        kv_quant: bool = False) -> dict:
    """The ``serve_decode`` workload: paged continuous-batching decode on
    the selftest's tiny engine (build_inference → paged engine → batcher →
    asyncio bridge), mixed short and long (chunked-prefill) prompts.
    ``kv_quant=True`` serves from int8 quantized KV pages (the
    ``serve_decode_quant`` arm); every result line stamps ``kv_quant``
    and the resolved kernel-vs-gather choice, so the driver's history can
    bucket the two configurations apart.

    At these widths the numbers are the serving SCHEDULER's and the
    paged-cache bookkeeping's (decode tokens/sec, p50/p99 request latency,
    peak page-pool utilization), not the chip's; the serving cell at real
    widths is ROADMAP S1. The line names the device it ran on.
    """
    import asyncio

    import jax
    import numpy as np

    from autodist_tpu import metrics as M
    from autodist_tpu.obs.slo import SLOTracker
    from autodist_tpu.ops.crossover import resolve_paged_impl
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState
    from autodist_tpu.serve.sampling import SamplingParams
    from autodist_tpu.serve.server import (
        _tiny_engine, async_generate, mock_load_prompt)

    registry = M.MetricsRegistry()
    rng = np.random.default_rng(0)
    engine, _params, _cfg = _tiny_engine(n_slots=32, prefix_cache=True,
                                         kv_quant=kv_quant)
    engine.generate(rng.integers(1, 127, size=6), max_new)  # warm compiles
    paged_impl = resolve_paged_impl(
        getattr(_cfg, "paged_attention_impl", "auto"), engine.n_slots,
        engine.max_pages, engine.page_len, _cfg.num_heads)

    slo = SLOTracker()
    batcher = ContinuousBatcher(engine, max_queue=max(n_requests, 64),
                                registry=registry, slo=slo)
    # Every other request is stochastic (a low/mid/high temperature mix,
    # counter-based draws — serve/sampling.py), the rest greedy: the
    # bench line then carries real sampled-vs-greedy stream counts and,
    # on spec fleets, per-temperature-bucket acceptance.
    temp_mix = (0.0, 0.7, 1.0, 1.4)

    def sampling_for(i: int):
        t = temp_mix[i % len(temp_mix)]
        if t <= 0.0:
            return None
        return SamplingParams(temperature=t, top_p=0.95, seed=i)
    util_peak = {"v": 0.0}
    # The selftest's canonical mixed load (mock_load_prompt), with the
    # second half of the request stream repeating the first half's
    # prompts — the repeat traffic is what exercises the COW prefix
    # cache, so the bench line carries a real prefix_hit_rate and a
    # cached-TTFT percentile next to the uncached one.
    base_prompts = [mock_load_prompt(rng, i)
                    for i in range(max(n_requests // 2, 1))]

    async def run():
        async def client(i):
            await asyncio.sleep(0.001 * (i % 8))
            return await async_generate(
                batcher, base_prompts[i % len(base_prompts)], max_new,
                request_id=f"bench-{i}", sampling=sampling_for(i))

        async def sampler():
            while True:
                util_peak["v"] = max(util_peak["v"],
                                     engine.page_utilization)
                await asyncio.sleep(0.005)

        sample = asyncio.ensure_future(sampler())
        try:
            return await asyncio.gather(
                *(client(i) for i in range(n_requests)))
        finally:
            sample.cancel()

    batcher.start()
    t0 = time.perf_counter()
    try:
        results = asyncio.run(asyncio.wait_for(run(), timeout=240))
    finally:
        batcher.stop(drain=False)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in results)
    completed = sum(1 for r in results if r.state is RequestState.DONE)
    snap = registry.snapshot()
    decode_tokens = float(snap.get("serve_decode_tokens_generated_total", 0.0))
    lat = snap.get("serve_request_latency_s", {})
    ttft = snap.get("serve_ttft_s", {})
    itl = snap.get("serve_itl_s", {})
    ttft_cached = snap.get("serve_ttft_cached_s", {})
    if not isinstance(ttft_cached, dict):
        ttft_cached = {}
    hit_rate = snap.get("serve_prefix_hit_rate", float("nan"))
    slo_report = slo.report()
    return {"bench_serve": {
        "decode_tokens_per_sec": round(decode_tokens / dt, 1) if dt > 0 else None,
        "tokens_per_sec": round(tokens / dt, 1) if dt > 0 else None,
        "p50_latency_s": round(lat.get("p50", float("nan")), 4),
        "p99_latency_s": round(lat.get("p99", float("nan")), 4),
        "ttft_p50_s": round(ttft.get("p50", float("nan")), 4),
        "ttft_p99_s": round(ttft.get("p99", float("nan")), 4),
        "itl_p50_s": round(itl.get("p50", float("nan")), 4),
        "itl_p99_s": round(itl.get("p99", float("nan")), 4),
        "ttft_cached_p50_s": round(
            ttft_cached.get("p50", float("nan")), 4),
        "prefix_hit_rate": round(float(hit_rate), 4),
        "temperature_mix": list(temp_mix),
        "sampled_streams": int(
            slo_report["counts"].get("sampled_streams", 0)),
        "greedy_streams": int(
            slo_report["counts"].get("greedy_streams", 0)),
        "acceptance_by_temperature": {
            b: round(float(r), 4) for b, r in slo_report["measured"].get(
                "acceptance_by_temperature", {}).items()},
        "page_utilization_peak": round(util_peak["v"], 4),
        "n_requests": n_requests,
        "completed": completed,
        "dropped": n_requests - completed,
        "programs_compiled": engine.compiled_programs,
        "page_len": engine.page_len,
        "n_pages": engine.pool.n_pages,
        "kv_quant": "on" if kv_quant else "off",
        "paged_attention_impl": paged_impl,
        "quant_capacity_x": round(
            float(getattr(engine, "quant_capacity_x", 1.0)), 2),
        "device": jax.devices()[0].platform,
    }}


def _router_bench(n_requests: int = 24, max_new: int = 6) -> dict:
    """The ``serve_router`` workload: the multi-replica control plane
    under a mid-decode replica kill (3 in-process replicas of the tiny
    engine behind the router, the selftest's fleet). Measures failover
    latency (death → first rerouted token delivered), requests rerouted,
    and the drop count (the zero-drop contract).
    """
    import threading

    import jax
    import numpy as np

    from autodist_tpu import metrics as M
    from autodist_tpu.serve.batcher import RequestState
    from autodist_tpu.serve.router import build_test_fleet
    from autodist_tpu.serve.server import mock_load_prompt
    from autodist_tpu.utils import retry

    registry = M.MetricsRegistry()
    rng = np.random.default_rng(0)
    router, _control = build_test_fleet(n_replicas=3, registry=registry)
    prompts = [np.asarray(mock_load_prompt(rng, i), np.int32)
               for i in range(n_requests)]
    router.start()
    for rep in router.replicas.values():
        rep.wait_ready(120.0)

    def killer():
        def armed() -> bool:
            with router._lock:
                return any(f.replica_id == 1 and len(f.front.tokens) > 0
                           for f in router._flights.values())

        if retry.wait_until(armed, 60.0, interval_s=0.005):
            router.replicas[1].kill("bench: injected mid-decode death")

    thread = threading.Thread(target=killer, daemon=True)
    t0 = time.perf_counter()
    thread.start()
    fronts = [router.submit(p, max_new_tokens=max_new) for p in prompts]
    states = [f.wait(240.0).state for f in fronts]
    dt = time.perf_counter() - t0
    thread.join(timeout=5.0)
    completed = sum(1 for s in states if s is RequestState.DONE)
    ledger = router.ledger()
    snap = registry.snapshot()
    lat = snap.get("serve_router_request_latency_s", {})
    ttft = snap.get("serve_router_ttft_s", {})
    itl = snap.get("serve_router_itl_s", {})
    slo_report = router.slo_report()
    router.stop(drain=False)
    return {"bench_router": {
        "n_requests": n_requests,
        "n_replicas": 3,
        "completed": completed,
        "dropped": n_requests - completed,
        "exactly_once": bool(len(ledger) == n_requests
                             and all(v == 1 for v in ledger.values())),
        "failovers": int(snap.get("serve_router_failovers_total", 0)),
        "requests_rerouted": int(
            snap.get("serve_router_requests_rerouted_total", 0)),
        "failover_latency_s": round(
            float(snap.get("serve_router_failover_latency_s", 0.0)), 4),
        "p50_latency_s": round(lat.get("p50", float("nan")), 4),
        "p99_latency_s": round(lat.get("p99", float("nan")), 4),
        "ttft_p50_s": round(ttft.get("p50", float("nan")), 4),
        "ttft_p99_s": round(ttft.get("p99", float("nan")), 4),
        "itl_p50_s": round(itl.get("p50", float("nan")), 4),
        "itl_p99_s": round(itl.get("p99", float("nan")), 4),
        "slo_compliant": bool(slo_report["compliant"]["overall"]),
        "burn_rate_fast": round(slo_report["burn_rate"]["fast"], 3),
        "wall_s": round(dt, 2),
        "device": jax.devices()[0].platform,
    }}


def _run_one(name: str, plan_cache: str = "") -> None:
    """Child mode: measure one workload on the TPU, print its raw dict as
    JSON. Exits ``RC_NO_TPU`` before measuring anything when jax's backend
    is not a TPU whose peak the table lists."""
    import jax

    from autodist_tpu.obs.profiler import peak_flops_for_kind
    from autodist_tpu.utils.compile_cache import enable_compile_cache

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"bench: jax backend is {backend!r}, not 'tpu'; nothing was "
              f"measured", file=sys.stderr)
        sys.exit(RC_NO_TPU)
    try:
        peak_flops_for_kind(jax.devices()[0].device_kind)
    except ValueError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(RC_NO_TPU)
    enable_compile_cache()
    if name == "serve_decode":
        print(json.dumps(_serve_decode_bench()))
    elif name == "serve_decode_quant":
        print(json.dumps(_serve_decode_bench(kv_quant=True)))
    elif name == "serve_router":
        print(json.dumps(_router_bench()))
    else:
        print(json.dumps(measure_workload(name, plan_cache=plan_cache)))


def _emit_postmortem(reason: str, timeout_s: float = 20.0) -> None:
    """When a workload failed, run the postmortem doctor over the run's ft
    artifacts and emit a ``bench_postmortem`` JSON line with the verdict
    code, so a failed run carries a classification and not only an exit
    code (docs/observability.md § doctor).

    Runs ``python -m autodist_tpu.obs doctor`` in a subprocess with a
    timeout after the workloads have exited (it reads files, never the
    chip; the bench parent stays jax-free). Always prints exactly one
    line, BEFORE the final result line so a last-line parse still lands on
    the result.
    """
    import subprocess

    line = {"verdict": "unavailable", "code": "DOC999", "reason": reason}
    try:
        # The launcher exports AUTODIST_FT_DIR to every fleet process;
        # standalone bench runs fall back to the const.py default base
        # (literal here: the parent never imports the package).
        ft_dir = os.environ.get("AUTODIST_FT_DIR") or "/tmp/autodist-tpu/ft"
        line["ft_dir"] = ft_dir
        r = subprocess.run(
            [sys.executable, "-m", "autodist_tpu.obs", "doctor", ft_dir,
             "--json"],
            timeout=timeout_s, capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        doc = _last_json_line(r.stdout)
        if doc is not None:
            line.update({
                "verdict": doc.get("verdict", "unknown"),
                "code": doc.get("code", "DOC999"),
                "evidence": [e.get("detail", "")
                             for e in (doc.get("evidence") or [])[:5]],
                "stats": doc.get("stats", {}),
            })
        else:
            line["error"] = f"doctor exited rc={r.returncode} with no JSON"
    except Exception as e:  # noqa: BLE001 - the postmortem must not crash bench
        line["error"] = f"{type(e).__name__}: {e}"[:200]
    print(json.dumps({"bench_postmortem": line}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model",
                    choices=("bert", "resnet", "bert_large", "both"),
                    default="both")
    ap.add_argument("--one", help=argparse.SUPPRESS)          # child mode
    ap.add_argument(
        "--plan-cache", default="", metavar="DIR",
        help="build strategies through the search-based planner backed by "
             "this persistent plan cache (docs/planner.md); hit/miss counts "
             "are logged in the JSON line so repeated runs show reuse")
    ap.add_argument(
        "--lint", action="store_true",
        help="run the static sharding analyzer (shardlint, docs/analysis.md) "
             "on each workload's compiled program BEFORE any timed window, "
             "emit a bench_lint JSON line at once and put lint_findings "
             "counts in the result line")
    ap.add_argument(
        "--serve", action="store_true",
        help="also run the serve_decode workloads (paged continuous-"
             "batching decode on the tiny selftest engine, fp and int8 "
             "pages) and the 3-replica router failover drill, each emitting "
             "its own bench_serve / bench_router JSON line before the "
             "train workloads")
    ap.add_argument(
        "--attrib", action="store_true",
        help="capture + join a measured-wire attribution "
             "(docs/observability.md § attribution) of one short window "
             "BEFORE any timed window, emit a bench_attrib JSON line at "
             "once and put attrib_* fields in the result line; the full "
             "MeasuredWire JSON lands in a temp dir for "
             "`explain --wire-measured`")
    args = ap.parse_args()
    if args.lint:
        # Env, not a flag, so the child processes (_measure_in_subprocess)
        # inherit the mode without plumbing.
        os.environ["AUTODIST_BENCH_LINT"] = "1"
    if args.attrib:
        os.environ["AUTODIST_BENCH_ATTRIB"] = "1"
    if args.one:
        _run_one(args.one, plan_cache=args.plan_cache)
        return

    per_workload_s = float(os.environ.get("BENCH_WORKLOAD_TIMEOUT", "2400"))
    workloads = (("bert", "resnet", "bert_large") if args.model == "both"
                 else (args.model,))
    serve_arms = ((("serve_decode", "bench_serve"),
                   ("serve_decode_quant", "bench_serve"),
                   ("serve_router", "bench_router")) if args.serve else ())
    measured, errors = {}, {}
    for name, key in serve_arms:
        out, err, rc = _measure_in_subprocess(name, 600.0)
        if rc == RC_NO_TPU:
            sys.exit(RC_NO_TPU)
        if out is not None and key in out:
            print(json.dumps(out), flush=True)
        else:
            errors[name] = err or "no result line"
    for name in workloads:
        out, err, rc = _measure_in_subprocess(
            name, per_workload_s, plan_cache=args.plan_cache)
        if rc == RC_NO_TPU:
            sys.exit(RC_NO_TPU)
        if err is not None:
            errors[name] = err
            print(f"bench[{name}] failed: {err}", file=sys.stderr)
            continue
        measured[name] = out

    if errors:
        # What the fleet's black box says happened, before the (partial)
        # result line — which stays last for a last-line parse.
        _emit_postmortem("; ".join(f"{k}: {v}" for k, v in errors.items()))
    if measured:
        print(json.dumps(_format_result(measured, errors)))
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    main()
