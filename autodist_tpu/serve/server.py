"""Asyncio front end + the ``--selftest`` CPU-sim proof.

The front end is deliberately thin: a dependency-free HTTP/1.1 listener on
``asyncio.start_server`` that bridges requests onto the
:class:`~autodist_tpu.serve.batcher.ContinuousBatcher`'s scheduler thread
(completion callbacks resolve asyncio futures via ``call_soon_threadsafe``
— the event loop never blocks on the device). Routes:

- ``POST /generate`` ``{"tokens": [...], "max_new_tokens": N,
  "timeout_s": T?, "temperature": t?, "top_k": k?, "top_p": p?,
  "seed": s?, "tenant": name?, "request_id": id?}`` →
  ``{"tokens": [...], "state": "done"}``; 429 on backpressure, 400 on an
  unservable request or invalid sampling params (typed
  ``invalid_sampling_params`` — temperature < 0, top_p outside (0, 1],
  top_k < 0 are the client's bug, never a 500). Explicit sampling
  fields override the tenant's defaults (``tenant_defaults``); absent
  both, decode is greedy (serve/sampling.py).
- ``GET /metrics`` → the metrics registry as OpenMetrics text, rendered by
  the one shared exporter (``autodist_tpu.obs.exporter`` — byte-identical
  to the headless file exporter's output on the same snapshot).
- ``GET /healthz`` → typed readiness (``ReplicaState``) + queue/slot
  gauges + page-pool utilization as JSON — **503** while
  ``STARTING``/``DRAINING`` (200 only when READY), so the router and any
  external supervisor probe a replica the same way.
- ``POST /drain`` → run the graceful drain (quiesce → finish in-flight →
  persist leftovers) and report ``{"drained": n, "persisted": n}`` — the
  admin surface a rolling upgrade drives from outside the process.

``python -m autodist_tpu.serve --selftest`` is the zero-hardware proof the
acceptance bar names: a tiny CPU transformer served to >=64 concurrent mock
requests with zero drops/deadlocks, p50/p99 latency and tokens/sec from the
metrics registry, and batched throughput measured strictly above the
sequential single-request baseline.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Dict, List, Optional

import numpy as np

from autodist_tpu import metrics as M
from autodist_tpu.serve.batcher import Backpressure, ContinuousBatcher, RequestState
from autodist_tpu.serve.sampling import InvalidSamplingParams, SamplingParams
from autodist_tpu.utils import logging


async def async_generate(
    batcher: ContinuousBatcher,
    tokens,
    max_new_tokens: int = 32,
    timeout_s: Optional[float] = None,
    request_id: Optional[str] = None,
    sampling: Optional[SamplingParams] = None,
):
    """Submit + await one request from the event loop (shared by the HTTP
    handler and the selftest's mock clients). ``batcher`` is anything
    with the ``submit`` contract (batcher or router); ``request_id`` /
    ``sampling`` forward to it."""
    loop = asyncio.get_running_loop()
    fut: asyncio.Future = loop.create_future()
    req = batcher.submit(tokens, max_new_tokens, timeout_s=timeout_s,
                         request_id=request_id, sampling=sampling)
    req.add_done_callback(
        lambda r: loop.call_soon_threadsafe(
            lambda: fut.done() or fut.set_result(r)))
    return await fut


def parse_sampling(payload: Dict[str, Any],
                   tenant_defaults: Optional[Dict[str, SamplingParams]] = None,
                   ) -> Optional[SamplingParams]:
    """Resolve one request's sampling params at the HTTP edge: explicit
    body fields (``temperature`` / ``top_k`` / ``top_p`` / ``seed``)
    override the ``tenant``'s defaults, which override greedy. Returns
    None (pure greedy) when neither the body nor the tenant says
    anything. Raises :class:`InvalidSamplingParams` on out-of-range or
    non-numeric values — the ONE typed 400, never a 500."""
    tenant = payload.get("tenant")
    base = (tenant_defaults or {}).get(tenant) if tenant else None
    fields = {k: payload[k] for k in ("temperature", "top_k", "top_p", "seed")
              if k in payload}
    if base is None and not fields:
        return None
    doc = (base or SamplingParams()).to_dict()
    doc.update(fields)
    try:
        params = SamplingParams(
            temperature=float(doc["temperature"]), top_k=int(doc["top_k"]),
            top_p=float(doc["top_p"]), seed=int(doc["seed"]))
    except (TypeError, ValueError) as e:
        raise InvalidSamplingParams(f"bad sampling params: {e}") from e
    params.validate()
    return params


class ServeFrontend:
    """Minimal HTTP server over one batcher (optionally one
    :class:`~autodist_tpu.serve.replica.Replica`, which adds typed
    readiness to ``/healthz`` and a real drain to ``POST /drain``)."""

    def __init__(self, batcher: ContinuousBatcher, host: str = "127.0.0.1",
                 port: int = 8476, registry: Optional[M.MetricsRegistry] = None,
                 replica=None,
                 tenant_defaults: Optional[Dict[str, SamplingParams]] = None):
        self._batcher = batcher
        self.host, self.port = host, port
        self.registry = registry or M.registry
        self.replica = replica
        # tenant name -> default SamplingParams; a request's explicit
        # body fields override these (parse_sampling).
        self.tenant_defaults = dict(tenant_defaults or {})
        self._server: Optional[asyncio.AbstractServer] = None

    @property
    def batcher(self) -> Optional[ContinuousBatcher]:
        """The live batcher: a replica swaps its batcher across
        drain/restart cycles, so the frontend always asks it."""
        if self.replica is not None and self.replica.batcher is not None:
            return self.replica.batcher
        return self._batcher

    async def start(self) -> "ServeFrontend":
        if self.replica is not None:
            # Bind the listener BEFORE the (possibly minutes-long) engine
            # build: the whole point of typed STARTING readiness is that
            # a supervisor probing /healthz during the build gets a 503
            # JSON answer, not connection-refused.
            import threading

            threading.Thread(target=self.replica.start,
                             name="replica-start", daemon=True).start()
        else:
            self.batcher.start()
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        addr = self._server.sockets[0].getsockname()
        logging.info("serve frontend listening on %s:%d", *addr[:2])
        return self

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        async with self._server:
            await self._server.serve_forever()

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        if self.replica is not None:
            self.replica.stop()
        elif self.batcher is not None:
            self.batcher.stop()

    # ----------------------------------------------------------------- http
    @staticmethod
    async def _read_request(reader) -> Optional[tuple]:
        line = await reader.readline()
        if not line:
            return None
        try:
            method, path, _ = line.decode().split(None, 2)
        except ValueError:
            return None
        headers: Dict[str, str] = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        body = b""
        n = int(headers.get("content-length", 0) or 0)
        if n:
            body = await reader.readexactly(n)
        return method.upper(), path, headers, body

    @staticmethod
    def _respond(writer, status: int, payload, content_type="application/json"):
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  429: "Too Many Requests", 500: "Internal Server Error",
                  503: "Service Unavailable"}
        body = (json.dumps(payload).encode()
                if content_type == "application/json" else payload.encode())
        writer.write(
            f"HTTP/1.1 {status} {reason.get(status, '')}\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n".encode() + body)

    async def _handle(self, reader, writer) -> None:
        try:
            parsed = await self._read_request(reader)
            if parsed is None:
                return
            method, path, _, body = parsed
            if method == "GET" and path == "/metrics":
                # render_text delegates to THE OpenMetrics renderer
                # (obs/exporter.py) — one rendering path for every surface.
                self._respond(writer, 200, self.registry.render_text(),
                              content_type="text/plain")
            elif method == "GET" and path == "/slo":
                self._slo(writer)
            elif method == "GET" and path == "/healthz":
                self._healthz(writer)
            elif method == "POST" and path == "/drain":
                await self._drain(writer)
            elif method == "POST" and path == "/generate":
                await self._generate(writer, body)
            else:
                self._respond(writer, 404, {"error": f"no route {path}"})
            await writer.drain()
        except Exception as e:  # noqa: BLE001 - per-connection isolation
            try:
                self._respond(writer, 500, {"error": str(e)})
                await writer.drain()
            except Exception:  # noqa: BLE001
                pass
        finally:
            writer.close()

    def _slo(self, writer) -> None:
        """The single-engine ``slo_report`` (docs/serving.md § SLO
        runbook): rendered from the batcher's SLOTracker when one was
        wired (``ContinuousBatcher(slo=...)`` / ``Replica(slo=...)``);
        404 with a pointer otherwise. NaN-safe JSON (json_safe)."""
        from autodist_tpu.obs.slo import json_safe

        batcher = self.batcher
        tracker = getattr(batcher, "slo", None) if batcher else None
        if tracker is None:
            self._respond(writer, 404, {
                "error": "no SLO tracker wired; construct the batcher/"
                         "replica with slo=obs.slo.SLOTracker(spec)"})
            return
        self._respond(writer, 200, json_safe(tracker.report()))

    def _healthz(self, writer) -> None:
        """Typed readiness probe: 200 only when READY; 503 while
        STARTING/DRAINING (or DEAD/SUSPECT) — the router and an external
        supervisor (k8s-style readiness gate) consume the same answer."""
        from autodist_tpu.serve.replica import ReplicaState

        if self.replica is not None:
            doc = self.replica.healthz()
            state = self.replica.state
        else:
            # Batcher-only deployment: derive the readiness the batcher
            # can express (no STARTING phase to observe from here).
            state = (ReplicaState.DRAINING if self.batcher._draining
                     else ReplicaState.READY)
            engine = self.batcher.engine
            doc = {
                "state": state.value,
                "outstanding": self.batcher.outstanding,
                "page_pool_utilization": round(
                    float(getattr(engine, "page_utilization", 0.0)), 4),
            }
        batcher = self.batcher
        doc["ok"] = state is ReplicaState.READY
        doc["queue_depth"] = len(batcher._queue) if batcher else 0
        doc["active_slots"] = (getattr(batcher.engine, "active_slots", 0)
                               if batcher else 0)
        self._respond(writer, 200 if doc["ok"] else 503, doc)

    async def _drain(self, writer) -> None:
        """Admin drain: quiesce → finish in-flight → persist leftovers.
        Runs off the event loop (a drain blocks up to its deadline); the
        response reports what was drained/persisted."""
        if self.replica is not None:
            out = await asyncio.to_thread(self.replica.drain)
        else:
            finished, leftovers = await asyncio.to_thread(self.batcher.drain)
            out = {"drained": finished, "persisted": 0,
                   "preempted": len(leftovers)}
        self._respond(writer, 200, out)

    async def _generate(self, writer, body: bytes) -> None:
        try:
            payload = json.loads(body.decode() or "{}")
            tokens = payload["tokens"]
            max_new = int(payload.get("max_new_tokens", 32))
            sampling = parse_sampling(payload, self.tenant_defaults)
        except InvalidSamplingParams as e:
            # Typed 4xx: invalid sampling params are the client's bug
            # (temperature < 0, top_p outside (0,1], top_k < 0) — never
            # a 500 from deep inside the scheduler.
            self._respond(writer, 400, {
                "error": str(e), "type": "invalid_sampling_params"})
            return
        except (ValueError, KeyError) as e:
            self._respond(writer, 400, {"error": f"bad request body: {e}"})
            return
        batcher = self.batcher
        if batcher is None:
            self._respond(writer, 503,
                          {"error": "replica is not ready (starting or "
                                    "draining)"})
            return
        try:
            req = await async_generate(
                batcher, tokens, max_new,
                timeout_s=payload.get("timeout_s"),
                request_id=payload.get("request_id") or None,
                sampling=sampling)
        except Backpressure as e:
            self._respond(writer, 429, {"error": str(e)})
            return
        except ValueError as e:
            self._respond(writer, 400, {"error": str(e)})
            return
        if req.state is RequestState.REJECTED and req.unservable:
            # Typed admission rejection for an unservable request (over the
            # engine's static max_len) — the client's bug, not load: 400,
            # matching the pre-paging ValueError contract.
            self._respond(writer, 400, {"error": req.error})
            return
        self._respond(writer, 200, {
            "id": req.id,
            "state": req.state.value,
            "tokens": req.tokens,
            "latency_s": req.latency_s,
        })


class RouterFrontend:
    """HTTP front end for the multi-replica control plane
    (:class:`~autodist_tpu.serve.router.Router`): the fleet's single
    client-visible address. Routes:

    - ``POST /generate`` — admitted through the router (journaled,
      exactly-once, failover-transparent); 429 on router backpressure,
      400 on an unservable request.
    - ``GET /metrics`` — the FLEET exposition: the shared registry plus
      per-replica samples labeled ``{replica="<id>"}``
      (``Router.metrics_snapshot``), rendered by the ONE OpenMetrics
      renderer — byte-identical to ``render_openmetrics`` over the same
      snapshot, parseable by the same golden-test parser.
    - ``GET /healthz`` — fleet readiness JSON (per-replica states from
      the router's observer-combined view); 200 while at least one
      replica is READY, 503 otherwise.
    - ``GET /slo`` — the JSON ``slo_report`` (measured TTFT/ITL/queue
      percentiles, burn rates, compliance — docs/serving.md § SLOs).
    """

    def __init__(self, router, host: str = "127.0.0.1", port: int = 8475,
                 tenant_defaults: Optional[Dict[str, SamplingParams]] = None):
        self.router = router
        self.host, self.port = host, port
        self.tenant_defaults = dict(tenant_defaults or {})
        self._server: Optional[asyncio.AbstractServer] = None

    async def start(self) -> "RouterFrontend":
        self.router.start()
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port)
        addr = self._server.sockets[0].getsockname()
        logging.info("router frontend listening on %s:%d", *addr[:2])
        return self

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        async with self._server:
            await self._server.serve_forever()

    async def close(self) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
        self.router.stop()

    async def _handle(self, reader, writer) -> None:
        respond = ServeFrontend._respond
        try:
            parsed = await ServeFrontend._read_request(reader)
            if parsed is None:
                return
            method, path, _, body = parsed
            if method == "GET" and path == "/metrics":
                from autodist_tpu.obs.exporter import render_openmetrics

                respond(writer, 200,
                        render_openmetrics(
                            snapshot=self.router.metrics_snapshot()),
                        content_type="text/plain")
            elif method == "GET" and path == "/healthz":
                self._healthz(writer)
            elif method == "GET" and path == "/slo":
                from autodist_tpu.obs.slo import json_safe

                # json_safe: an empty-window report carries NaN
                # percentiles, and bare NaN is not RFC-8259 JSON.
                respond(writer, 200, json_safe(self.router.slo_report()))
            elif method == "POST" and path == "/generate":
                await self._generate(writer, body)
            else:
                respond(writer, 404, {"error": f"no route {path}"})
            await writer.drain()
        except Exception as e:  # noqa: BLE001 - per-connection isolation
            try:
                respond(writer, 500, {"error": str(e)})
                await writer.drain()
            except Exception:  # noqa: BLE001
                pass
        finally:
            writer.close()

    def _healthz(self, writer) -> None:
        states = {rid: self.router.replica_state(rid).value
                  for rid in sorted(self.router.replicas)}
        ready = sum(1 for s in states.values() if s == "ready")
        doc = {
            "ok": ready >= 1,
            "replicas": {str(k): v for k, v in states.items()},
            "replicas_ready": ready,
            "outstanding": self.router.outstanding,
        }
        ServeFrontend._respond(writer, 200 if doc["ok"] else 503, doc)

    async def _generate(self, writer, body: bytes) -> None:
        respond = ServeFrontend._respond
        try:
            payload = json.loads(body.decode() or "{}")
            tokens = payload["tokens"]
            max_new = int(payload.get("max_new_tokens", 32))
            sampling = parse_sampling(payload, self.tenant_defaults)
        except InvalidSamplingParams as e:
            respond(writer, 400, {
                "error": str(e), "type": "invalid_sampling_params"})
            return
        except (ValueError, KeyError) as e:
            respond(writer, 400, {"error": f"bad request body: {e}"})
            return
        try:
            req = await async_generate(
                self.router, tokens, max_new,
                timeout_s=payload.get("timeout_s"),
                request_id=payload.get("request_id") or None,
                sampling=sampling)
        except Backpressure as e:
            respond(writer, 429, {"error": str(e)})
            return
        except ValueError as e:
            respond(writer, 400, {"error": str(e)})
            return
        if req.state is RequestState.REJECTED and req.unservable:
            respond(writer, 400, {"error": req.error})
            return
        respond(writer, 200, {
            "id": req.request_id,
            "state": req.state.value,
            "tokens": req.tokens,
            "latency_s": req.latency_s,
        })


# ---------------------------------------------------------------- selftest
#: The selftests' pool: 56 pages of 8 positions (the scratch page among
#: them), 448 KV timeline tokens in HBM. The int8 and the prefix selftests
#: size their comparisons off the same pool.
_PAGE_LEN = 8
_N_PAGES = 56


def _tiny_cfg(**overrides):
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import TransformerConfig

    kw = dict(
        vocab_size=128, num_layers=2, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=64, causal=True, dtype=jnp.float32)
    kw.update(overrides)
    return TransformerConfig(**kw)


def _tiny_engine(n_slots: int = 32, page_len: int = _PAGE_LEN,
                 n_pages: Optional[int] = _N_PAGES,
                 prefix_cache: bool = False,
                 kv_quant: bool = False,
                 paged_impl: Optional[str] = None):
    """CPU-sim paged engine: a tiny fp32 transformer through the full
    ``AutoDist.build_inference`` path (strategy → plan → engine).
    Returns ``(engine, params, cfg)`` so callers can hold the engine
    against the model's own forward on the same checkpoint. ``kv_quant``
    serves from int8 KV pages; ``paged_impl`` forces gather/kernel
    (default: the config's measured "auto" — gather on CPU)."""
    import jax

    from autodist_tpu.api import AutoDist
    from autodist_tpu.models.transformer import decode_model, init_params

    overrides = {}
    if kv_quant:
        overrides["kv_quant"] = True
    if paged_impl is not None:
        overrides["paged_attention_impl"] = paged_impl
    cfg = _tiny_cfg(**overrides)
    params = init_params(jax.random.PRNGKey(0), cfg)
    AutoDist.reset_default()
    autodist = AutoDist()
    engine = autodist.build_inference(
        params,
        decode_model=decode_model(cfg),
        n_slots=n_slots,
        page_len=page_len,
        n_pages=n_pages,
        prefill_chunk=page_len,
        prefix_cache=prefix_cache,
    )
    AutoDist.reset_default()
    return engine, params, cfg


def mock_load_prompt(rng, i: Optional[int] = None, long_every: int = 8):
    """The canonical mixed serving load: mostly short chat-style prompts
    with every ``long_every``-th request a long (multi-chunk-prefill)
    one. ONE definition shared by the selftest's acceptance run and
    ``bench.py``'s ``serve_decode`` workload, so the workload the bench
    measures IS the workload the acceptance bar proves."""
    if i is not None and i % long_every == long_every // 2:
        return rng.integers(1, 127, size=int(rng.integers(30, 45)))
    return rng.integers(1, 127, size=int(rng.integers(3, 12)))


def _admission_capacity(engine, prompt_len: int, max_new: int,
                        limit: int = 1024) -> int:
    """How many concurrent requests the engine can hold admitted at once
    (idle probe: reserve until denied, then release everything).
    Admission is page bookkeeping only, so this counts CAPACITY: the HBM
    figure the int8 selftest's >=2x bar compares."""
    from autodist_tpu.serve.engine import AdmissionDenied

    held = []
    prompt = np.arange(1, prompt_len + 1, dtype=np.int32)
    for _ in range(limit):
        got = engine.admit(prompt, max_new)
        if isinstance(got, AdmissionDenied):
            break
        held.append(got)
    for slot in held:
        engine.release(slot)
    return len(held)


#: Documented logit-drift bound for int8 KV pages vs the fp oracle
#: (teacher-forced max |Δlogit| on the tiny selftest model; docs/serving.md
#: § quantized pages). tests/test_paged_kernel.py asserts the same bound.
QUANT_LOGIT_DRIFT_BOUND = 0.05


def _quant_logit_drift(params, cfg, page_len: int = _PAGE_LEN,
                       steps: int = 6) -> float:
    """Teacher-forced max |logit| drift of int8 KV pages vs the fp oracle.

    Both caches replay the SAME token history (the fp oracle's stream), so
    the number is pure quantization error, not divergence compounding. The
    probe runs the model functions directly — never the engine's compiled
    programs, so the 2-program pin is untouched.
    """
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import (
        forward_paged_decode_step, forward_paged_prefill_chunk,
        init_paged_kv_cache)

    prompt = np.arange(1, page_len + 1, dtype=np.int32)  # one full page
    table_row = jnp.asarray(np.array([1, 2, 3, 4], np.int32))
    caches = [init_paged_kv_cache(cfg, 6, page_len, quantized=q)
              for q in (False, True)]
    tok = jnp.asarray(prompt[None, :], jnp.int32)
    token = None
    for i in range(2):
        nt, caches[i] = forward_paged_prefill_chunk(
            params, tok, 0, len(prompt), caches[i], table_row, cfg)
        if i == 0:
            token = nt                       # the fp oracle drives both
    tables = table_row[None, :]
    pos = len(prompt)
    drift = 0.0
    for _ in range(steps):
        step_logits = []
        for i in range(2):
            nt, lg, caches[i] = forward_paged_decode_step(
                params, token, jnp.asarray([pos], jnp.int32), caches[i],
                tables, cfg, return_logits=True)
            step_logits.append(lg)
            if i == 0:
                next_token = nt
        drift = max(drift, float(jnp.max(jnp.abs(
            step_logits[0] - step_logits[1]))))
        token, pos = next_token, pos + 1
    return drift


def uncached_greedy(params, cfg, prompt, n_new: int) -> List[int]:
    """The parity oracle (the selftest's and the serving tests'): the whole
    sequence through ``transformer.forward`` for every token, argmax at the
    frontier. It shares no cache code with the engine it is held against.
    The sequence rides in one padded ``[1, cfg.max_seq_len]`` buffer, so
    every step runs the same compiled operations; under the causal mask the
    padding past the frontier cannot reach the frontier's logits."""
    import jax.numpy as jnp

    from autodist_tpu.models.transformer import forward

    seq = [int(t) for t in prompt]
    for _ in range(n_new):
        padded = np.zeros((1, cfg.max_seq_len), np.int32)
        padded[0, :len(seq)] = seq
        logits = forward(params, jnp.asarray(padded), cfg)
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[len(prompt):]


def selftest(n_requests: int = 64, n_slots: int = 32, max_new: int = 12,
             seed: int = 0, kv_quant: bool = False) -> int:
    """The acceptance proof; returns a process exit code.

    Phase 0 (parity): on shared prompts (short, page-crossing,
    multi-chunk) the engine's greedy token streams must equal, token for
    token, a greedy loop over the uncached ``transformer.forward`` on the
    SAME checkpoint, which recomputes the whole sequence for every token
    and shares no cache code with the engine.
    Phase 1 (sequential baseline): single requests generated back-to-back
    through the paged engine. Phase 2 (batched): ``n_requests``
    concurrent mock clients — mixed short and long (chunked-prefill)
    prompts — through the asyncio bridge and the continuous batcher.
    Asserts zero dropped/deadlocked requests, batched tokens/sec strictly
    above sequential, bit-identical streams from the pallas paged-
    attention kernel (interpret mode) vs the gather path, and exactly TWO
    compiled serving programs (one decode + one chunked prefill) after the
    whole mixed-length run, then prints one JSON line with p50/p99 latency
    and throughput from the metrics registry.

    ``kv_quant=True`` runs the quantized acceptance instead (int8 KV
    pages): >=2x admitted concurrency at equal pool bytes vs fp pages
    with prefix sharing on, zero dropped, logit drift within
    :data:`QUANT_LOGIT_DRIFT_BOUND`, kernel-vs-gather stream identity on
    the SAME quantized pages, and the analyzer pricing quantized bytes.
    """
    if kv_quant:
        return _selftest_quant(n_requests=n_requests, max_new=max_new,
                               seed=seed)
    registry = M.MetricsRegistry()
    rng = np.random.default_rng(seed)
    engine, params, cfg = _tiny_engine(n_slots=n_slots)
    paged_pool_tokens = engine.pool.n_pages * engine.page_len

    # ---- how many short requests (6 prompt + 6 new) the pool admits.
    paged_cap = _admission_capacity(engine, 6, 6)

    # ---- greedy bit-equality with the uncached forward on the same
    # checkpoint (short, page-crossing, multi-chunk prompts).
    parity_prompts = [
        np.array([5, 17, 3, 88, 2], np.int32),
        rng.integers(1, 127, size=20).astype(np.int32),   # crosses pages
        rng.integers(1, 127, size=41).astype(np.int32),   # many chunks
    ]
    parity_ok = all(
        engine.generate(p, 10) == uncached_greedy(params, cfg, p, 10)
        for p in parity_prompts)

    # ---- pallas kernel vs gather: bit-identical streams on the same
    # checkpoint (interpret mode on CPU — the same kernel logic the TPU
    # compiles; ops/paged_attention.py). Small engine: the interpreted
    # grid walks (rows x pages) in Python.
    kernel_engine, _, _ = _tiny_engine(n_slots=4, paged_impl="kernel")
    kernel_parity_ok = all(
        engine.generate(p, 10) == kernel_engine.generate(p, 10)
        for p in parity_prompts)

    def mock_prompt(i=None):
        return mock_load_prompt(rng, i)

    # Warm the compile caches outside both timed phases (compile time is a
    # one-off; the throughput comparison is about steady-state batching).
    engine.generate(mock_prompt(), max_new)

    t0 = time.monotonic()
    seq_tokens = 0
    for _ in range(8):
        seq_tokens += len(engine.generate(mock_prompt(), max_new))
    seq_tps = seq_tokens / (time.monotonic() - t0)

    batcher = ContinuousBatcher(engine, max_queue=max(n_requests, 64),
                                registry=registry)

    async def run_clients():
        async def client(i):
            # Stagger arrivals slightly: a realistic open-loop trickle, and
            # it exercises admission racing retirement.
            await asyncio.sleep(0.001 * (i % 8))
            return await async_generate(batcher, mock_prompt(i), max_new)

        return await asyncio.gather(*(client(i) for i in range(n_requests)))

    batcher.start()
    t1 = time.monotonic()
    try:
        results = asyncio.run(asyncio.wait_for(run_clients(), timeout=300))
    finally:
        batcher.stop(drain=False)
    dt_batched = time.monotonic() - t1

    batched_tokens = sum(len(r.tokens) for r in results)
    batched_tps = batched_tokens / dt_batched
    states = {s: sum(1 for r in results if r.state is s) for s in RequestState}
    snap = registry.snapshot()
    lat = snap.get("serve_request_latency_s", {})
    programs = engine.compiled_programs
    ok = (
        states.get(RequestState.DONE, 0) == n_requests
        and batched_tps > seq_tps
        and parity_ok
        and kernel_parity_ok
        and programs == 2
    )
    line = {
        "selftest": "autodist_tpu.serve",
        "ok": bool(ok),
        "n_requests": n_requests,
        "completed": states.get(RequestState.DONE, 0),
        "dropped": n_requests - states.get(RequestState.DONE, 0),
        "p50_latency_s": round(lat.get("p50", float("nan")), 4),
        "p99_latency_s": round(lat.get("p99", float("nan")), 4),
        "batched_tokens_per_sec": round(batched_tps, 1),
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "speedup": round(batched_tps / seq_tps, 2) if seq_tps else None,
        "tokens_generated": int(snap.get("serve_tokens_generated_total", 0)),
        "queue_depth_final": int(snap.get("serve_queue_depth", 0)),
        "paged_capacity": paged_cap,
        "kv_pool_tokens": paged_pool_tokens,
        "paged_vs_uncached_forward_bit_equal": bool(parity_ok),
        "kernel_vs_gather_bit_equal": bool(kernel_parity_ok),
        "kv_quant": "off",
        "programs_compiled": programs,
        "page_len": engine.page_len,
        "n_pages": engine.pool.n_pages,
        "n_slots": engine.n_slots,
        "device": __import__("jax").devices()[0].platform,
    }
    print(json.dumps(line))
    if not ok:
        logging.warning(
            "selftest failed: states=%s seq=%.1f batched=%.1f "
            "parity=%s kernel_parity=%s programs=%d",
            {s.value: n for s, n in states.items() if n},
            seq_tps, batched_tps, parity_ok, kernel_parity_ok, programs)
    return 0 if ok else 1


def _selftest_quant(n_requests: int = 64, max_new: int = 12,
                    seed: int = 0) -> int:
    """The int8-KV-pages acceptance proof (``--selftest --kv-quant``).

    An fp paged engine (the oracle) and a quantized engine sized to the
    SAME pool bytes — equal HBM — both with COW prefix sharing on. The
    quantized pool funds ~3.2x the pages (int8 + f32 scales vs f32 KV at
    head_dim 16), which must buy >=2x admitted concurrency; the batched
    phase must complete every request (zero dropped); teacher-forced
    logit drift vs the fp oracle stays within
    :data:`QUANT_LOGIT_DRIFT_BOUND`; the pallas kernel over the SAME
    quantized pages streams bit-identically to the quantized gather; the
    analyzer's memory pass prices the PHYSICAL quantized bytes with the
    capacity multiplier annotated; and the program pin (exactly 2) holds
    on the quantized engine.
    """
    import jax

    from autodist_tpu.analysis.passes import hbm_budget
    from autodist_tpu.models.transformer import init_paged_kv_cache

    registry = M.MetricsRegistry()
    rng = np.random.default_rng(seed)
    n_slots = 96   # past both pools' page capacity: pages are the binding
    #                constraint the equal-bytes comparison measures.
    fp_engine, params, cfg = _tiny_engine(
        n_slots=n_slots, prefix_cache=True)
    fp_pool_bytes = fp_engine.page_pool_bytes

    # Size the quantized pool to the fp pool's byte budget.
    quant_page_bytes = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(jax.eval_shape(
            lambda: init_paged_kv_cache(cfg, 1, _PAGE_LEN,
                                        quantized=True))))
    n_pages_q = int(fp_pool_bytes // quant_page_bytes)
    engine, _, qcfg = _tiny_engine(
        n_slots=n_slots, n_pages=n_pages_q, kv_quant=True,
        prefix_cache=True)
    equal_bytes_ok = engine.page_pool_bytes <= fp_pool_bytes

    # ---- admitted concurrency at equal pool bytes (6 prompt + 6 new).
    quant_cap = _admission_capacity(engine, 6, 6)
    fp_cap = _admission_capacity(fp_engine, 6, 6)
    concurrency_x = quant_cap / max(fp_cap, 1)

    # ---- teacher-forced logit drift vs the fp oracle.
    drift = _quant_logit_drift(params, cfg)
    drift_ok = drift < QUANT_LOGIT_DRIFT_BOUND

    # ---- kernel vs gather over the SAME quantized pages: bit-identical
    # streams (interpret mode on CPU).
    parity_prompts = [
        np.array([5, 17, 3, 88, 2], np.int32),
        rng.integers(1, 127, size=20).astype(np.int32),
        rng.integers(1, 127, size=41).astype(np.int32),
    ]
    kernel_engine, _, _ = _tiny_engine(
        n_slots=4, kv_quant=True, paged_impl="kernel")
    gather_small, _, _ = _tiny_engine(n_slots=4, kv_quant=True)
    kernel_parity_ok = all(
        gather_small.generate(p, 10) == kernel_engine.generate(p, 10)
        for p in parity_prompts)

    # ---- analyzer accounting: the pool tenant carries the PHYSICAL
    # quantized bytes; the capacity multiplier rides the summary.
    _, mem = hbm_budget(
        engine.plan, serve_pool_bytes=engine.page_pool_bytes,
        serve_quant_capacity_x=engine.quant_capacity_x)
    analyzer_ok = (
        abs(mem["serve_pool_gb_per_chip"] * 1e9
            - engine.page_pool_bytes) < 1.0
        and mem["serve_quant_capacity_x"] >= 2.0)

    # ---- batched phase through the quantized engine: zero dropped.
    def mock_prompt(i=None):
        return mock_load_prompt(rng, i)

    engine.generate(mock_prompt(), max_new)   # warm the compile caches
    batcher = ContinuousBatcher(engine, max_queue=max(n_requests, 64),
                                registry=registry)

    async def run_clients():
        async def client(i):
            await asyncio.sleep(0.001 * (i % 8))
            return await async_generate(batcher, mock_prompt(i), max_new)

        return await asyncio.gather(*(client(i) for i in range(n_requests)))

    batcher.start()
    try:
        results = asyncio.run(asyncio.wait_for(run_clients(), timeout=300))
    finally:
        batcher.stop(drain=False)
    states = {s: sum(1 for r in results if r.state is s)
              for s in RequestState}
    programs = engine.compiled_programs
    snap = registry.snapshot()
    ok = (
        states.get(RequestState.DONE, 0) == n_requests
        and equal_bytes_ok
        and concurrency_x >= 2.0
        and drift_ok
        and kernel_parity_ok
        and analyzer_ok
        and programs == 2
    )
    line = {
        "selftest": "autodist_tpu.serve.kv_quant",
        "ok": bool(ok),
        "kv_quant": "on",
        "n_requests": n_requests,
        "completed": states.get(RequestState.DONE, 0),
        "dropped": n_requests - states.get(RequestState.DONE, 0),
        "pool_bytes": int(engine.page_pool_bytes),
        "fp_pool_bytes": int(fp_pool_bytes),
        "n_pages_quant": engine.pool.n_pages,
        "n_pages_fp": fp_engine.pool.n_pages,
        "quant_capacity_x": round(engine.quant_capacity_x, 2),
        "quant_capacity": quant_cap,
        "fp_capacity": fp_cap,
        "concurrency_x_vs_fp": round(concurrency_x, 2),
        "logit_drift": round(drift, 5),
        "logit_drift_bound": QUANT_LOGIT_DRIFT_BOUND,
        "kernel_vs_gather_bit_equal": bool(kernel_parity_ok),
        "analyzer_prices_quant": bool(analyzer_ok),
        "programs_compiled": programs,
        "quant_pool_gauge_bytes": float(snap.get(
            "serve_page_pool_physical_bytes", 0.0)),
        "page_len": engine.page_len,
        "device": jax.devices()[0].platform,
    }
    print(json.dumps(line))
    if not ok:
        logging.warning(
            "kv-quant selftest failed: states=%s equal_bytes=%s "
            "concurrency_x=%.2f drift=%.5f kernel_parity=%s analyzer=%s "
            "programs=%d",
            {s.value: n for s, n in states.items() if n}, equal_bytes_ok,
            concurrency_x, drift, kernel_parity_ok, analyzer_ok, programs)
    return 0 if ok else 1
