"""Continuous batching: bounded admission queue + paged slot scheduler.

The serving analog of the training data pipeline's "keep the device fed"
contract. Requests enter a bounded FIFO (``submit`` raises
:class:`Backpressure` when full — admission control, never silent drops);
a single scheduler thread assembles the active batch dynamically under
**page availability** (admission reserves a request's whole
``prompt + max_new`` timeline in the engine's page pool, all-or-nothing),
advances every mid-prefill request by one fixed-size chunk per tick —
chunked prefill interleaved with decode, so a long prompt never stalls
in-flight decodes — runs ONE decode step per tick across every decoding
slot, and retires sequences the moment they finish (EOS /
``max_new_tokens`` / deadline), recycling their pages in the same tick —
no batch barrier, a request never waits for its batchmates (Orca-style
iteration-level scheduling over a vLLM-style paged cache).

Admission is typed end to end: a request that can NEVER run (over the
engine's static ``max_len`` ceiling) comes back from ``submit`` already
terminal ``REJECTED`` — impossibility is a value at the edge, not an
exception and never a stuck queue head; a request the pool cannot place
YET stays queued (retirement frees pages), with pool pressure
flight-recorded so the postmortem doctor's timeline shows when the pool —
not the queue bound — was the limiter. Progress is guaranteed by
construction: every admitted sequence has a finite timeline
(``max_new_tokens`` bounds it even if EOS never fires), so pages always
recycle; liveness is a property, not a tuning outcome — the
``--selftest`` acceptance bar (zero dropped/deadlocked) tests it.

Metrics (through :mod:`autodist_tpu.metrics`' registry):
``serve_queue_depth`` / ``serve_active_slots`` /
``serve_page_pool_utilization`` / ``serve_page_fragmentation`` /
``serve_param_bytes`` (the engine's placed parameter tree) and, for a
model with per-slot state, ``serve_ssm_state_bytes`` gauges,
``serve_requests_{submitted,completed,timeout,rejected}_total`` counters,
``serve_tokens_generated_total`` / ``serve_decode_tokens_generated_total``
counters (a rate is their increase over the reader's own interval), and
``serve_request_latency_s`` / ``serve_ttft_s`` / ``serve_itl_s`` histograms
(p50/p99 exported by the registry; ``serve_itl_s`` observes every
inter-token gap, from ``GenRequest.t_tokens``). Engines exposing ``spec_stats()``
(speculative decode, serve/spec.py) additionally publish
``serve_spec_acceptance_rate`` / ``serve_spec_tokens_per_step`` and feed
the SLO tracker's rolling acceptance window; decode rounds then emit
0..k+1 tokens per slot, truncated at EOS / ``max_new_tokens`` /
deadline exactly where plain decode would have stopped.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, Dict, List, Optional

import numpy as np

from autodist_tpu import metrics as M
from autodist_tpu.obs import recorder as obs_recorder
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.serve import sampling as serve_sampling
from autodist_tpu.serve.engine import (
    AdmissionDenied,
    EngineDeadError,
    InferenceEngine,
    Slot,
)
from autodist_tpu.utils import logging, retry


class Backpressure(RuntimeError):
    """Admission queue full — the client should retry/shed (HTTP 429)."""


class RequestState(Enum):
    QUEUED = "queued"
    ACTIVE = "active"
    DONE = "done"
    TIMEOUT = "timeout"
    REJECTED = "rejected"
    # Terminal because the SERVER is shutting down, not because the request
    # failed: the ft drain controller persists these for replay on restart
    # (autodist_tpu/ft/drain.py).
    PREEMPTED = "preempted"


_ids = itertools.count()


@dataclass
class GenRequest:
    """One generation request and its lifecycle."""

    prompt: np.ndarray
    max_new_tokens: int
    deadline: Optional[float] = None      # absolute time.monotonic() cutoff
    id: int = field(default_factory=lambda: next(_ids))
    # Stable identity for journaling/dedupe across process boundaries
    # (ft/drain.py format v2, serve/router.py exactly-once): unlike the
    # in-process ``id`` counter, it survives persist/replay and lets two
    # journals recognize the same failed-over request.
    request_id: str = ""
    t_submit: float = field(default_factory=time.monotonic)
    t_admit: Optional[float] = None        # engine admission (slot granted)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    queue_wait_s: Optional[float] = None   # submit -> engine admission
    # True when admission mapped a cached prefix onto shared pages
    # (serve/prefix.py): the batcher splits TTFT attribution on it, so a
    # hit-rate shift can't silently mask a prefill regression.
    cached: bool = False
    # Stochastic sampling params (serve/sampling.py); None means greedy.
    # Rides the request into engine admission (per-slot arrays), the
    # router journal and the drain journal — a replayed stream re-derives
    # the identical draws from (request_id, seed, position) alone.
    sampling: Optional[serve_sampling.SamplingParams] = None
    tokens: List[int] = field(default_factory=list)
    # time.monotonic() at which each entry of ``tokens`` was appended by
    # the scheduler (tokens of one spec round share a tick and lie
    # microseconds apart); the inter-token gaps are its differences.
    t_tokens: List[float] = field(default_factory=list)
    state: RequestState = RequestState.QUEUED
    error: str = ""
    # Typed rejection cause: True when the request can NEVER be served by
    # this engine (over the static max_len ceiling) — the front end maps
    # it to HTTP 400 and the drain replay drops it, WITHOUT parsing the
    # error prose (the AdmissionDenied.retryable contract, kept typed all
    # the way to the edge).
    unservable: bool = False
    _event: threading.Event = field(default_factory=threading.Event, repr=False)

    def __post_init__(self):
        if not self.request_id:
            self.request_id = f"g{os.getpid()}-{self.id}"
    _cb_lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    _callbacks: List[Callable[["GenRequest"], None]] = field(
        default_factory=list, repr=False)

    def wait(self, timeout: Optional[float] = None) -> "GenRequest":
        """Block until terminal; returns self (check ``state``)."""
        self._event.wait(timeout)
        return self

    @property
    def done(self) -> bool:
        return self._event.is_set()

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft_s(self) -> Optional[float]:
        """Time from engine ADMISSION to first token (None until one was
        delivered). Admission-relative, not submit-relative: queue wait
        is reported separately (``queue_wait_s``), so a cached-prefix
        admission whose prefill collapses to one chunk reports its true
        prefill latency instead of inheriting the queue backlog — and a
        0/1-chunk path can no longer record a degenerate
        queue_wait/prefill split (ISSUE 16). Falls back to submit when
        the request never went through ``admit`` (direct construction)."""
        if self.t_first_token is None:
            return None
        base = self.t_admit if self.t_admit is not None else self.t_submit
        return self.t_first_token - base

    @property
    def itl_s(self) -> Optional[float]:
        """Mean inter-token latency over the decode phase (needs a
        terminal request with >= 2 tokens). The single gaps are the
        differences of ``t_tokens``; ``serve_itl_s`` observes each."""
        if (self.t_done is None or self.t_first_token is None
                or len(self.tokens) < 2):
            return None
        return (self.t_done - self.t_first_token) / (len(self.tokens) - 1)

    def add_done_callback(self, fn: Callable[["GenRequest"], None]) -> None:
        """Run ``fn(request)`` on completion, from the scheduler thread —
        the asyncio bridge (the server wraps it in call_soon_threadsafe).
        Fires immediately if already terminal. The lock closes the
        check-then-append race against a concurrent ``_finish``: without
        it, a request finishing between the two would strand the callback
        unfired (a hung HTTP client)."""
        with self._cb_lock:
            if not self._event.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def _finish(self, state: RequestState, error: str = "") -> None:
        with self._cb_lock:
            if self._event.is_set():
                # Already terminal: first writer wins. Closes the race
                # where a drain/stop whose scheduler join TIMED OUT
                # preempts a request whose in-flight tick then completes —
                # without this, the late DONE would overwrite PREEMPTED
                # after the drain controller persisted it for replay
                # (a double-serve on restart).
                return
            self.state = state
            self.error = error
            self.t_done = time.monotonic()
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            try:
                fn(self)
            except Exception:  # noqa: BLE001 - a bad callback can't kill the loop
                logging.warning("request %d done-callback raised", self.id,
                                exc_info=True)


def make_rejected(prompt, max_new_tokens: int, error: str,
                  request_id: Optional[str] = None,
                  sampling: Optional[serve_sampling.SamplingParams] = None,
                  ) -> GenRequest:
    """Build an already-terminal typed-``REJECTED`` request — the ONE
    rendering of the typed-shed fallback (``try_submit`` here and on the
    router), so the contract's prose and coercion rules cannot drift."""
    try:
        arr = np.asarray(prompt, np.int32).ravel()
    except (TypeError, ValueError):
        arr = np.zeros(0, np.int32)
    req = GenRequest(prompt=arr, max_new_tokens=max_new_tokens,
                     request_id=request_id or "", sampling=sampling)
    req._finish(RequestState.REJECTED, f"admission rejected: {error}")
    return req


class ContinuousBatcher:
    """Request queue + scheduler around one paged :class:`InferenceEngine`.

    ``max_queue`` bounds admission (backpressure). The active batch is
    bounded by the engine itself — decode rows and page-pool capacity —
    so there is no separate token budget to tune: what HBM actually holds
    IS the admission limit. ``start()`` spawns the scheduler thread;
    ``submit`` is thread-safe and wakes it.
    """

    def __init__(
        self,
        engine: InferenceEngine,
        max_queue: int = 256,
        registry: Optional[M.MetricsRegistry] = None,
        on_tick: Optional[Callable[[float], None]] = None,
        slo=None,
    ):
        if engine.decode_model is None:
            raise ValueError("ContinuousBatcher needs an engine with a "
                             "decode_model")
        self.engine = engine
        self.max_queue = max_queue
        # Optional obs.slo.SLOTracker: fed TTFT/ITL/queue-wait at retire
        # and sheds at the admission edge, so a single-engine deployment
        # renders the same slo_report the router does fleet-wide.
        self.slo = slo
        # Scheduler-tick duration observer (seconds per progressing tick):
        # the replica wrapper (serve/replica.py) feeds these into its
        # obs.aggregate.HostAggregator so the router's straggler scores
        # see real per-replica step times.
        self.on_tick = on_tick
        self._queue: deque[GenRequest] = deque()
        self._active: Dict[Slot, GenRequest] = {}
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._running = False
        self._stopped = False
        self._draining = False  # quiesced: no new admissions, finish active
        self._thread: Optional[threading.Thread] = None
        self._shed_lock = threading.Lock()
        self._shed_last = -1e9   # monotonic stamp of the last shed
        self._shed_count = 0
        self._pressure_last = -1e9  # last pool-pressure flight event
        self._SHED_WINDOW_S = 1.0
        # Per-instance shed-record source: replay keys cumulative-delta
        # arithmetic by it (an in-process fleet runs several batchers).
        self._shed_src = f"batcher-{next(_ids)}"
        self._tick_seq = 0          # progressing ticks (flight sampling)

        # Speculative-decode accounting (engines exposing spec_stats()):
        # cumulative snapshot for delta arithmetic + lazily-registered
        # gauges, so plain engines add no metric families.
        self._spec_last: Dict[str, int] = {}
        # Per-temperature-bucket cumulative high-water marks mirroring
        # _spec_last: the SLO tracker wants per-tick deltas per bucket.
        self._spec_last_bucket: Dict[str, Dict[str, int]] = {}
        self._m_spec_accept = None
        self._m_spec_tps = None

        # Prefix-cache accounting (engines built with prefix_cache=...,
        # serve/prefix.py): cached/uncached TTFT split + hit-rate /
        # shared-pages / sharing-ratio gauges, lazily registered so plain
        # engines add no metric families.
        self._m_ttft_cached = None
        self._m_ttft_uncached = None
        self._m_prefix_hit = None
        self._m_prefix_shared = None
        self._m_sharing_ratio = None

        # Quantized-pool accounting (int8 KV pages, ops/paged_attention.py):
        # physical vs fp-equivalent byte split, lazily registered so fp
        # engines add no metric families.
        self._m_quant_capacity = None
        self._m_quant_physical = None
        self._m_quant_fp_equiv = None

        # Window-ring accounting (a model whose CacheLayout states a
        # window, serve/pages.py): lazily registered so plain paged
        # engines add no metric families.
        self._m_rolls = None
        self._m_rolls_decode = None
        self._m_summaries = None
        self._m_ring_pages = None
        self._m_summary_pages = None
        # The paged kernel's walk over the plain timeline (engine
        # ``kv_groups`` / ``kv_groups_live``), lazily registered likewise.
        self._m_kv_groups = None
        self._m_kv_groups_live = None
        # What only the device knows of a decode step (the engine's
        # ``fact_totals``, a model's ``step_facts``), lazily registered.
        self._m_facts = None
        # Rows whose per-slot state the decode steps updated (the engine's
        # ``ssm_rows``), lazily registered.
        self._m_ssm_rows = None
        # Arrays put on the device for the programs' inputs (the engine's
        # ``input_puts``), lazily registered.
        self._m_input_puts = None

        reg = registry or M.registry
        self._registry = reg
        self._m_depth = reg.gauge("serve_queue_depth")
        self._m_active = reg.gauge("serve_active_slots")
        self._m_pool_util = reg.gauge("serve_page_pool_utilization")
        self._m_frag = reg.gauge("serve_page_fragmentation")
        self._m_submitted = reg.counter("serve_requests_submitted_total")
        self._m_completed = reg.counter("serve_requests_completed_total")
        self._m_timeout = reg.counter("serve_requests_timeout_total")
        self._m_rejected = reg.counter("serve_requests_rejected_total")
        self._m_tokens = reg.counter("serve_tokens_generated_total")
        self._m_decode_tokens = reg.counter(
            "serve_decode_tokens_generated_total")
        self._m_latency = reg.histogram("serve_request_latency_s")
        self._m_ttft = reg.histogram("serve_ttft_s")
        self._m_itl = reg.histogram("serve_itl_s")
        # The bytes of the parameter tree the engine placed for its
        # programs (a model's ``serving_params`` of the caller's, or it).
        reg.gauge("serve_param_bytes").set(
            float(getattr(engine, "param_bytes", 0)))
        # ... and of the per-slot state placed beside the pool, where the
        # model carries one.
        if getattr(engine, "ssm_state_bytes", 0):
            reg.gauge("serve_ssm_state_bytes").set(float(engine.ssm_state_bytes))

    # ---------------------------------------------------------------- clients
    def submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        sampling: Optional[serve_sampling.SamplingParams] = None,
    ) -> GenRequest:
        """Enqueue a request. Raises :class:`Backpressure` when the queue
        is at ``max_queue`` (or the batcher is stopped/draining). A
        request that can NEVER be placed — over the engine's static
        ``max_len`` ceiling — comes back already terminal
        ``RequestState.REJECTED`` with the reason in ``.error``: a typed
        admission rejection at the edge, not an exception and never a
        stuck queue head. ``timeout_s`` sets the request deadline
        relative to now; ``request_id`` carries a caller-assigned stable
        identity (router journaling, drain replay dedupe); ``sampling``
        carries stochastic params (validated HERE, at the edge — invalid
        params raise :class:`~autodist_tpu.serve.sampling.
        InvalidSamplingParams`, a ValueError, never a scheduler crash)."""
        prompt = np.asarray(prompt, np.int32).ravel()
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if sampling is not None:
            sampling.validate()
        req = GenRequest(
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            deadline=(time.monotonic() + timeout_s) if timeout_s else None,
            request_id=request_id or "",
            sampling=sampling,
        )
        denied = self.engine.check_admissible(len(prompt), max_new_tokens)
        if denied is not None:
            self._m_rejected.inc()
            self._shed("unservable request")
            req.unservable = True
            req._finish(RequestState.REJECTED,
                        f"admission rejected: {denied.reason}")
            return req
        shed_reason = None
        with self._wake:
            if self._stopped:
                # Accepting work that will never run would hang the client
                # in wait() forever. (Pre-start submission is fine — the
                # queue drains once start() runs.)
                shed_reason = "batcher is stopped"
            elif self._draining:
                # Graceful shutdown in progress: shed at the edge so the
                # client retries against the replacement server.
                shed_reason = "batcher is draining"
            elif len(self._queue) >= self.max_queue:
                shed_reason = (
                    f"admission queue full ({self.max_queue} requests)")
            else:
                self._queue.append(req)
                self._m_submitted.inc()
                self._m_depth.set(len(self._queue))
                self._wake.notify()
        if shed_reason is not None:
            self._m_rejected.inc()
            self._shed(shed_reason)
            raise Backpressure(shed_reason)
        return req

    def try_submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        timeout_s: Optional[float] = None,
        request_id: Optional[str] = None,
        sampling: Optional[serve_sampling.SamplingParams] = None,
    ) -> GenRequest:
        """Admission that degrades *typed* instead of raising: always
        returns a :class:`GenRequest`. A shed request comes back already
        terminal — ``state == RequestState.REJECTED`` with the reason in
        ``.error`` — so load-shedding under chaos (engine death, admission
        stalls, page-pool bursts, queue overflow) is a value the caller
        can route on, never a hang and never an anonymous exception
        (docs/chaos.md). Invalid sampling params land here too — a typed
        REJECTED, which the HTTP edge maps to a 4xx."""
        try:
            return self.submit(prompt, max_new_tokens, timeout_s=timeout_s,
                               request_id=request_id, sampling=sampling)
        except (Backpressure, ValueError) as e:
            return make_rejected(prompt, max_new_tokens, str(e),
                                 request_id=request_id, sampling=sampling)

    def submit_with_retry(
        self,
        prompt,
        max_new_tokens: int = 32,
        timeout_s: Optional[float] = None,
        policy: Optional[retry.RetryPolicy] = None,
    ) -> GenRequest:
        """Client-side admission under backpressure through the ONE retry
        layer (utils/retry.py): jittered-exponential re-submission until
        admitted or the policy's deadline/attempt budget is spent (the
        final :class:`Backpressure` then propagates)."""
        policy = policy or retry.RetryPolicy(
            initial_s=0.02, max_s=1.0, max_attempts=8, deadline_s=10.0)
        try:
            return retry.retry_call(
                lambda: self.submit(prompt, max_new_tokens,
                                    timeout_s=timeout_s),
                policy=policy, retry_on=(Backpressure,),
                describe="serve admission")
        except retry.RetryError as e:
            raise Backpressure(str(e)) from e.__cause__

    def _shed(self, reason: str) -> None:
        """Black-box a load-shedding decision. One flight event opens each
        shed window (rejections less than ``_SHED_WINDOW_S`` apart share
        it), so the postmortem doctor's timeline shows *when* the server
        was refusing work without a per-rejection fsync storm."""
        now = time.monotonic()
        with self._shed_lock:
            # Fixed windows (advance _shed_last only when one OPENS): a
            # sustained >1-event/s storm must keep emitting one record
            # per window — a debounce that slides on every event would
            # record only the storm's first shed, and the postmortem
            # replay (obs/slo.py) would recover 1 shed from a 100s storm.
            opens = now - self._shed_last > self._SHED_WINDOW_S
            if opens:
                self._shed_last = now
            self._shed_count += 1
            n = self._shed_count
        if opens:
            # src keys the replay's cumulative-delta arithmetic: router
            # and batcher counters are independent even in one process.
            obs_recorder.record_event("shed", critical=False,
                                      src=self._shed_src,
                                      reason=reason, total_shed=n,
                                      pool_free_pages=getattr(
                                          self.engine, "pool", None)
                                      and self.engine.pool.free_pages)
        if self.slo is not None:
            self.slo.observe(ok=False, shed=True)

    def _pool_pressure(self, reason: str) -> None:
        """Flight-record page-pool pressure (rate-limited like ``_shed``):
        admission is deferring because HBM pages — not the queue bound —
        are the limiter. Retirement recycles pages, so this is a signal,
        not a failure; the doctor's timeline shows the pressure window."""
        now = time.monotonic()
        with self._shed_lock:
            # Fixed windows, like _shed: sustained pressure keeps
            # emitting one record per window (the doctor's DOC007
            # abrupt-end check reads the pressure TAIL).
            opens = now - self._pressure_last > self._SHED_WINDOW_S
            if opens:
                self._pressure_last = now
        if opens:
            obs_recorder.record_event(
                "pool_pressure", critical=False, reason=reason,
                free_pages=self.engine.pool.free_pages,
                used_pages=self.engine.pool.used_pages,
                queue_depth=len(self._queue))

    # -------------------------------------------------------------- accounting
    @property
    def stopped(self) -> bool:
        """True once the scheduler will never run again (orderly stop OR
        engine death) — the replica's supervision reads it to notice a
        batcher that died out from under a READY replica."""
        with self._lock:
            return self._stopped

    @property
    def outstanding(self) -> int:
        """Queued + active request count — the router's
        least-outstanding-work routing currency (also published in the
        replica heartbeat payload)."""
        with self._lock:
            return len(self._queue) + len(self._active)

    # -------------------------------------------------------------- lifecycle
    def start(self) -> "ContinuousBatcher":
        with self._lock:
            if self._running:
                return self
            self._running = True
            self._stopped = False
            self._draining = False
        self._thread = threading.Thread(
            target=self._loop, name="serve-batcher", daemon=True)
        self._thread.start()
        return self

    def stop(self, drain: bool = True, timeout_s: float = 30.0) -> None:
        """Stop the scheduler; ``drain=True`` finishes in-flight + queued
        work first (bounded by each request's own limits). Whatever is
        still undone when the scheduler exits — drain disabled, drain
        timeout, or work submitted before start() of a batcher that never
        started — is failed terminally, so no client ever blocks in
        ``wait()`` on a request nobody will run."""
        if drain and self._thread is not None:
            def idle() -> bool:
                with self._lock:
                    return not self._queue and not self._active

            retry.wait_until(idle, timeout_s, interval_s=0.01)
        with self._wake:
            self._running = False
            self._stopped = True
            self._wake.notify()
        stuck = self._join_scheduler(timeout_s)
        self._fail_all("batcher stopped before this request completed",
                       release=not stuck)

    def _join_scheduler(self, timeout_s: float) -> bool:
        """Join the scheduler thread; True when it OUTLIVED the timeout
        (blocked in a device call — first-tick compile, wedged chip).
        A live scheduler still owns the engine's single-writer state, so
        the caller must not touch slot tables or release pages: leaking
        them to process teardown beats corrupting a dispatch mid-flight
        (or a double page free racing the stuck tick's own retire)."""
        thread = self._thread
        if thread is None:
            return False
        thread.join(timeout=timeout_s)
        self._thread = None
        if thread.is_alive():
            logging.warning(
                "serve scheduler still running after %.1fs join; leaving "
                "engine slot state to it (pages reclaimed at teardown)",
                timeout_s)
            return True
        return False

    def die(self, reason: str) -> None:
        """Abrupt-death path (replica kill, chaos): shed ALL queued and
        in-flight work with typed ``REJECTED`` results carrying an
        engine-death reason, flight-record the error for the postmortem
        doctor, and stop — the same contract the scheduler's own
        ``EngineDeadError`` handler keeps, callable from outside the
        scheduler thread (``serve/replica.py``'s ``kill()``). Idempotent;
        never blocks a client."""
        with self._wake:
            already = self._stopped
            self._running = False
            self._stopped = True
            self._wake.notify()
        if already:
            return
        obs_recorder.record_event(
            "error", error=f"EngineDeadError: {reason}"[:500])
        self._shed(f"engine dead: {reason}")
        stuck = self._join_scheduler(2.0)
        self._fail_all(f"engine died mid-decode: {reason}",
                       release=not stuck)

    def quiesce(self) -> None:
        """Stop admitting — new ``submit``s are refused and queued entries
        are no longer promoted to slots — while active decodes keep
        stepping. The first phase of a graceful drain (ft/drain.py)."""
        with self._wake:
            self._draining = True
            self._wake.notify()

    def drain(self, deadline_s: float = 30.0):
        """Graceful shutdown: quiesce, let in-flight decodes finish within
        ``deadline_s``, then stop the scheduler.

        Returns ``(n_finished_during_drain, leftovers)`` where
        ``leftovers`` are the requests this process will never run — the
        untouched queue plus any decode the deadline cut off — each
        already finished terminally as :attr:`RequestState.PREEMPTED` (so
        no client blocks forever). The caller decides their fate; the ft
        :class:`~autodist_tpu.ft.drain.DrainController` persists them for
        exactly-once replay on restart.
        """
        before = self._m_completed.value
        self.quiesce()
        if self._thread is not None:
            def no_active() -> bool:
                with self._lock:
                    return not self._active

            retry.wait_until(no_active, deadline_s, interval_s=0.005)
        with self._wake:
            self._running = False
            self._stopped = True
            self._wake.notify()
        stuck = self._join_scheduler(max(1.0, deadline_s))
        with self._lock:
            active = list(self._active.items())
            self._active.clear()
            leftovers = list(self._queue)
            self._queue.clear()
            self._m_depth.set(0)
            self._m_active.set(0)
        if not stuck:
            for slot, _req in active:
                self.engine.release(slot)
        leftovers = [req for _, req in active] + leftovers
        for req in leftovers:
            req._finish(RequestState.PREEMPTED,
                        "server draining; request persisted for replay")
        finished = int(self._m_completed.value - before)
        return finished, leftovers

    def __enter__(self) -> "ContinuousBatcher":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -------------------------------------------------------------- scheduler
    def _loop(self) -> None:
        while True:
            with self._wake:
                if not self._running:
                    break
                if not self._queue and not self._active:
                    self._wake.wait(timeout=0.5)
                    continue
            try:
                t_tick = time.monotonic()
                with obs_spans.span("serve.tick", seq=self._tick_seq) as tick:
                    progressed = tick["progressed"] = self._tick()
                if progressed:
                    self._tick_seq += 1
                    if self._tick_seq % 32 == 1:
                        # Sampled per-tick engine flight record: occupancy,
                        # prefill/decode mix, pool utilization, tick wall —
                        # the serve-side stream the SLO/sentry/doctor layer
                        # reads (1-in-32 keeps the recorder overhead bound).
                        obs_recorder.record_step(
                            surface="serve", event="tick",
                            tick_wall_s=round(
                                time.monotonic() - t_tick, 6),
                            active=getattr(self.engine, "active_slots", 0),
                            prefilling=getattr(
                                self.engine, "prefilling_slots", 0),
                            decoding=getattr(
                                self.engine, "decoding_slots", 0),
                            pool_utilization=round(float(getattr(
                                self.engine, "page_utilization", 0.0)), 4),
                            queue_depth=len(self._queue))
                if progressed and self.on_tick is not None:
                    with obs_spans.span("serve.on_tick"):
                        try:
                            self.on_tick(time.monotonic() - t_tick)
                        except Exception:  # noqa: BLE001 - observer only
                            logging.warning("on_tick observer raised",
                                            exc_info=True)
                if not progressed:
                    # Queue non-empty but nothing progressed (a page-
                    # pressure window with an empty active set, or a
                    # drain with untouched leftovers): pace the poll
                    # instead of spinning a core — retirement/submit
                    # notify the condition, so 20 ms is a backstop, not
                    # the latency floor.
                    with self._wake:
                        if self._running:
                            self._wake.wait(timeout=0.02)
            except EngineDeadError as e:
                # The engine cannot decode anymore: shed ALL load with
                # explicit typed rejections (never hang a client on a dead
                # engine), black-box the death for the postmortem doctor,
                # and stop admitting — the replacement server takes over.
                logging.error("engine died mid-decode; shedding all work: %s",
                              e)
                obs_recorder.record_event(
                    "error", error=f"EngineDeadError: {e}"[:500])
                self._shed(f"engine dead: {e}")
                with self._wake:
                    self._running = False
                    self._stopped = True
                self._fail_all(f"engine died mid-decode: {e}")
                break
            except Exception:  # noqa: BLE001 - scheduler must survive
                # A tick failure (e.g. transient compile/OOM) fails the
                # requests it touched via _fail_all below rather than
                # killing the loop silently.
                logging.warning("batcher tick failed", exc_info=True)
                self._fail_all("scheduler tick failed; see server log")

    def _fail_all(self, msg: str, release: bool = True) -> None:
        """Terminally fail everything. ``release=False`` when a LIVE
        scheduler thread may still own the engine (post-join-timeout
        stop): requests still unblock — ``_finish`` is first-writer-wins
        — but slot state is left to the thread that owns it."""
        with self._lock:
            active = list(self._active.items())
            self._active.clear()
            queued = list(self._queue)
            self._queue.clear()
            self._m_depth.set(0)
        for slot, req in active:
            if release:
                self.engine.release(slot)
            req._finish(RequestState.REJECTED, msg)
        for req in queued:
            req._finish(RequestState.REJECTED, msg)
        self._m_rejected.inc(len(active) + len(queued))

    def _tick(self) -> bool:
        """One scheduler iteration: expire → admit → prefill → decode →
        retire. Returns whether anything progressed (admission, a prefill
        chunk, a decode step, an expiry) — False lets the loop pace
        itself instead of spinning on a blocked queue."""
        progress = False
        now = time.monotonic()

        # Queued requests whose deadline already passed will only get staler
        # waiting for pages: time them out from the queue.
        with self._lock:
            expired = [r for r in self._queue
                       if r.deadline is not None and now > r.deadline]
            for r in expired:
                self._queue.remove(r)
            self._m_depth.set(len(self._queue))
        for r in expired:
            self._m_timeout.inc()
            progress = True
            r._finish(RequestState.TIMEOUT, "deadline expired in queue")

        # Admission: FIFO while the engine can place the head. admit() is
        # host bookkeeping only (page + row reservation — prefill compute
        # happens chunk-by-chunk below), and runs OUTSIDE self._lock: only
        # this scheduler thread ever pops, so the peeked head is stable,
        # and submit()/the asyncio event loop never block on it.
        if self._queue:
            with obs_spans.span("serve.admit"):
                progress = self._admit_queued() or progress

        progress = self._prefill_round() or progress
        progress = self._decode_round() or progress
        with obs_spans.span("serve.tick_metrics") as sp:
            self._update_spec_metrics()
            self._update_prefix_metrics()
            self._update_quant_metrics()
            self._update_ring_metrics(sp)
            self._update_kv_group_metrics()
            self._update_step_fact_metrics(sp)
            self._update_ssm_metrics(sp)
            self._update_input_put_metrics(sp)
            with self._lock:
                self._m_active.set(len(self._active))
            self._m_pool_util.set(self.engine.page_utilization)
            self._m_frag.set(self.engine.page_fragmentation)
        return progress

    def _admit_queued(self) -> bool:
        """The admission loop of one tick (host-only reservation); returns
        whether anything was admitted, rejected or timed out."""
        progress = False
        while True:
            dead = None
            with self._lock:
                if self._draining or not self._queue:
                    # Draining: queued entries stay untouched for the drain
                    # controller to persist; only active slots keep stepping.
                    break
                head = self._queue[0]
                if head.deadline is not None and time.monotonic() > head.deadline:
                    # Submitted after this tick's expiry sweep: never admit
                    # an already-dead request. (_finish runs outside the
                    # lock — a done-callback may re-enter submit.)
                    dead = self._queue.popleft()
                    self._m_depth.set(len(self._queue))
            if dead is not None:
                self._m_timeout.inc()
                progress = True
                dead._finish(RequestState.TIMEOUT, "deadline expired in queue")
                continue
            t_admit, t_admit_wall = time.monotonic(), time.time()
            admitted = self.engine.admit(head.prompt, head.max_new_tokens,
                                         request_id=head.request_id,
                                         sampling=head.sampling)
            if isinstance(admitted, AdmissionDenied):
                if admitted.retryable:
                    # Pages/rows will free on retirement; keep it queued
                    # and flight-record the pressure window.
                    self._pool_pressure(admitted.reason)
                    break
                with self._lock:
                    self._queue.popleft()
                    self._m_depth.set(len(self._queue))
                self._m_rejected.inc()
                progress = True
                head.unservable = True
                head._finish(RequestState.REJECTED,
                             f"admission rejected: {admitted.reason}")
                continue
            # Queue-wait span, recorded retroactively now the wait is known
            # (submit → admission; the prefill-chunk spans follow on the
            # same timeline, so a request reads wait → prefill → decode).
            wait_s = max(t_admit - head.t_submit, 0.0)
            head.queue_wait_s = wait_s
            head.t_admit = t_admit
            obs_spans.add_span("serve.queue_wait", t_admit_wall - wait_s,
                               wait_s, request_id=head.request_id)
            with self._lock:
                self._queue.popleft()
                self._m_depth.set(len(self._queue))
                head.state = RequestState.ACTIVE
                self._active[admitted] = head
            progress = True
        return progress

    def _prefill_round(self) -> bool:
        """Chunked prefill: every mid-prefill slot advances ONE chunk per
        tick, so a long prompt interleaves with (never stalls) the decode
        round. The first generated token arrives with the final chunk —
        prefill emits it, exactly like the unpaged design."""
        progress = False
        for slot in self.engine.prefill_pending():
            with self._lock:
                req = self._active.get(slot)
            if req is None:
                continue
            if req.deadline is not None and time.monotonic() > req.deadline:
                self._retire(slot, req, RequestState.TIMEOUT,
                             "deadline expired mid-prefill")
                progress = True
                continue
            first = self.engine.prefill_step(slot)
            progress = True
            if first is None:
                continue
            with obs_spans.span("serve.emit"):
                req.t_first_token = time.monotonic()
                self._append_token(req, first, req.t_first_token)
                # cached flag is read BEFORE release resets the slot
                # arrays; it rides the request for the retire-time flight
                # record/SLO.
                slot_cached = getattr(self.engine, "slot_cached", None)
                req.cached = (bool(slot_cached(slot))
                              if callable(slot_cached) else False)
                ttft = req.ttft_s
                self._m_ttft.observe(ttft)
                if getattr(self.engine, "prefix_cache", None) is not None:
                    if self._m_ttft_cached is None:
                        self._m_ttft_cached = self._registry.histogram(
                            "serve_ttft_cached_s")
                        self._m_ttft_uncached = self._registry.histogram(
                            "serve_ttft_uncached_s")
                    (self._m_ttft_cached if req.cached
                     else self._m_ttft_uncached).observe(ttft)
                self._m_tokens.inc(1)
                self._maybe_retire(slot, req)
        return progress

    def _decode_round(self) -> bool:
        """One decode round over every decoding slot (ONE compiled program
        — plain greedy emits one token per slot; a speculative round
        emits 1..k+1 greedy-identical tokens per slot). Tokens are
        appended one at a time so EOS / max_new_tokens / deadline
        truncate a multi-token burst at exactly the token plain decode
        would have stopped on — the engine's overshoot is discarded
        with the retiring slot."""
        with self._lock:
            if not self._active:
                return False
        emitted = self.engine.step_many()
        if not emitted:
            return False
        with obs_spans.span("serve.emit"):
            n_appended = 0
            eos = self.engine.decode_model.eos_id
            for slot, tokens in emitted.items():
                with self._lock:
                    req = self._active.get(slot)
                if req is None:
                    continue
                for token in tokens:
                    now = time.monotonic()
                    self._append_token(req, token, now)
                    n_appended += 1
                    if (len(req.tokens) >= req.max_new_tokens
                            or (eos is not None and token == eos)):
                        break
                    # Deadline parity with plain decode: one round past
                    # an expired deadline still lands its (first) token,
                    # then the request times out — the burst's remaining
                    # tokens are exactly the ones plain decode would
                    # never have computed.
                    if req.deadline is not None and now > req.deadline:
                        break
                self._maybe_retire(slot, req)
            self._m_tokens.inc(n_appended)
            self._m_decode_tokens.inc(n_appended)
        return True

    def _append_token(self, req: GenRequest, token: int, now: float) -> None:
        """Deliver one token: stamp its time on the request and observe
        the gap since the request's previous token (``serve_itl_s`` holds
        every inter-token gap, so one stalled tick shows in its tail)."""
        if req.t_tokens:
            self._m_itl.observe(now - req.t_tokens[-1])
        req.tokens.append(token)
        req.t_tokens.append(now)

    def _update_spec_metrics(self) -> None:
        """Publish speculative-decode gauges + feed the SLO tracker's
        acceptance window from the engine's cumulative ``spec_stats()``
        (delta arithmetic per tick). No-op on plain engines — the
        ``serve_spec_*`` families exist only where spec decode runs, so a
        spec-decode replica's ``GET /metrics`` carries its acceptance
        rate per replica (the router-side context for SNT007-009: a
        low-acceptance replica legitimately runs at plain-decode cadence,
        which is load shape, not sickness)."""
        stats_fn = getattr(self.engine, "spec_stats", None)
        if not callable(stats_fn):
            return
        stats = stats_fn()
        if self._m_spec_accept is None:
            self._m_spec_accept = self._registry.gauge(
                "serve_spec_acceptance_rate")
            self._m_spec_tps = self._registry.gauge(
                "serve_spec_tokens_per_step")
        self._m_spec_accept.set(float(stats.get("acceptance_rate", 0.0)))
        self._m_spec_tps.set(float(stats.get("tokens_per_round", 0.0)))
        if self.slo is not None:
            d_prop = int(stats.get("proposed", 0)) - self._spec_last.get(
                "proposed", 0)
            d_acc = int(stats.get("accepted", 0)) - self._spec_last.get(
                "accepted", 0)
            if d_prop > 0:
                self.slo.observe(spec_proposed=d_prop, spec_accepted=d_acc)
            # Same delta arithmetic per temperature bucket: a bucketed
            # observe feeds ONLY that bucket's window (the blended call
            # above already counted these proposals once).
            for b, bs in (stats.get("by_temperature") or {}).items():
                last = self._spec_last_bucket.get(
                    b, {"proposed": 0, "accepted": 0})
                bp = int(bs.get("proposed", 0))
                ba = int(bs.get("accepted", 0))
                if bp - last["proposed"] > 0:
                    self.slo.observe(spec_proposed=bp - last["proposed"],
                                     spec_accepted=ba - last["accepted"],
                                     spec_bucket=b)
                self._spec_last_bucket[b] = {"proposed": bp, "accepted": ba}
        self._spec_last = {"proposed": int(stats.get("proposed", 0)),
                           "accepted": int(stats.get("accepted", 0))}

    def _update_prefix_metrics(self) -> None:
        """Publish prefix-sharing gauges from the engine's cumulative
        ``prefix_stats()`` (serve/prefix.py). No-op on engines without a
        prefix cache — the ``serve_prefix_*`` / sharing-ratio families
        exist only where sharing runs. ``serve_page_pool_utilization``
        already reports PHYSICAL (deduped) pages — the pool allocates
        each shared page once and the tree owns it — so the sharing
        ratio (logical/physical) is the one extra gauge the accounting
        needs for SLM001/002 agreement."""
        cache = getattr(self.engine, "prefix_cache", None)
        if cache is None:
            return
        if self._m_prefix_hit is None:
            self._m_prefix_hit = self._registry.gauge(
                "serve_prefix_hit_rate")
            self._m_prefix_shared = self._registry.gauge(
                "serve_prefix_shared_pages")
            self._m_sharing_ratio = self._registry.gauge(
                "serve_page_pool_sharing_ratio")
        stats = self.engine.prefix_stats()
        self._m_prefix_hit.set(float(stats.get("hit_rate", 0.0)))
        self._m_prefix_shared.set(float(stats.get("shared_pages", 0)))
        self._m_sharing_ratio.set(
            float(getattr(self.engine, "sharing_ratio", 1.0)))

    def _update_ring_metrics(self, sp) -> None:
        """Publish what a window ring adds to the pool's accounting, from
        the engine's cumulative counts. No-op on a plain paged timeline —
        the families exist only where a model states a ring, mirroring
        the spec/prefix/quant pattern. Counters: ``serve_window_rolls_total``
        (a row's position reached a multiple of the window, in a prefill
        chunk or a decode step), ``serve_window_rolls_decode_total`` (the
        decode step's part of them), ``serve_summary_chunks_total`` (chunk
        summaries made). Gauges: ``serve_ring_pages_in_use`` and
        ``serve_summary_pages_in_use``, the two kinds of page behind
        ``serve_page_pool_utilization``. The same five readings ride the
        ``serve.tick_metrics`` span, so a reader of the span ring has the
        counters on the spans' clock. There is no ``serve.window_roll``
        span: the host does nothing at a boundary."""
        layout = getattr(self.engine, "layout", None)
        if layout is None or not layout.window:
            return
        if self._m_rolls is None:
            reg = self._registry
            self._m_rolls = reg.counter("serve_window_rolls_total")
            self._m_rolls_decode = reg.counter(
                "serve_window_rolls_decode_total")
            self._m_summaries = reg.counter("serve_summary_chunks_total")
            self._m_ring_pages = reg.gauge("serve_ring_pages_in_use")
            self._m_summary_pages = reg.gauge("serve_summary_pages_in_use")
        eng = self.engine
        for counter, now in ((self._m_rolls, eng.window_rolls),
                             (self._m_rolls_decode, eng.window_rolls_decode),
                             (self._m_summaries, eng.summary_chunks)):
            counter.inc(now - counter.value)
        ring, summary = eng.ring_pages_in_use, eng.summary_pages_in_use
        self._m_ring_pages.set(ring)
        self._m_summary_pages.set(summary)
        sp["window_rolls"] = eng.window_rolls
        sp["window_rolls_decode"] = eng.window_rolls_decode
        sp["summary_chunks"] = eng.summary_chunks
        sp["ring_pages"] = ring
        sp["summary_pages"] = summary

    def _update_kv_group_metrics(self) -> None:
        """Publish how often the paged kernel's skip engages, from the
        engine's cumulative counts: ``serve_kv_groups_total`` (page groups
        of the rows' tables in a layer's call of every decode step and
        prefill chunk dispatched) and ``serve_kv_groups_live_total``
        (those of them at or under a query's position: the rest are
        skipped whole). The per-call readings ride the
        ``serve.decode_dispatch`` and ``serve.prefill_chunk`` spans as
        ``kv_groups`` / ``kv_groups_live``. No-op over a window ring, whose
        kernel counts its own segments."""
        eng = self.engine
        layout = getattr(eng, "layout", None)
        if layout is None or layout.window:
            return
        if self._m_kv_groups is None:
            self._m_kv_groups = self._registry.counter(
                "serve_kv_groups_total")
            self._m_kv_groups_live = self._registry.counter(
                "serve_kv_groups_live_total")
        for counter, now in ((self._m_kv_groups, eng.kv_groups),
                             (self._m_kv_groups_live, eng.kv_groups_live)):
            counter.inc(now - counter.value)

    def _update_step_fact_metrics(self, sp) -> None:
        """Publish what only the device knows of the decode steps (a
        model's ``step_facts``, which reach the host with the tokens), from
        the engine's cumulative counts: a counter ``serve_<fact>_total`` a
        fact (``serve_moe_pairs_total``: token-expert pairs that fell on
        the experts this chip holds; ``serve_moe_experts_hit_total``: held
        experts with at least one pair, summed over the expert layers) and
        the decode steps they were summed over, under the name the model
        states (``steps_fact``: ``serve_moe_steps_total``). The cumulative
        readings ride the
        ``serve.tick_metrics`` span under the counters' names without
        ``serve_`` and ``_total``; a step's own are on its
        ``serve.decode_step`` span and a chunk's on its
        ``serve.prefill_chunk`` span. No-op for a model that states none."""
        eng = self.engine
        facts = getattr(eng, "step_facts", ())
        if not facts:
            return
        readings = dict(eng.fact_totals)
        if eng.steps_fact:
            readings[eng.steps_fact] = eng.fact_steps
        if self._m_facts is None:
            self._m_facts = {
                name: self._registry.counter(f"serve_{name}_total")
                for name in readings}
        for name, now in readings.items():
            self._m_facts[name].inc(now - self._m_facts[name].value)
            sp[name] = now

    def _update_ssm_metrics(self, sp) -> None:
        """Publish ``serve_ssm_rows_total``: rows whose per-slot state the
        decode steps dispatched updated, from the engine's cumulative count
        (a step's own rides its ``serve.decode_dispatch`` span as
        ``ssm_rows``; the cumulative reading rides ``serve.tick_metrics``).
        No-op for a model that carries no per-slot state."""
        eng = self.engine
        if not getattr(eng, "ssm_state_bytes", 0):
            return
        if self._m_ssm_rows is None:
            self._m_ssm_rows = self._registry.counter("serve_ssm_rows_total")
        self._m_ssm_rows.inc(eng.ssm_rows - self._m_ssm_rows.value)
        sp["ssm_rows"] = eng.ssm_rows

    def _update_input_put_metrics(self, sp) -> None:
        """Publish ``serve_input_puts_total``: arrays the engine put on the
        device for its programs' inputs, from its cumulative count (a
        call's own rides its ``serve.decode_dispatch`` /
        ``serve.prefill_chunk`` span as ``puts``; the cumulative reading
        rides ``serve.tick_metrics`` as ``input_puts``). No-op for an
        engine that keeps no such count."""
        puts = getattr(self.engine, "input_puts", None)
        if puts is None:
            return
        if self._m_input_puts is None:
            self._m_input_puts = self._registry.counter(
                "serve_input_puts_total")
        self._m_input_puts.inc(puts - self._m_input_puts.value)
        sp["input_puts"] = puts

    def _update_quant_metrics(self) -> None:
        """Publish the physical-vs-quantized pool byte split. No-op on fp
        engines — the ``serve_page_pool_physical_bytes`` /
        ``..._fp_equiv_bytes`` / ``..._quant_capacity_x`` families exist
        only where int8 KV pages run, mirroring the spec/prefix gauge
        pattern. Physical bytes are what the chip actually holds (and what
        SLM001 accounts); fp-equiv is the same KV capacity priced at the
        model's fp cache dtype, so capacity_x = fp_equiv / physical is the
        quantization win the admission headroom actually gained."""
        if not bool(getattr(self.engine, "kv_quant", False)):
            return
        if self._m_quant_capacity is None:
            self._m_quant_capacity = self._registry.gauge(
                "serve_page_pool_quant_capacity_x")
            self._m_quant_physical = self._registry.gauge(
                "serve_page_pool_physical_bytes")
            self._m_quant_fp_equiv = self._registry.gauge(
                "serve_page_pool_fp_equiv_bytes")
        self._m_quant_capacity.set(float(self.engine.quant_capacity_x))
        self._m_quant_physical.set(float(self.engine.page_pool_bytes))
        self._m_quant_fp_equiv.set(
            float(self.engine.page_pool_fp_equiv_bytes))

    def _maybe_retire(self, slot: Slot, req: GenRequest) -> None:
        """Finish + recycle the slot's pages when the sequence is done.

        Liveness needs no per-bucket defensive bound anymore: admission
        reserved the full ``prompt + max_new`` timeline in pages, and
        ``max_new_tokens`` retires the sequence before its last write
        could leave that reservation."""
        now = time.monotonic()
        eos = self.engine.decode_model.eos_id
        state = None
        if req.deadline is not None and now > req.deadline:
            state, why = RequestState.TIMEOUT, "deadline expired mid-decode"
        elif eos is not None and req.tokens and req.tokens[-1] == eos:
            state, why = RequestState.DONE, ""
        elif len(req.tokens) >= req.max_new_tokens:
            state, why = RequestState.DONE, ""
        if state is None:
            return
        self._retire(slot, req, state, why)

    def _retire(self, slot: Slot, req: GenRequest, state: RequestState,
                why: str) -> None:
        with self._lock:
            self._active.pop(slot, None)
        self.engine.release(slot)
        (self._m_timeout if state is RequestState.TIMEOUT
         else self._m_completed).inc()
        req._finish(state, why)
        self._m_latency.observe(time.monotonic() - req.t_submit)
        itl = req.itl_s
        # One request-level flight record: the SLO inputs (TTFT, ITL,
        # queue wait, outcome) survive the process — obs/slo.py's
        # replay_flight_records recomputes the SLO position postmortem.
        temp = (float(req.sampling.temperature)
                if req.sampling is not None else 0.0)
        obs_recorder.record_step(
            surface="serve", event="request", request_id=req.request_id,
            state=state.value, n_tokens=len(req.tokens),
            ttft_s=req.ttft_s, itl_s=itl, queue_wait_s=req.queue_wait_s,
            cached=req.cached, temperature=temp)
        if self.slo is not None:
            # itl_tokens weights the sample by the inter-token gaps it
            # summarizes: a multi-token spec round must not let a long
            # request count the same as a 2-token one in the ITL
            # percentiles (per-TOKEN attribution, not per-step/request).
            self.slo.observe(ttft_s=req.ttft_s, itl_s=itl,
                             itl_tokens=max(len(req.tokens) - 1, 1),
                             queue_wait_s=req.queue_wait_s,
                             ok=state is RequestState.DONE,
                             cached=req.cached, temperature=temp)
        with self._wake:
            self._wake.notify()  # pages freed: admission may proceed
