"""Host-driven asynchronous parameter server (``sync=False`` rendering).

The reference's async PS let every worker push its gradient into the
server's optimizer the moment it was ready, with no barrier against the
other workers, and pull whatever parameters the server currently held
(ps_synchronizer.py:553-630; synchronizers.proto:28). That machine model
has no rendering *inside* an SPMD program — every device in a jitted
program is lockstep by construction — but the asynchrony never lived in
the kernels in the reference either: it lived in the host-side dispatch
schedule. This module renders exactly that part:

- One canonical parameter store (:class:`ParamServer`) owns params +
  optimizer slots behind a lock, with a monotonically increasing
  ``version`` (one bump per applied push).
- ``n_workers`` logical workers each loop pull → grad → push. A push
  applies immediately through the jitted optimizer update — no
  accumulation, no waiting for peers — so updates interleave and every
  worker computes gradients against parameters that may be stale by the
  other workers' pushes. This is the reference's async semantics.
- ``staleness=K > 0`` bounds the lag (SSP): a push whose snapshot is more
  than K versions behind is REJECTED (the gradient is dropped) and the
  worker re-pulls and recomputes on fresh parameters — stale work is
  discarded, never applied. ``staleness=0`` means unbounded (pure async),
  matching the reference's default.

Compute still runs on the device through ordinary jitted functions —
gradients ride the MXU; only the *schedule* is host-driven. On a single
chip, worker dispatches serialize on the device queue (the semantics —
interleaved, stale updates — are unchanged); on a multi-device host each
worker is pinned round-robin to a device. Multi-host asynchrony would
need a parameter RPC service, which this framework deliberately does not
ship — the SPMD collectives path (``sync=True``) is the scalable product
path; async PS exists for semantic parity and staleness research. See
docs/async_ps.md.

Two schedules:

- ``schedule="threads"`` (production): real OS threads, genuinely
  nondeterministic interleaving (jax dispatch releases the GIL).
- ``schedule="round_robin"`` (tests/debug): the same pull/push loop run
  deterministically on the calling thread — all workers pull a snapshot,
  then push in worker order. Reproducible stale-gradient dynamics.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
import optax

from autodist_tpu import metrics as M
from autodist_tpu.utils import logging


@dataclass
class AsyncServerState:
    """Canonical server-side training state (params live HERE, not on the
    workers — the defining PS property; reference ps_strategy.py:38-55)."""

    params: Any
    opt_state: Any
    version: int = 0


@dataclass
class AsyncMetrics:
    """Per-push records, in apply order."""

    losses: List[float] = field(default_factory=list)
    lags: List[int] = field(default_factory=list)       # version - snapshot
    workers: List[int] = field(default_factory=list)    # who pushed
    wall_s: float = 0.0

    @property
    def max_lag(self) -> int:
        return max(self.lags) if self.lags else 0

    def summary(self) -> Dict[str, float]:
        return {
            "pushes": len(self.losses),
            "last_loss": self.losses[-1] if self.losses else float("nan"),
            "max_lag": self.max_lag,
            "pushes_per_sec": (len(self.losses) / self.wall_s)
            if self.wall_s > 0 else float("nan"),
        }


class ParamServer:
    """The shared store. ``pull`` returns a snapshot + its version;
    ``push`` applies one worker's gradient immediately (async apply)."""

    def __init__(self, params, tx: optax.GradientTransformation,
                 staleness: int = 0, device=None,
                 state: Optional[AsyncServerState] = None):
        self._tx = tx
        self._lock = threading.Lock()
        # The server owns ONE device; params + slots live there, and every
        # push transfers the worker's gradient onto it — that transfer IS
        # the worker→server wire of the reference's PS.
        self._device = device if device is not None else jax.local_devices()[0]
        if state is not None:
            # Adopt a restored state as-is (checkpoint resume): no fresh
            # tx.init / params copy — Adam-sized slot allocations on resume
            # would be pure waste.
            self.state = state
        else:
            params = jax.device_put(params, self._device)
            self.state = AsyncServerState(
                params=params, opt_state=tx.init(params))
        self.staleness = int(staleness)
        self.metrics = AsyncMetrics()
        # One jitted update shared by every push. NO buffer donation here:
        # pulled snapshots alias the server's buffers, so donating would
        # delete arrays workers are still computing against (async pulls
        # outlive the next apply by design).
        def _apply(params, opt_state, grads):
            updates, new_opt = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        self._apply = jax.jit(_apply)

    # ------------------------------------------------------------- protocol
    def pull(self):
        with self._lock:
            return self.state.params, self.state.version

    def push(self, grads, snapshot_version: int, worker: int,
             loss: Optional[float] = None) -> int:
        """Apply ``grads`` computed against ``snapshot_version``. Returns
        the new version, or -1 if the snapshot exceeds the staleness bound
        (SSP): the gradient is dropped and the caller must re-pull and
        recompute. With ``staleness=0`` every push applies (pure async,
        the reference default)."""
        with self._lock:
            lag = self.state.version - snapshot_version
            if self.staleness > 0 and lag > self.staleness:
                # Too stale to apply: in SSP the slow worker REFRESHES
                # rather than poisoning the model with an ancient gradient.
                # The caller re-pulls and recomputes; we record the drop.
                logging.debug(
                    "async-ps: worker %d snapshot v%d is %d > K=%d behind; "
                    "re-pull", worker, snapshot_version, lag, self.staleness)
                return -1
            self.state.params, self.state.opt_state = self._apply(
                self.state.params, self.state.opt_state,
                jax.device_put(grads, self._device))
            self.state.version += 1
            if loss is not None:
                self.metrics.losses.append(float(loss))
            self.metrics.lags.append(lag)
            self.metrics.workers.append(worker)
            return self.state.version


class AsyncPSTrainer:
    """User-facing async trainer; returned by ``AutoDist.build`` when the
    compiled strategy carries ``sync=False`` PS nodes.

    API mirrors the synchronous :class:`DistributedTrainStep` where the
    concepts map: ``init`` builds server state, ``run`` executes a fixed
    number of *pushes* (the async analog of steps), returning
    ``(state, metrics)``.
    """

    def __init__(
        self,
        loss_fn: Callable,
        tx: optax.GradientTransformation,
        n_workers: int,
        staleness: int = 0,
        schedule: str = "threads",
        has_aux: bool = False,
        devices: Optional[Sequence] = None,
        registry: Optional[M.MetricsRegistry] = None,
    ):
        if schedule not in ("threads", "round_robin"):
            raise ValueError(f"unknown schedule {schedule!r}")
        if n_workers < 1:
            raise ValueError("n_workers must be >= 1")
        self.loss_fn = loss_fn
        self.tx = tx
        self.n_workers = n_workers
        self.staleness = int(staleness)
        self.schedule = schedule
        self.has_aux = has_aux
        self.devices = list(devices) if devices else jax.local_devices()
        self._vg = jax.jit(jax.value_and_grad(loss_fn, has_aux=has_aux))
        self._server: Optional[ParamServer] = None
        # Operational export surface: the trainer publishes through the
        # SAME registry serve does, so the one OpenMetrics renderer
        # (obs/exporter.py — serve /metrics, headless file exporter) covers
        # async-PS training without any bespoke text path.
        reg = registry or M.registry
        self._c_pushes = reg.counter("async_ps_pushes_total")
        self._g_version = reg.gauge("async_ps_version")
        self._g_loss = reg.gauge("async_ps_last_loss")
        self._g_pps = reg.gauge("async_ps_pushes_per_sec")
        self._h_lag = reg.histogram("async_ps_push_lag")
        # Pushes already exported for the CURRENT server (its per-push lists
        # restart at zero whenever a fresh ParamServer is adopted, while the
        # registry counter — possibly shared process-wide — never resets).
        self._published = 0

    # ------------------------------------------------------------------ api
    def init(self, params) -> AsyncServerState:
        self._server = ParamServer(params, self.tx, staleness=self.staleness)
        self._published = 0
        return self._server.state

    def _worker_loop(self, server: ParamServer, worker: int,
                     next_batch: Callable[[int], Any], budget: List[int],
                     budget_lock: threading.Lock):
        dev = self.devices[worker % len(self.devices)]
        while True:
            with budget_lock:
                if budget[0] <= 0:
                    return
                budget[0] -= 1
                tick = budget[0]
            params, version = server.pull()
            batch = next_batch(tick)
            out = self._vg(jax.device_put(params, dev),
                           jax.device_put(batch, dev))
            loss, grads = (out[0][0], out[1]) if self.has_aux else out
            # The server needs the loss on the host anyway; fetching it
            # also waits for this worker's gradients.
            loss = float(loss)
            if server.push(grads, version, worker, loss=loss) < 0:
                # Snapshot exceeded the staleness bound: SSP refresh —
                # the gradient is dropped, the tick returns to the budget.
                with budget_lock:
                    budget[0] += 1

    def run(self, state: AsyncServerState, next_batch: Callable[[int], Any],
            n_pushes: int):
        """Execute ``n_pushes`` asynchronous updates.

        ``next_batch(tick)`` supplies each worker pull's batch (tick is a
        decreasing budget counter — deterministic batches per tick let
        tests replay schedules). Returns ``(state, metrics_dict)``.
        """
        server = self._server
        if server is None or server.state is not state:
            # Accept externally-restored state (checkpoint resume); adopts
            # the state without re-initializing optimizer slots.
            server = ParamServer(None, self.tx, staleness=self.staleness,
                                 state=state)
            self._server = server
            self._published = 0
        t0 = time.perf_counter()
        if self.schedule == "round_robin":
            self._run_round_robin(server, next_batch, n_pushes)
        else:
            budget = [n_pushes]
            budget_lock = threading.Lock()
            threads = [
                threading.Thread(
                    target=self._worker_loop,
                    args=(server, w, next_batch, budget, budget_lock),
                    daemon=True,
                )
                for w in range(self.n_workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        server.metrics.wall_s += time.perf_counter() - t0
        m = server.metrics
        self._publish(server)
        return server.state, {
            "loss": np.asarray(m.losses, np.float32),
            "lag": np.asarray(m.lags, np.int32),
            "worker": np.asarray(m.workers, np.int32),
            **m.summary(),
        }

    def _publish(self, server: ParamServer) -> None:
        """Refresh the registry from this run's per-push records (delta
        counters, point-in-time gauges)."""
        m = server.metrics
        new_pushes = len(m.losses) - self._published
        if new_pushes > 0:
            self._published = len(m.losses)
            self._c_pushes.inc(new_pushes)
            for lag in m.lags[-new_pushes:]:
                self._h_lag.observe(float(lag))
        self._g_version.set(server.state.version)
        if m.losses:
            self._g_loss.set(m.losses[-1])
        s = m.summary()
        if s["pushes_per_sec"] == s["pushes_per_sec"]:  # not NaN
            self._g_pps.set(s["pushes_per_sec"])

    def _run_round_robin(self, server: ParamServer,
                         next_batch: Callable[[int], Any], n_pushes: int):
        """Deterministic schedule: rounds of (all workers pull the SAME
        snapshot) then (pushes apply in worker order). Worker w>0's
        gradient in each round applies onto params already advanced by
        workers <w — stale by construction, reproducibly."""
        tick = n_pushes
        pending: List = []
        while tick > 0 or pending:
            if not pending:
                k = min(self.n_workers, tick)
                snapshots = [server.pull() for _ in range(k)]
                for w in range(k):
                    tick -= 1
                    params, version = snapshots[w]
                    out = self._vg(params, next_batch(tick))
                    loss, grads = (out[0][0], out[1]) if self.has_aux else out
                    pending.append((grads, version, w, float(loss)))
            grads, version, w, loss = pending.pop(0)
            if server.push(grads, version, w, loss=loss) < 0:
                tick += 1  # SSP refresh: recompute on a fresh snapshot
