"""Step profiler: dispatch-gap vs device-compute split, live MFU, roofline.

:class:`StepProfiler` wraps a :class:`~autodist_tpu.kernel.DistributedTrainStep`
(or any object with the same ``run(state, batch, num_steps)`` contract) and
times each windowed run with the one-end-barrier discipline ``bench.py``
established: ``run`` returns as soon as the window program is *dispatched*
(host latency — the dispatch gap), and a single trailing device→host fetch
of the last loss is the barrier (the value depends on every step of the
window, and the sentry wants it on the host anyway). Per window:

- ``dispatch_gap_s`` — time for ``run()`` to return (host dispatch, plus
  XLA compile on a window's first execution);
- ``wall_s`` — dispatch → barrier (the whole window);
- ``device_s`` — ``wall_s - dispatch_gap_s``, the device-side residue.

FLOPs and HBM bytes come from the **compiled program's own cost analysis**
(``DistributedTrainStep.window_cost`` → XLA's per-executable numbers), not
an analytical model, so live MFU is measured-over-measured:
``mfu = flops_per_step / (device_s_per_step × peak_flops)``. Roofline
position reuses :mod:`autodist_tpu.utils.roofline`'s time conversion with
the compiled byte counts, and the same bound yields the
``exposed_comm_fraction`` metric — device time beyond the compute/HBM
roofline, i.e. wire (and scheduling) time NOT hidden under compute — the
before/after signal for bucketed backward-overlap gradient sync
(``GraphConfig.bucket_bytes``, docs/performance.md). Compile counts/times ride the step's
``compile_log`` (fresh-program first-call latencies) and the HBM
high-water mark comes from ``device.memory_stats()`` where the platform
exposes one (TPU; None on CPU).

:class:`StepTimer` (plain wall-clock step timing, previously
``utils/tracing.py``) lives here now; the old import path remains as a
compat shim.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from autodist_tpu import metrics as M
from autodist_tpu.obs import recorder as _flight
from autodist_tpu.obs import spans as _spans
from autodist_tpu.utils import logging

__all__ = ["StepProfiler", "StepTimer", "detect_peak_flops",
           "peak_flops_for_kind"]

# Peak bf16 FLOPs/s per chip by TPU generation (Google Cloud TPU
# documentation, per-generation system-architecture pages) — the ONE peak
# table: bench.py, chip_smoke.py and the live MFU below all read it.
# Matched against jax ``Device.device_kind`` by substring in this order
# ("v5 lite" is how a v5e reports itself).
_PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v6e": 918e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
}


def _peak_lookup(device_kind: str) -> Optional[float]:
    kind = (device_kind or "").lower()
    for key, val in _PEAK_FLOPS.items():
        if key in kind:
            return val
    return None


def peak_flops_for_kind(device_kind: str) -> float:
    """Per-chip bf16 peak for a ``device_kind`` the table lists. A kind it
    does not list raises: a utilization against a guessed peak is not a
    measurement, so benchmarks stop instead of assuming one."""
    peak = _peak_lookup(device_kind)
    if peak is None:
        raise ValueError(
            f"device_kind {device_kind!r} is not in the peak-FLOPs table "
            f"({sorted(_PEAK_FLOPS)}); add it with its source before "
            f"measuring on it")
    return peak


def detect_peak_flops(device) -> Optional[float]:
    """Per-chip peak for a recognized accelerator; None when unknown (CPU,
    unlisted generation) — the live profiler then reports no MFU rather
    than one against a guessed peak."""
    return _peak_lookup(getattr(device, "device_kind", ""))


def _hbm_high_water() -> Optional[int]:
    """Max ``peak_bytes_in_use`` across local devices; None when the
    platform exposes no memory stats (CPU host platform)."""
    import jax

    peaks = []
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 - optional platform API
            stats = None
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class StepProfiler:
    """Profile windowed train-step execution with near-zero overhead.

    Usage::

        prof = obs.StepProfiler(step)
        for _ in range(n_windows):
            state, metrics = prof.run(state, batch, window)
        print(json.dumps(prof.report()))

    Each profiled window adds one host-side timing pair and one span; the
    device program is untouched (the overhead guard in tests/test_obs.py
    pins enabled-vs-disabled cost). ``registry`` receives ``obs_*`` gauges
    on every window so exporters see live values.
    """

    def __init__(
        self,
        step,
        registry: Optional[M.MetricsRegistry] = None,
        tracer: Optional[_spans.SpanTracer] = None,
        peak_flops_per_chip: Optional[float] = None,
        hbm_bw_bytes_per_s: Optional[float] = None,
        recorder=None,
        sentry=None,
    ):
        import jax

        self.step = step
        self.tracer = tracer or _spans.get_tracer()
        # Black-box feed (docs/observability.md § flight recorder): every
        # profiled window appends one step record, and the sentry watches
        # the same stream online. Defaults follow the always-on contract —
        # the env-gated process recorder, plus a monitor-less sentry so
        # NaN/regression verdicts exist wherever the recorder does.
        self.recorder = (_flight.get_recorder() if recorder is None
                         else recorder)
        if sentry is None and self.recorder is not None:
            from autodist_tpu.obs.sentry import Sentry

            sentry = Sentry(registry=registry, recorder=self.recorder)
        self.sentry = sentry
        # Planned per-step collective payload (sum of the plan's promised
        # wire, docs/analysis.md): a constant of the compiled program,
        # computed once and stamped on every flight record so postmortems
        # can relate wall-time anomalies to wire pressure. None for steps
        # without a plan (foreign step objects).
        self._collective_bytes: Optional[float] = None
        try:
            wire = self.step.plan.promised_wire()
            self._collective_bytes = float(
                sum(w.storage_bytes for w in wire.values()))
        except Exception:  # noqa: BLE001 - telemetry only
            pass
        self._n_devices = jax.device_count()
        self.peak_flops_per_chip = (
            peak_flops_per_chip
            if peak_flops_per_chip is not None
            else detect_peak_flops(jax.devices()[0]))
        self.hbm_bw_bytes_per_s = hbm_bw_bytes_per_s
        self.windows: List[Dict[str, float]] = []
        # Last measured-wire attribution (obs/attrib.py): set by
        # attribute(); when present its trace-measured exposed-comm
        # fraction replaces the roofline-residue estimate in report().
        self.last_attribution = None
        # Cumulative profiled-step counter: stamps flight records and
        # sentry findings with WHICH step an anomaly hit (a proxy for the
        # training step — exact when profiling starts at step 0).
        self._steps_total = 0
        self._cost: Dict[int, Dict[str, float]] = {}
        # Cost analysis runs OFF the training thread: it AOT-compiles the
        # single-step program, which on a big TPU model takes minutes — a
        # synchronous call inside the first profiled window would stall
        # training. report() joins the thread.
        self._cost_thread: Optional[threading.Thread] = None

        reg = registry or M.registry
        self._h_wall = reg.histogram("obs_step_wall_s")
        self._g_dispatch = reg.gauge("obs_dispatch_gap_s")
        self._g_device = reg.gauge("obs_device_compute_s")
        self._g_mfu = reg.gauge("obs_mfu")
        self._g_flops = reg.gauge("obs_flops_per_step")
        self._g_hbm = reg.gauge("obs_hbm_high_water_bytes")
        self._g_compiles = reg.gauge("obs_programs_compiled")
        self._g_exposed = reg.gauge("obs_exposed_comm_fraction")
        self._c_windows = reg.counter("obs_profiled_windows_total")

    # ------------------------------------------------------------------ run
    def run(self, state, batch, num_steps: int, stacked: bool = False):
        """``step.run`` with the window profiled; returns its result."""
        t_wall = time.time()
        t0 = time.perf_counter()
        state, metrics = self.step.run(state, batch, num_steps,
                                       stacked=stacked)
        dispatch = time.perf_counter() - t0
        # ONE end barrier per window (bench.py discipline): a device→host
        # scalar fetch of the final loss.
        loss = metrics.get("loss") if isinstance(metrics, dict) else None
        loss_val = None
        if loss is not None:
            loss_val = float(np.asarray(loss).ravel()[-1])
        else:
            import jax

            jax.block_until_ready(metrics)
        wall = time.perf_counter() - t0
        # Norm scalars (present when the step was built with
        # record_norms=True) ride the same already-barriered metrics tree.
        norms = {}
        if isinstance(metrics, dict):
            for key in ("grad_norm", "update_norm"):
                if key in metrics:
                    norms[key] = float(np.asarray(metrics[key]).ravel()[-1])
        self._record(num_steps, stacked, dispatch, wall, t_wall, state,
                     batch, loss_val, norms)
        return state, metrics

    def _record(self, num_steps, stacked, dispatch, wall, t_wall,
                state, batch, loss_val=None, norms=None) -> None:
        device_s = max(wall - dispatch, 0.0)
        cost = self._step_cost(state, batch, stacked)
        flops_step = cost.get("flops", 0.0)
        rec = {
            "steps": float(num_steps),
            "dispatch_gap_s": dispatch,
            "wall_s": wall,
            "device_s": device_s,
        }
        self.windows.append(rec)
        self._c_windows.inc()
        self._h_wall.observe(wall)
        self._g_dispatch.set(dispatch)
        self._g_device.set(device_s)
        self._g_compiles.set(len(getattr(self.step, "compile_log", ())))
        if flops_step:  # cost analysis may still be compiling in background
            self._g_flops.set(flops_step)
            mfu = self._mfu(flops_step, device_s / max(num_steps, 1))
            if mfu is not None:
                self._g_mfu.set(mfu)
        hbm = _hbm_high_water()
        if hbm is not None:
            self._g_hbm.set(hbm)
        self.tracer.add_span(
            "profiler.window", t_wall, wall, steps=num_steps,
            dispatch_gap_ms=round(dispatch * 1e3, 3),
        )
        # Flight-record + sentry feed: one compact record per window, with
        # per-step derived values (the exposed-comm fraction joins once the
        # background cost analysis lands AND a bandwidth was configured).
        n = max(int(num_steps), 1)
        self._steps_total += n
        exposed = self._window_exposed_fraction(device_s / n, cost)
        if self.recorder is not None:
            rec = {
                "step": self._steps_total,
                "steps": int(num_steps),
                "step_wall_s": wall / n,
                "dispatch_gap_s": dispatch,
                "device_s": device_s,
            }
            if loss_val is not None:
                rec["loss"] = loss_val
            if norms:
                rec.update(norms)
            if hbm is not None:
                rec["hbm_high_water"] = hbm
            if exposed is not None:
                rec["exposed_comm_fraction"] = exposed
            if flops_step:
                rec["flops_per_step"] = flops_step
            if self._collective_bytes:
                rec["collective_bytes_planned"] = self._collective_bytes
            self.recorder.record_step(**rec)
        if self.sentry is not None:
            norms = norms or {}
            self.sentry.observe_step(
                step=self._steps_total, loss=loss_val,
                step_time_s=wall / n, hbm_bytes=hbm,
                grad_norm=norms.get("grad_norm"),
                update_norm=norms.get("update_norm"))

    def _window_exposed_fraction(self, step_device_s: float,
                                 cost) -> Optional[float]:
        """Per-window exposed-comm fraction (same formula as report();
        None until the cost analysis and a bandwidth are both known)."""
        if (not cost or not self.hbm_bw_bytes_per_s
                or not self.peak_flops_per_chip or step_device_s <= 0):
            return None
        from autodist_tpu.utils import roofline

        bounds = {
            "flops": cost.get("flops", 0.0),
            "lower_bytes": cost.get("bytes_accessed", 0.0),
            "upper_bytes": cost.get("bytes_accessed", 0.0),
        }
        times = roofline.roofline_times(
            bounds, self.peak_flops_per_chip, self.hbm_bw_bytes_per_s)
        if not times.get("t_roofline_s"):
            return None
        exposed = max(step_device_s - times["t_roofline_s"], 0.0)
        return exposed / step_device_s

    def _step_cost(self, state, batch, stacked: bool) -> Dict[str, float]:
        """Per-step FLOPs/bytes = the SINGLE-STEP compiled program's cost
        analysis (XLA counts a scan body once regardless of trip count, so
        dividing a window's numbers by its length would under-report — see
        DistributedTrainStep.window_cost; the numbers are PER-DEVICE: cost
        analysis sees the partitioned module). A stacked window's batch
        carries a leading num_steps axis; one slice of it is the per-step
        batch, so costing the whole stack as one step would over-report by
        the window factor.

        Non-blocking: the AOT compile runs on a background thread (first
        call kicks it off; until it lands this returns ``{}`` and the
        flops/mfu gauges stay unset). :meth:`report` joins it."""
        cached = self._cost.get(1)
        if cached is not None:
            return cached
        if self._cost_thread is None:
            wc = getattr(self.step, "window_cost", None)
            if wc is None:
                self._cost[1] = {}
                return self._cost[1]
            import jax

            if stacked:
                batch = jax.tree.map(lambda x: x[0], batch)
            # Abstract shapes captured NOW, on the caller thread: the next
            # profiled window donates the live state's buffers, and the
            # background lower() must never touch them.
            state_shapes = jax.eval_shape(lambda: state)
            batch_shapes = jax.eval_shape(lambda: batch)

            def compute():
                try:
                    self._cost[1] = wc(state_shapes, batch_shapes, 1)
                except Exception as e:  # noqa: BLE001 - never fail training
                    logging.debug("window_cost unavailable: %s", e)
                    self._cost[1] = {}

            self._cost_thread = threading.Thread(
                target=compute, name="obs-step-cost", daemon=True)
            self._cost_thread.start()
        return {}

    # --------------------------------------------------------------- report
    def _mfu(self, flops_per_step: float,
             device_s_per_step: float) -> Optional[float]:
        """Measured MFU. ``flops_per_step`` is PER-DEVICE (XLA's cost
        analysis sees the partitioned module), so the denominator is the
        per-CHIP peak — multiplying by device_count would under-report
        fleet MFU by exactly that factor."""
        if (not flops_per_step or not device_s_per_step
                or self.peak_flops_per_chip is None):
            return None
        return flops_per_step / (device_s_per_step * self.peak_flops_per_chip)

    def report(self) -> Dict[str, Any]:
        """Aggregated profile: median window split, per-step FLOPs, MFU,
        roofline position (with a bandwidth), compile log, HBM high-water.
        Joins the background cost-analysis compile (bounded) so the FLOPs
        fields are final."""
        if self._cost_thread is not None and self._cost_thread.is_alive():
            self._cost_thread.join(timeout=600.0)
        out: Dict[str, Any] = {
            "windows": len(self.windows),
            "n_devices": self._n_devices,
        }
        if not self.windows:
            return out
        med = lambda k: float(np.median([w[k] for w in self.windows]))  # noqa: E731
        steps = self.windows[-1]["steps"] or 1.0
        cost = self._cost.get(1) or {}
        out.update({
            "steps_per_window": steps,
            "dispatch_gap_s": med("dispatch_gap_s"),
            "wall_s": med("wall_s"),
            "device_s": med("device_s"),
            "step_wall_s": med("wall_s") / steps,
            "step_device_s": med("device_s") / steps,
            # Per-device numbers (partitioned module) — see _mfu.
            "flops_per_step": cost.get("flops", 0.0),
            "bytes_per_step": cost.get("bytes_accessed", 0.0),
        })
        if out["flops_per_step"]:
            self._g_flops.set(out["flops_per_step"])
        mfu = self._mfu(out["flops_per_step"], out["step_device_s"])
        if mfu is not None:
            out["mfu"] = mfu
            self._g_mfu.set(mfu)
        if self.hbm_bw_bytes_per_s and self.peak_flops_per_chip:
            from autodist_tpu.utils import roofline

            # Per-device flops/bytes against per-chip peak and per-chip
            # bandwidth: consistent units, so vs_roofline ~ 1 means AT the
            # hardware ceiling on any mesh size.
            bounds = {
                "flops": out["flops_per_step"],
                "lower_bytes": out["bytes_per_step"],
                "upper_bytes": out["bytes_per_step"],
            }
            times = roofline.roofline_times(
                bounds, self.peak_flops_per_chip, self.hbm_bw_bytes_per_s)
            out["roofline"] = {
                **times,
                # >1: measured step above the hardware bound (overhead to
                # hunt); ~1: at the ceiling.
                "vs_roofline": (out["step_device_s"] / times["t_roofline_s"]
                                if times["t_roofline_s"] else float("nan")),
            }
            # Exposed-communication split: device step time BEYOND the
            # compiled program's own compute/HBM roofline bound is time the
            # chip spent neither on the MXU nor on HBM — on real meshes
            # that residue is dominated by collectives NOT hidden under
            # compute (plus scheduling slack), so the fraction is the
            # measurable "did bucketed backward-overlap actually hide the
            # wire" signal (docs/performance.md): it drops when
            # GraphConfig.bucket_bytes moves the grad sync into the
            # backward, and it is what the plan calibration's overlap_s
            # coefficient is fitted against. Upper bound by construction —
            # any non-comm overhead inflates it, never deflates.
            if out["step_device_s"] > 0:
                exposed = max(
                    out["step_device_s"] - times["t_roofline_s"], 0.0)
                out["exposed_comm_s_per_step"] = exposed
                out["exposed_comm_fraction"] = (
                    exposed / out["step_device_s"])
                self._g_exposed.set(out["exposed_comm_fraction"])
        if self.last_attribution is not None:
            # Trace-measured wire beats the roofline residue: the residue
            # is an upper bound (any non-comm overhead inflates it), the
            # attribution measured the collectives themselves.
            wire = self.last_attribution
            out["measured_wire"] = wire.summary()
            frac = wire.exposed_comm_fraction
            if frac is not None:
                out["exposed_comm_s_per_step"] = wire.exposed_wire_s_per_step
                out["exposed_comm_fraction"] = frac
                self._g_exposed.set(frac)
        compile_log = list(getattr(self.step, "compile_log", ()))
        out["compiles"] = {
            "count": len(compile_log),
            "total_first_call_s": round(
                sum(e.get("first_call_s", 0.0) for e in compile_log), 4),
        }
        hbm = _hbm_high_water()
        if hbm is not None:
            out["hbm_high_water_bytes"] = hbm
        return out

    def log_report(self, prefix: str = "profile") -> Dict[str, Any]:
        rep = self.report()
        logging.info("%s: %s", prefix, json.dumps(rep, sort_keys=True,
                                                  default=float))
        return rep

    # ---------------------------------------------------------- attribution
    def attribute(self, state, batch, num_steps: int = 4,
                  trace_dir: Optional[str] = None, stacked: bool = False):
        """Measured-wire attribution of one windowed run (obs/attrib.py):
        capture a ``jax.profiler`` trace, join every device op back to the
        plan's promised wire, and return ``(MeasuredWire, new_state)``
        (``run`` donates ``state``).

        Side effects: the report lands on :attr:`last_attribution`; the
        trace-measured exposed-comm fraction (a direct measurement, unlike
        the roofline residue) updates the ``obs_exposed_comm_fraction``
        gauge and subsequent :meth:`report` calls; an ``attrib`` event
        goes to the flight recorder when one is active."""
        from autodist_tpu.obs import attrib as _attrib

        wire, state = _attrib.attribute(
            self.step, state, batch, num_steps=num_steps,
            trace_dir=trace_dir, stacked=stacked)
        self.last_attribution = wire
        frac = wire.exposed_comm_fraction
        if frac is not None:
            self._g_exposed.set(frac)
        if self.recorder is not None:
            self.recorder.record_event("attrib", critical=False,
                                       **wire.summary())
        return wire, state

    @property
    def exposed_comm_fraction(self) -> Optional[float]:
        """The step-level exposed-communication fraction, best evidence
        first: the trace-measured number when :meth:`attribute` ran (wire
        time not covered by concurrent same-device compute), else the
        roofline-residue estimate from :meth:`report` (device time beyond
        the compiled program's compute/HBM bound), else None."""
        if self.last_attribution is not None:
            frac = self.last_attribution.exposed_comm_fraction
            if frac is not None:
                return frac
        rep = self.report()
        return rep.get("exposed_comm_fraction")

    def calibration_record(self, cost, name: str = ""):
        """This profile as a planner calibration point: pair the measured
        per-step wall split (and the compiled program's FLOPs/bytes) with
        the analytic :class:`~autodist_tpu.strategy.cost_model.StrategyCost`
        of the strategy that ran. Feed the result to
        :func:`autodist_tpu.plan.calibrate.calibrate_from_records` and the
        planner's cost model starts predicting THIS topology
        (docs/planner.md § calibration loop)."""
        from autodist_tpu.plan.calibrate import record_from_profiler

        return record_from_profiler(self.report(), cost, name=name)


# ----------------------------------------------------------------- StepTimer
class StepTimer:
    """Wall-clock step timing + throughput summary.

    ``items_per_step`` (e.g. global batch size, or tokens/step) turns times
    into throughput. First ``warmup`` steps are excluded (compile + cache
    effects). Use as a callable context around each step.
    """

    def __init__(self, items_per_step: float = 0.0, warmup: int = 2):
        self.items_per_step = items_per_step
        self.warmup = warmup
        self.times: List[float] = []
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        assert self._t0 is not None
        self.times.append(time.perf_counter() - self._t0)
        self._t0 = None
        return False

    @property
    def measured(self) -> List[float]:
        return self.times[self.warmup:] if len(self.times) > self.warmup else []

    def summary(self) -> Dict[str, Any]:
        xs = sorted(self.measured)
        if not xs:
            return {"steps": len(self.times), "measured": 0}
        n = len(xs)
        mean = sum(xs) / n
        out = {
            "steps": len(self.times),
            "measured": n,
            "mean_s": mean,
            "p50_s": xs[n // 2],
            "p90_s": xs[min(n - 1, int(n * 0.9))],
            "min_s": xs[0],
        }
        if self.items_per_step:
            out["items_per_sec"] = self.items_per_step / mean
        return out

    def log_summary(self, prefix: str = "steps") -> Dict[str, Any]:
        s = self.summary()
        logging.info("%s: %s", prefix, json.dumps(s, sort_keys=True))
        return s
