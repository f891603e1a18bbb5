"""Fault tolerance (L1.5): elastic, preemption-aware training + serving.

The reference AutoDist's fault story ended at fail-fast: a worker death
killed the chief (``coordinator.py:98-110``) and a human restarted the
job. This package is the production counterpart the ROADMAP north star
requires — surviving TPU preemptions and host failures without losing
minutes of training or dropping queued inference requests:

- :mod:`~autodist_tpu.ft.heartbeat` — :class:`HealthMonitor`: positive
  liveness signals (vs. exit codes), healthy/suspect/dead classification
  with exponential escalation backoff, metrics-registry gauges, and the
  fleet verdicts the launcher's supervisor consumes.
- :mod:`~autodist_tpu.ft.snapshot` — :class:`SnapshotManager`: async
  ring of integrity-hashed train-state snapshots + the SIGTERM
  (preemption) hook that forces a final one.
- :mod:`~autodist_tpu.ft.elastic` — recompile the Strategy→ShardingPlan
  on the surviving mesh and restore the snapshot through the Saver's
  re-sharding read (GSPMD recompilation-on-resize, arXiv:2105.04663).
- :mod:`~autodist_tpu.ft.drain` — serve-side graceful degradation:
  quiesce → finish in-flight → persist undrained queue → replay on
  restart, zero loss / zero duplicates.
- :mod:`~autodist_tpu.ft.procdrain` — signal-then-grace subprocess
  termination (standalone: no package imports).

Entry point for users: ``AutoDist(fault_tolerance=FTConfig(...))`` — the
returned :class:`FTRuntime` rides on ``autodist.ft``. See
docs/fault_tolerance.md.
"""
from __future__ import annotations

from typing import Optional

from autodist_tpu import metrics as M
from autodist_tpu.ft.config import FTConfig
from autodist_tpu.ft.drain import DrainController, persist_requests, replay_requests
from autodist_tpu.ft.elastic import (
    ElasticController,
    recompile_on,
    resume_from_snapshot,
    surviving_resource_spec,
)
from autodist_tpu.ft.heartbeat import (
    CoordinatorTransport,
    FileTransport,
    FleetVerdict,
    HealthMonitor,
    MemoryTransport,
    PeerState,
)
from autodist_tpu.ft.snapshot import SnapshotManager, latest_snapshot_step

__all__ = [
    "CoordinatorTransport",
    "DrainController",
    "ElasticController",
    "FTConfig",
    "FTRuntime",
    "FileTransport",
    "FleetVerdict",
    "HealthMonitor",
    "MemoryTransport",
    "PeerState",
    "SnapshotManager",
    "latest_snapshot_step",
    "persist_requests",
    "recompile_on",
    "replay_requests",
    "resume_from_snapshot",
    "surviving_resource_spec",
]


class FTRuntime:
    """The per-process bundle ``AutoDist(fault_tolerance=...)`` creates:
    one started :class:`HealthMonitor` (file transport under the resolved
    heartbeat dir), one :class:`SnapshotManager`, and the preemption hook
    when configured. Components stay individually constructible for
    callers that want only one of them."""

    def __init__(self, config: FTConfig,
                 registry: Optional[M.MetricsRegistry] = None,
                 start_monitor: bool = True,
                 install_preempt_hook: Optional[bool] = None):
        import jax

        self.config = config.resolved()
        self.monitor = HealthMonitor(
            FileTransport(self.config.heartbeat_dir),
            process_id=jax.process_index(),
            config=self.config,
            registry=registry,
        )
        if start_monitor:
            self.monitor.start()
        self.snapshots = SnapshotManager.from_config(
            self.config, registry=registry)
        self.elastic = ElasticController(self.monitor, self.snapshots)
        if (self.config.snapshot_on_preempt
                if install_preempt_hook is None else install_preempt_hook):
            try:
                self.snapshots.install_preempt_hook()
            except ValueError:
                # Not the main thread (embedded runtimes): the hook is an
                # optimization, not a correctness requirement.
                pass

    def maybe_snapshot(self, state, step: Optional[int] = None,
                       step_obj=None) -> Optional[str]:
        """Periodic-snapshot hook for training loops; also refreshes the
        heartbeat payload's progress counter."""
        resolved = SnapshotManager._resolve_step(state, step)
        self.monitor.set_step(resolved)
        self.snapshots.register_state_provider(
            lambda: ((step_obj.logical_state(state)
                      if step_obj is not None else state), resolved))
        return self.snapshots.maybe_snapshot(state, step=resolved,
                                             step_obj=step_obj)

    def shutdown(self) -> None:
        self.monitor.stop()
        self.snapshots.wait()
