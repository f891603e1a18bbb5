"""Multi-host launcher: ``python -m autodist_tpu.runtime.launcher``.

The user-facing bring-up tool — the analog of the reference's implicit
"construct AutoDist on the chief and it SSH-launches everything" flow
(``/root/reference/autodist/autodist.py:120-128`` → ``cluster.start()`` →
``coordinator.launch_clients()``), packaged the way TPU users expect: one
command that runs the same training script on every host of the cluster with
the right role env, then watches the fleet.

Usage::

    python -m autodist_tpu.runtime.launcher --resource-spec spec.yml \
        -- python train.py --flags ...

On the chief this execs the script locally with chief role; for every other
node it re-execs the identical command over SSH (TPU-VM images) or as a local
subprocess (single-host multi-process testing with ``address: localhost``
specs is rejected by ResourceSpec validation, so local fan-out is driven by
``--num-local-processes`` instead, which emulates N hosts on one machine for
CPU-mesh testing).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading
import time
from typing import Callable, List, Optional, Sequence, TYPE_CHECKING

from autodist_tpu import const
from autodist_tpu.const import ENV
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runtime.cluster import Cluster, clean_stale_processes, write_pidfile
from autodist_tpu.runtime.coordinator import Coordinator
from autodist_tpu.utils import logging

if TYPE_CHECKING:
    from autodist_tpu.ft import FTConfig


def _scrub_role_vars(env: dict) -> dict:
    """Drop the framework's role/strategy vars from an environment.

    Any earlier chief-side ``build()`` in the calling process exports
    ``AUTODIST_STRATEGY_ID`` into ``os.environ`` (and a stale
    ``AUTODIST_WORKER`` can linger the same way); a freshly launched
    process inheriting them is misrouted onto the coordinator-shipped-
    strategy path, waiting for a file that was never shipped while the
    chief blocks in the runtime broadcast. Launchers must set role vars
    explicitly; behavior knobs (log level, testing flags) and user vars
    pass through.
    """
    role_vars = {
        ENV.AUTODIST_WORKER.name,
        ENV.AUTODIST_STRATEGY_ID.name,
        ENV.AUTODIST_COORDINATOR.name,
        ENV.AUTODIST_NUM_PROCESSES.name,
        ENV.AUTODIST_PROCESS_ID.name,
    }
    return {k: v for k, v in env.items() if k not in role_vars}


class _FleetWatch:
    """Launcher-side fleet observer: a non-publishing
    :class:`~autodist_tpu.ft.heartbeat.HealthMonitor` over the fleet's
    heartbeat directory, plus a watchdog thread that terminates the chief
    when the whole fleet goes silent (``fleet_hung``).

    This is the capability blind exit-code supervision cannot have: a hung
    fleet never *exits*, so ``--max-restarts`` alone would wait on it
    forever. The watchdog converts the HealthMonitor's verdict into a
    chief termination, which surfaces as a non-zero ``launch`` return the
    supervisor can act on.
    """

    def __init__(self, ft_config: "FTConfig"):
        from autodist_tpu.ft import FileTransport, HealthMonitor

        self.config = ft_config.resolved()
        # Sweep beats left by a previous incarnation: their stale stamps
        # would otherwise read as an immediately-hung fleet.
        hb_dir = self.config.heartbeat_dir
        os.makedirs(hb_dir, exist_ok=True)
        for name in os.listdir(hb_dir):
            if name.startswith("hb-"):
                try:
                    os.remove(os.path.join(hb_dir, name))
                except OSError:
                    pass
        self.monitor = HealthMonitor(
            FileTransport(hb_dir), publish=False, config=self.config)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.hang_detected = False

    def env(self) -> dict:
        """Role env every fleet process needs to heartbeat into the same
        base dir the watchdog sweeps. The pilot dir rides along so a
        controller (and the doctor stitching its decision journal) agree
        on one ``<base>/pilot`` across the fleet (docs/autopilot.md)."""
        return {
            ENV.AUTODIST_FT_DIR.name: self.config.base_dir,
            ENV.AUTODIST_PILOT_DIR.name: os.path.join(
                self.config.base_dir, "pilot"),
        }

    def write_bundle(self, reason: str = "fleet_hung") -> Optional[str]:
        """Persist a doctor bundle — last heartbeats (per-peer state +
        payload), fleet verdict, and this launcher's open spans — under
        ``<ft base>/doctor/`` BEFORE the kill, so a supervised termination
        is attributable: ``python -m autodist_tpu.obs doctor <ft base>``
        reads it as the primary wedge evidence (docs/observability.md).
        Best-effort, atomic, fsync'd; never blocks the kill on IO."""
        import json

        try:
            from autodist_tpu.obs.spans import get_tracer

            peers = {}
            for pid, p in self.monitor.peers().items():
                peers[str(pid)] = {
                    "state": p.state.value,
                    "last_seen": p.last_seen,
                    "misses": p.misses,
                    "last_payload": p.last_payload,
                }
            bundle = {
                "written_at": time.time(),
                "reason": reason,
                "verdict": self.monitor.verdict().value,
                "hang_after_misses": self.config.hang_after_misses,
                "heartbeat_interval_s": self.config.heartbeat_interval_s,
                "heartbeats": peers,
                "launcher_spans": [
                    {"name": s.name, "t_start_s": s.t_start_s,
                     "dur_s": s.dur_s, "attrs": s.attrs}
                    for s in get_tracer().spans()[-64:]
                ],
            }
            bundle_dir = os.path.join(self.config.base_dir, "doctor")
            os.makedirs(bundle_dir, exist_ok=True)
            path = os.path.join(
                bundle_dir, f"hang-bundle-{int(time.time())}.json")
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(bundle, f, indent=2, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            logging.info("wrote doctor bundle -> %s", path)
            return path
        except Exception:  # noqa: BLE001 - the kill must proceed regardless
            logging.warning("doctor bundle write failed", exc_info=True)
            return None

    def start(self, chief: subprocess.Popen) -> None:
        def watch():
            while not self._stop.is_set():
                try:
                    self.monitor.tick()
                    if self.monitor.fleet_hung():
                        self.hang_detected = True
                        logging.error(
                            "fleet heartbeats silent for %d intervals "
                            "(verdict %s); terminating chief for restart",
                            self.config.hang_after_misses,
                            self.monitor.verdict().value,
                        )
                        # Attribution before termination: the bundle is the
                        # context SIGTERM would otherwise discard.
                        self.write_bundle()
                        chief.terminate()
                        return
                except Exception:  # noqa: BLE001 - watchdog must not die
                    logging.warning("fleet watchdog tick failed", exc_info=True)
                self._stop.wait(self.config.heartbeat_interval_s)

        self._thread = threading.Thread(
            target=watch, name="ft-fleet-watch", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def verdict(self) -> str:
        return self.monitor.verdict().value

    def progress_step(self) -> Optional[int]:
        """Newest snapshot step the fleet persisted (the supervisor's
        progress signal)."""
        from autodist_tpu.ft.snapshot import latest_snapshot_step

        return latest_snapshot_step(self.config.snapshot_dir)


def launch(
    resource_spec: ResourceSpec,
    argv: Sequence[str],
    num_local_processes: int = 0,
    coordinator_port: Optional[int] = None,
    extra_env: Optional[dict] = None,
    supervised: bool = False,
    ft_config: "Optional[FTConfig]" = None,
) -> int:
    """Launch ``argv`` across the cluster; returns the chief's exit code.

    With ``num_local_processes > 1`` the cluster is emulated on this machine:
    N processes, process 0 (chief) runs in the foreground, the rest are
    subprocesses with worker role env — the moral equivalent of the
    reference's docker-on-one-box distributed CI (``Jenkinsfile:93-131``).

    ``extra_env`` is merged into every process's environment (chief and
    workers, local or SSH). ``supervised=True`` redirects the coordinator's
    worker-death fail-fast from ``os._exit(1)`` to terminating the chief
    subprocess, so this function *returns* non-zero instead of killing the
    calling process — required by :func:`launch_supervised`'s restart loop.
    ``ft_config`` additionally arms the fleet watchdog: every process gets
    ``AUTODIST_FT_DIR`` pointing at one shared base, and a launcher-side
    :class:`~autodist_tpu.ft.heartbeat.HealthMonitor` observer terminates a
    fleet whose heartbeats all go silent (a hang never exits on its own).
    """
    clean_stale_processes()
    argv = list(argv)
    extra_env = dict(extra_env or {})
    watch = None
    if ft_config is not None:
        watch = _FleetWatch(ft_config)
        extra_env = {**watch.env(), **extra_env}

    # Observability contract (docs/observability.md): ONE trace id for the
    # whole launch, exported to every process (chief, local workers, SSH
    # remotes) so their spans stitch into a single cross-process timeline.
    # current_trace_id() also pins it into this launcher's own env, so the
    # launcher's spans carry the same id.
    from autodist_tpu.obs.spans import current_trace_id

    # A caller-supplied extra_env id/dir wins over this launcher's env;
    # either way the launcher process pins the SAME values into its own
    # env so its spans (launcher.fleet) join the fleet's trace and the
    # stitch below sees the right dir.
    trace_id = extra_env.get(ENV.AUTODIST_TRACE_ID.name)
    if trace_id:
        os.environ[ENV.AUTODIST_TRACE_ID.name] = trace_id
    else:
        trace_id = extra_env[ENV.AUTODIST_TRACE_ID.name] = current_trace_id()
    trace_out = (extra_env.get(ENV.AUTODIST_TRACE_OUT.name)
                 or ENV.AUTODIST_TRACE_OUT.val)
    if trace_out:
        extra_env.setdefault(ENV.AUTODIST_TRACE_OUT.name, trace_out)
        os.environ[ENV.AUTODIST_TRACE_OUT.name] = trace_out
    t_launch = time.time()

    if num_local_processes > 1:
        base = {**_scrub_role_vars(dict(os.environ)), **extra_env}
        code = _launch_local_fleet(
            argv, num_local_processes, coordinator_port, base_env=base,
            watch=watch)
        _finish_trace(trace_out, trace_id, t_launch, num_local_processes,
                      code)
        return code

    cluster = Cluster(resource_spec, coordinator_port=coordinator_port)
    coordinator = Coordinator(cluster, argv=argv, extra_env=extra_env)
    if supervised:
        # Placeholder until the chief exists: a worker dying in this window
        # leaves the cluster torn down, the chief then fails its runtime
        # join and launch() returns non-zero — still restartable.
        coordinator.set_failure_action(lambda: None)
    coordinator.launch_clients()

    env = {
        **extra_env,
        ENV.AUTODIST_COORDINATOR.name: cluster.coordinator_address,
        ENV.AUTODIST_NUM_PROCESSES.name: str(cluster.num_processes),
        ENV.AUTODIST_PROCESS_ID.name: "0",
    }
    chief = subprocess.Popen(argv, env={**_scrub_role_vars(dict(os.environ)), **env})
    if supervised:
        coordinator.set_failure_action(chief.terminate)
    if watch is not None:
        watch.start(chief)
    code = chief.wait()
    if watch is not None:
        watch.stop()
        if watch.hang_detected and code == 0:
            # A SIGTERM'd chief that exits 0 (its preemption hook ran clean)
            # must still read as a failed attempt, or the supervisor would
            # declare a hung fleet done.
            code = 1
        if code != 0:
            logging.error("fleet attempt failed rc=%d; health verdict: %s",
                          code, watch.verdict())
    if code == 0:
        coordinator.join()
        if coordinator.any_failed:
            # A worker died after the chief already exited cleanly (e.g.
            # crash during teardown/final save): under supervision the
            # failure action (chief.terminate) was a no-op by then, so the
            # failure must surface in the return code — a clean-looking 0
            # here would make the supervisor (and CI) report success.
            logging.error("chief exited 0 but a worker failed; reporting failure")
            code = 1
    cluster.terminate()
    _finish_trace(trace_out, trace_id, t_launch, cluster.num_processes, code)
    return code


def _finish_trace(trace_out: str, trace_id: str, t_launch: float,
                  n_processes: int, code: int) -> None:
    """Close the launch's observability loop: record the launcher's own
    fleet span, flush it, and stitch every process's part-file into ONE
    chrome-trace JSON (``trace-<id>.json`` under the trace-out dir).
    Best-effort — tracing must never change a launch's outcome."""
    if not trace_out:
        return
    try:
        from autodist_tpu.obs.spans import get_tracer, stitch

        tracer = get_tracer()
        tracer.add_span("launcher.fleet", t_launch, time.time() - t_launch,
                        processes=n_processes, exit_code=code)
        tracer.flush_part(trace_out)
        merged = stitch(trace_out, trace_id=trace_id)
        if merged:
            logging.info("stitched fleet trace -> %s (load in Perfetto or "
                         "chrome://tracing)", merged)
    except Exception:  # noqa: BLE001 - observability is never fatal here
        logging.warning("trace stitch failed", exc_info=True)


def launch_supervised(
    resource_spec: ResourceSpec,
    argv: Sequence[str],
    max_restarts: int = 0,
    num_local_processes: int = 0,
    coordinator_port: Optional[int] = None,
    restart_backoff_s: float = 5.0,
    ft_config: "Optional[FTConfig]" = None,
    restart_backoff_max_s: float = 300.0,
    backoff_seed: Optional[int] = None,
    restart_sleep: Optional[Callable[[float], None]] = None,
) -> int:
    """:func:`launch` under a restart supervisor (checkpoint-resume loop).

    The reference's fault story ended at fail-fast (worker death kills the
    chief, ``coordinator.py:98-110``) + manual restart; this closes the
    loop: a fleet that exits non-zero is relaunched — same command, fresh
    role env, stale pidfiles swept by the inner :func:`launch` — up to
    ``max_restarts`` times. Worker death is survivable too: ``supervised``
    launches redirect the coordinator's fail-fast from ``os._exit(1)`` to
    terminating the chief, so it surfaces as a non-zero return here
    instead of killing this process. Training scripts resume by
    construction when they open their state with
    ``DistributedTrainStep.init_or_restore`` (fresh init when the
    checkpoint dir is empty, latest checkpoint otherwise), so the
    supervisor needs no protocol with the script. Each attempt carries
    ``AUTODIST_RESTART`` (0 on the first run) in every process's env —
    chief, local workers, and SSH-launched remote workers alike.

    With ``ft_config`` the supervisor stops being a blind exit-code
    counter and consumes the ft subsystem's verdicts instead:

    - each :func:`launch` runs under the fleet watchdog (a hung fleet is
      terminated and restarted rather than waited on forever);
    - the restart budget counts restarts *since the fleet last made
      progress*: when the newest snapshot step advanced across an attempt
      (``ft.snapshot.latest_snapshot_step``), the counter resets — a run
      that keeps progressing between preemptions is never "given up on"
      by an absolute cap sized for genuine crash loops.

    Restart pacing is **jittered exponential backoff** through the ONE
    retry layer (``utils/retry.py``): ``restart_backoff_s`` is the first
    delay's base, doubling per consecutive failed attempt up to
    ``restart_backoff_max_s``, each delay jittered down by up to 50% so a
    crashing multi-fleet deployment cannot restart-storm in lockstep. The
    backoff resets together with the restart budget whenever the snapshot
    ring advances — a preempted-but-progressing run restarts promptly
    forever; only a no-progress crash loop slows down. ``backoff_seed``
    pins the jitter (chaos replay determinism); ``restart_sleep``
    overrides the sleep (tests, harnesses).
    """
    import random as _random

    from autodist_tpu.utils import retry as _retry

    def _progress() -> Optional[int]:
        if ft_config is None:
            return None
        from autodist_tpu.ft.snapshot import latest_snapshot_step

        return latest_snapshot_step(ft_config.resolved().snapshot_dir)

    backoff = _retry.Backoff(
        _retry.RetryPolicy(
            initial_s=restart_backoff_s, max_s=restart_backoff_max_s,
            multiplier=2.0, jitter=0.5),
        rng=_random.Random(backoff_seed) if backoff_seed is not None else None,
    )
    attempt = 0
    last_progress = _progress()
    while True:
        code = launch(
            resource_spec, argv,
            num_local_processes=num_local_processes,
            coordinator_port=coordinator_port,
            extra_env={"AUTODIST_RESTART": str(attempt)},
            # max_restarts=0 keeps exact unsupervised fail-fast semantics
            # (immediate os._exit on worker death) — there is no restart
            # loop to protect, so the reference behavior wins. ft_config
            # passes through REGARDLESS: the hang watchdog and the
            # AUTODIST_FT_DIR export are useful with zero restarts too (a
            # hung fleet still becomes a reportable non-zero exit).
            supervised=max_restarts > 0,
            ft_config=ft_config,
        )
        if code != 0:
            step_now = _progress()
            if step_now is not None and (
                    last_progress is None or step_now > last_progress):
                if attempt:
                    logging.info(
                        "fleet progressed to snapshot step %d since the last "
                        "restart; resetting the restart budget and backoff",
                        step_now)
                attempt = 0
                backoff.reset()
                last_progress = step_now
        if code == 0 or attempt >= max_restarts:
            if code != 0:
                logging.error(
                    "fleet failed rc=%d after %d restart(s) without "
                    "progress; giving up", code, attempt,
                )
            return code
        attempt += 1
        delay = backoff.next_delay()
        logging.warning(
            "fleet exited rc=%d; restarting (%d/%d) in %.1fs",
            code, attempt, max_restarts, delay,
        )
        if delay > 0:
            (restart_sleep or time.sleep)(delay)


def _launch_local_fleet(
    argv: List[str], n: int, coordinator_port: Optional[int],
    base_env: Optional[dict] = None, watch: Optional[_FleetWatch] = None,
) -> int:
    """Emulate an n-host cluster on one machine (testing path).

    Every process of the emulated fleet is pinned to the CPU platform by
    its environment: a TPU chip belongs to one process at a time, so n
    local processes that each initialized the default backend would fail
    or hang on the same chip. (A real multi-host TPU launch is one process
    per host through the SSH path, never this one.)

    ``base_env`` replaces the inherited environment — except the framework
    role vars, which are scrubbed from either source and set explicitly
    below (see :func:`_scrub_role_vars`).
    """
    port = coordinator_port or const.DEFAULT_COORDINATOR_PORT
    coord = f"127.0.0.1:{port}"
    inherited = _scrub_role_vars(
        dict(os.environ) if base_env is None else dict(base_env)
    )
    inherited["JAX_PLATFORMS"] = "cpu"
    procs: List[subprocess.Popen] = []
    for pid_idx in range(1, n):
        env = {
            **inherited,
            ENV.AUTODIST_WORKER.name: f"local-process-{pid_idx}",
            ENV.AUTODIST_COORDINATOR.name: coord,
            ENV.AUTODIST_NUM_PROCESSES.name: str(n),
            ENV.AUTODIST_PROCESS_ID.name: str(pid_idx),
        }
        procs.append(subprocess.Popen(argv, env=env, start_new_session=True))
    env = {
        **inherited,
        ENV.AUTODIST_COORDINATOR.name: coord,
        ENV.AUTODIST_NUM_PROCESSES.name: str(n),
        ENV.AUTODIST_PROCESS_ID.name: "0",
    }
    chief = subprocess.Popen(argv, env=env)
    if watch is not None:
        watch.start(chief)
    code = chief.wait()
    if watch is not None:
        watch.stop()
        if watch.hang_detected and code == 0:
            code = 1
    for p in procs:
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.terminate()
            code = code or 1
    return code


def initialize_from_env() -> None:
    """Worker/chief-side runtime join, driven purely by the env contract.

    Call this at the top of a training script launched by :func:`launch`
    (or let ``AutoDist`` call it). Reads ``AUTODIST_COORDINATOR`` /
    ``AUTODIST_NUM_PROCESSES`` / ``AUTODIST_PROCESS_ID`` and calls
    ``jax.distributed.initialize`` when a multi-process launch is detected.
    """
    n = ENV.AUTODIST_NUM_PROCESSES.val
    coord = ENV.AUTODIST_COORDINATOR.val
    if n <= 1 or not coord:
        return
    import jax

    if jax.distributed.is_initialized():
        return  # idempotent: AutoDist.__init__ and user scripts may both call
    write_pidfile()
    logging.info(
        "initialize_from_env: coordinator=%s process=%d/%d",
        coord, ENV.AUTODIST_PROCESS_ID.val, n,
    )
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=n,
        process_id=ENV.AUTODIST_PROCESS_ID.val,
    )


def main(args: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="autodist_tpu.runtime.launcher",
        description="Launch a training script across an autodist_tpu cluster.",
    )
    parser.add_argument("--resource-spec", default="", help="path to resource_spec.yml")
    parser.add_argument(
        "--num-local-processes", type=int, default=0,
        help="emulate N hosts on this machine, every process pinned to "
             "the CPU platform (testing)",
    )
    parser.add_argument("--coordinator-port", type=int, default=0)
    parser.add_argument(
        "--max-restarts", type=int, default=0,
        help="relaunch a non-zero-exiting fleet up to N times; scripts "
             "using init_or_restore resume from their latest checkpoint",
    )
    parser.add_argument("--restart-backoff", type=float, default=5.0)
    parser.add_argument(
        "--ft-dir", default="",
        help="enable fault-tolerance supervision rooted at this shared "
             "dir: fleet processes heartbeat under it, a hung fleet is "
             "terminated for restart, and the restart budget resets "
             "whenever the snapshot ring advances (docs/fault_tolerance.md)",
    )
    parser.add_argument(
        "--trace-out", default="",
        help="shared dir for cross-process span tracing: every fleet "
             "process flushes a chrome-trace part-file here and the "
             "launcher stitches them into one trace-<id>.json after the "
             "run (docs/observability.md)",
    )
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="-- python train.py ...")
    ns = parser.parse_args(args)
    command = [c for c in ns.command if c != "--"]
    if not command:
        parser.error("no command given; usage: launcher --resource-spec s.yml -- python train.py")
    spec = (
        ResourceSpec(ns.resource_spec) if ns.resource_spec else ResourceSpec.from_local_devices()
    )
    ft_config = None
    if ns.ft_dir:
        from autodist_tpu.ft import FTConfig

        ft_config = FTConfig(base_dir=ns.ft_dir)
    if ns.trace_out:
        # launch() reads the env contract; exporting here covers both the
        # launcher's own spans and every process it starts.
        os.environ[ENV.AUTODIST_TRACE_OUT.name] = ns.trace_out
    return launch_supervised(
        spec, command,
        max_restarts=ns.max_restarts,
        num_local_processes=ns.num_local_processes,
        coordinator_port=ns.coordinator_port or None,
        restart_backoff_s=ns.restart_backoff,
        ft_config=ft_config,
    )


if __name__ == "__main__":
    sys.exit(main())
