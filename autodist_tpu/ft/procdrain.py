"""Graceful subprocess termination: signal, grace period, then kill.

Standalone — **zero package imports** — so a supervisor can use it without
importing the framework (or jax) into its own process.

Why this exists: a hard kill gives a process no chance to leave a
consistent state behind. SIGTERM first gives the child its exit path: the
ft preemption hook snapshots, the serve drain persists its queue, a
benchmark's trailing barrier drains and the process releases the chip —
then, only if the grace period expires, the process group is SIGKILLed.
"""
from __future__ import annotations

import os
import signal
import subprocess


def signal_group(proc, sig) -> None:
    """Deliver ``sig`` to the child's process group (it was started with
    ``start_new_session=True``), falling back to the child alone."""
    try:
        os.killpg(proc.pid, sig)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            proc.send_signal(sig)
        except (ProcessLookupError, OSError):
            pass


def stop_gracefully(proc, grace_s: float = 60.0, kill_grace_s: float = 10.0):
    """SIGTERM ``proc``'s group, wait up to ``grace_s`` for a clean exit,
    escalate to SIGKILL, and reap.

    Returns ``(stdout, stderr)`` from the final ``communicate()`` (pipes
    captured by the caller's ``Popen``; ``(None, None)`` otherwise). The
    process is guaranteed reaped on return.
    """
    signal_group(proc, signal.SIGTERM)
    try:
        return proc.communicate(timeout=grace_s)
    except subprocess.TimeoutExpired:
        pass
    signal_group(proc, signal.SIGKILL)
    try:
        return proc.communicate(timeout=kill_grace_s)
    except subprocess.TimeoutExpired:
        # Unreapable (e.g. stuck in an uninterruptible syscall): report what
        # we have; the zombie is the kernel's problem now.
        return None, None
