"""The driver gate: ``dryrun_multichip`` must self-provision its mesh.

Round-1 failure mode (VERDICT.md missing #1): the dry run demanded a
pre-set ``XLA_FLAGS`` and went red under the driver, whose process has the
real single-chip backend already initialized. These tests pin both rescue
paths: running directly on an already-provisioned mesh, and re-exec'ing a
subprocess when the parent backend is too small.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import __graft_entry__ as graft  # noqa: E402


def test_dryrun_runs_on_preprovisioned_mesh():
    # conftest provisioned the 8-device CPU mesh; no subprocess needed.
    graft.dryrun_multichip(8)


@pytest.mark.integration
def test_dryrun_4_devices():
    # conftest pins 8 devices, so this deliberately exercises the
    # count-mismatch subprocess path with a dp+tp (no sp) mesh.
    graft.dryrun_multichip(4)


@pytest.mark.integration
def test_dryrun_reexecs_when_backend_too_small():
    # Simulate the driver: a fresh process whose backend is initialized
    # with a single device before the dry run is requested.
    code = (
        "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=1';"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "import jax; jax.config.update('jax_platforms','cpu');"
        "assert len(jax.devices()) == 1;"
        "import __graft_entry__ as g; g.dryrun_multichip(8)"
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "dryrun_multichip ok" in proc.stdout
