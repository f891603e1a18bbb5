"""What a CPU can pin about the chip check: `chip_smoke.py` refuses to run
without a TPU (and says why), and the compile-cache helper keeps the cache
where the environment says or at the fixed in-checkout path.

The smoke's legs themselves run only on the chip; `chip_smoke.py
--rehearse-cpu` walks them at tiny widths by hand (see the verify skill).
"""
import os
import subprocess
import sys

import jax
import pytest

from autodist_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def test_chip_smoke_without_tpu_exits_nonzero_and_says_why():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, SMOKE], env=env, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode != 0
    # It states what it found first, then refuses before any leg.
    assert "backend=cpu" in r.stdout
    assert "not 'tpu'" in r.stderr and "no leg was run" in r.stderr
    # No result line: nothing that could be read as a pass, no leg record.
    assert '"ok"' not in r.stdout and '"leg"' not in r.stdout
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    # Set from outside: jax reads the variable itself, the code sets nothing.
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    # Fixed: the path is part of the cache key, so every call (two
    # processes, two runs) must agree — nothing from tempfile, a pid or
    # the clock.
    want = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v4", 275e12), ("TPU v6e", 918e12)])
def test_peak_table_lists_known_kinds(kind, want):
    from autodist_tpu.obs.profiler import peak_flops_for_kind

    assert peak_flops_for_kind(kind) == want


def test_peak_table_unknown_kind_raises():
    """A device the table does not list is an error for a benchmark, not a
    default; the live profiler reports no MFU for it instead of a guess."""
    from autodist_tpu.obs.profiler import (
        detect_peak_flops, peak_flops_for_kind)

    with pytest.raises(ValueError, match="not in the peak-FLOPs table"):
        peak_flops_for_kind("TPU v9 hypothetical")
    with pytest.raises(ValueError):
        peak_flops_for_kind("cpu")

    class _Dev:
        device_kind = "TPU v9 hypothetical"

    assert detect_peak_flops(_Dev()) is None
