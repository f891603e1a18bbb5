"""Unit tests for bench.py's pure helpers and its no-TPU contract.

bench.py is the driver-facing perf surface. The measurement path itself
runs only on a TPU (the chip run exercises it); what is pinned here is what
a CPU can check: result-line formatting, recovery of a killed child's
provisional line, and that a machine without a TPU gets a non-zero exit
and no line at all — never a fallback number.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _head(unit_per="tokens", mfu=0.5):
    return {"unit_per": unit_per, "mfu": mfu, "units_per_sec": 1000.0,
            "achieved": 1e12, "n_chips": 1, "batch_size": 64, "loss": 2.0,
            "seq": 128, "device": "TPU v5 lite"}


def test_format_result_headline_bert_with_resnet_extras(bench):
    measured = {"resnet": _head(unit_per="images", mfu=0.2), "bert": _head()}
    r = bench._format_result(measured, {})
    assert r["metric"] == "bert_base_mfu" and r["value"] == 0.5
    assert r["resnet50_mfu"] == 0.2
    assert r["vs_baseline"] == pytest.approx(1.0)
    assert r["device"] == "TPU v5 lite"


def test_format_result_resnet_only_and_errors(bench):
    measured = {"resnet": _head(unit_per="images", mfu=0.2)}
    r = bench._format_result(measured, {"bert": "timed out"})
    assert r["metric"] == "resnet50_mfu"
    assert r["bert_error"] == "timed out"
    assert "seq_len" not in r


def test_format_result_bert_large_extras_and_head(bench):
    # bert_large rides as extras beside the bert head (full sweep)...
    measured = {"bert": _head(), "bert_large": _head(mfu=0.73)}
    r = bench._format_result(measured, {})
    assert r["metric"] == "bert_base_mfu"
    assert r["bert_large_mfu"] == 0.73
    assert r["bert_large_vs_baseline"] == pytest.approx(1.46)
    # ...and heads its own line (with seq_len) on a restricted run.
    r = bench._format_result({"bert_large": _head(mfu=0.73)}, {})
    assert r["metric"] == "bert_large_mfu" and r["seq_len"] == 128


def test_format_result_carries_watchdog_note(bench):
    w = _head()
    w["note"] = "watchdog killed the sweep after 60s"
    r = bench._format_result({"bert": w}, {})
    assert "watchdog killed" in r["bert_note"]
    json.loads(json.dumps(r))  # strictly serializable


def test_last_json_line_recovers_partial_stdout(bench):
    # Watchdog-killed child: recover the last provisional line from
    # truncated/bytes stdout; garbage after it must not break recovery.
    out = b'log noise\n{"a": 1}\n{"a": 2, "provisional_after": 128}\npartial trunc{'
    assert bench._last_json_line(out) == {"a": 2, "provisional_after": 128}
    assert bench._last_json_line(b"no json here") is None
    assert bench._last_json_line(None) is None
    # A final line killed mid-write falls back to the previous complete
    # provisional line — losing it would defeat the recovery.
    assert bench._last_json_line('{"a": 1}\n{"trunca') == {"a": 1}


@pytest.mark.parametrize("argv", [[], ["--serve", "--model", "bert"]])
def test_no_tpu_exits_nonzero_and_prints_nothing(argv):
    """No TPU: non-zero exit and no stdout at all — so no line under a
    device metric's name, no ``cached`` headline, no ``_cpu_smoke`` key.
    The parent stops at the first child's verdict instead of starting the
    other workloads to fail the same way."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, BENCH, *argv], env=env, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not 'tpu'" in r.stderr
    assert "cached" not in r.stdout and "_cpu_smoke" not in r.stdout


def test_source_has_no_fallback_machinery():
    """The acceptance grep: the probe ladder, the CPU-smoke configs, the
    cached headline, the guessed peak and the injected libtpu flags are
    gone, not dormant."""
    with open(BENCH, encoding="utf-8") as f:
        src = f.read()
    for needle in ("cpu_smoke", "DEFAULT_PEAK", "last_accel", "_preflight",
                   "LIBTPU_INIT_ARGS"):
        assert needle not in src, needle
