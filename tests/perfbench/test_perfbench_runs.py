"""Whole runs of each engine at a tiny size on the CPU: the last line is
well formed and names the CPU; without a chip the command refuses; the
control (the reference in fp8) and every planted fault come out as not
correct. These skip the harness's look for a chip and drive the rest."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402
from perfbench.harness import compare, manifest  # noqa: E402

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny")
TINY_MANIFEST = os.path.join(TINY, "BENCHMARK.tiny.json")


def _run(capsys, workload, hooks=None, trace=0, seed=2 ** 31 + 5):
    rc = bench.main(["--workload", workload, "--seed", str(seed), "--seconds", "1",
                     "--trace", str(trace)], manifest_path=TINY_MANIFEST,
                    data_dir=TINY, require_chip=False, hooks=hooks, t0=time.time())
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), out, err


def _well_formed(line, metric):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu", "a CPU run names the CPU"
    assert line["device"]["count"] == 1 and line["device"]["memory_peak_bytes"] == 0
    assert set(line["metrics"]) == {metric, "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for value, limit in line["checks"].values():
        assert value is not None and value <= limit


def test_no_chip_no_result(capsys):
    rc = bench.main(["--workload", manifest.load()["workloads"][0]["name"],
                     "--seed", "1", "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 3 and out == "" and "TPU" in err


def test_serve_run_is_well_formed_and_control_fails(capsys):
    line, out, err = _run(capsys, "tiny-serve")
    _well_formed(line, "serve_tok_s")
    assert line["correct"] is True
    assert "tokens per second of the window" in out
    assert "requests sent" in out and "finished" in out and "failed" in out
    assert err.strip().splitlines()[-1] == "correct: True"
    # The control need not decode: over the same prompts and tokens, read
    # the gap of the token that the lower precision puts first.
    cell = manifest.Cell(manifest.load(TINY_MANIFEST), "tiny-serve", data_dir=TINY)
    rng = np.random.default_rng(0)
    finished = [{"prompt": rng.integers(0, 4096, 8, dtype=np.int32),
                 "tokens": list(rng.integers(0, 4096, 100))} for _ in range(16)]
    worst, n_tokens, _, _ = cell.driver().check_served(
        cell.family(), finished, cell.model, 7, 16, ("float32", "bfloat16", "fp8"))
    limit = line["checks"]["logit_gap"][1]
    assert n_tokens == 1600
    assert worst["bfloat16"] <= limit, "the stated precision passes"
    assert worst["fp8"] > 3 * limit, "the reference in fp8 is not correct"


def test_serve_altered_token_is_not_correct(capsys):
    def break_engine(engine):
        real, n = engine.step_many, [0]

        def altered():
            out = real()
            for slot, toks in out.items():
                n[0] += 1
                if n[0] % 7 == 0:
                    out[slot] = [(toks[0] + 1) % 4096]
            return out

        engine.step_many = altered

    line, _, err = _run(capsys, "tiny-serve", {"engine": break_engine})
    assert line["correct"] is False
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]
    assert err.strip().splitlines()[-1] == "correct: False"


def test_train_run_is_well_formed_and_control_fails(capsys):
    line, out, _ = _run(capsys, "tiny-train", {"controls": {"fp8": {"precision": "fp8"}}})
    _well_formed(line, "train_tok_s_chip")
    assert line["correct"] is True
    assert "per-window ms" in out and "loss first" in out
    text = out.split("control fp8: ")[1].splitlines()[0]
    control = eval(text, {"inf": float("inf")})   # the driver's own dict repr
    limits = {k: v[1] for k, v in line["checks"].items()}
    assert not compare.decide(control, limits)[0]
    assert control["loss_gap"] > limits["loss_gap"]


class _Unchanged:
    """The step with its update lost: every window returns the state it got."""

    def __init__(self, step):
        self._step = step

    def __getattr__(self, name):
        return getattr(self._step, name)

    def run(self, state, window, k, stacked=False):
        import jax
        import jax.numpy as jnp

        _, metrics = self._step.run(jax.tree.map(jnp.copy, state), window, k,
                                    stacked=stacked)
        return state, metrics


def _first_rows(share):
    def wrap(loss_fn):
        return lambda p, b: loss_fn(
            p, {"tokens": b["tokens"][: b["tokens"].shape[0] // share]})
    return wrap


@pytest.mark.parametrize("cell,fault,hooks,number", [
    ("tiny-train", "state returned unchanged", {"step": _Unchanged}, "change_gap"),
    ("tiny-train", "half of the batch left out", {"loss_fn": _first_rows(2)}, "grad_gap"),
    # Four chips with the exchange left out: the update is made from one
    # chip's rows alone, a quarter of the batch.
    ("tiny-train-4", "exchange between chips left out", {"loss_fn": _first_rows(4)}, "grad_gap"),
])
def test_train_faults_are_not_correct(capsys, cell, fault, hooks, number):
    line, _, _ = _run(capsys, cell, hooks)
    assert line["correct"] is False, fault
    value, limit = line["checks"][number]
    assert value > 3 * limit, (fault, value, limit)


def test_train_on_four_devices_shards_state_and_is_correct(capsys):
    line, out, _ = _run(capsys, "tiny-train-4")
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["metrics"]["train_tok_s_chip"]["value"] > 0


def test_traced_run_reports_per_layer_metrics_only(capsys):
    line, _, _ = _run(capsys, "tiny-train", trace=1)
    assert "busy_s" in line["device"] and "window_s" in line["device"]
    assert line["device"]["window_s"] > 0
    assert "train_tok_s_chip" not in line["metrics"]
    assert not os.path.exists(os.path.join(ROOT, ".perfbench_trace"))
