"""Proof that the room for another model family is real: ``hfkeys``
(``tests/perfbench/tiny/families/hfkeys.py``) shares no model key with
GPT-2, holds bfloat16 leaves and a slice of its vocabulary, and no file that
the families share under ``perfbench/`` knows its name or its keys; yet a
serving cell and a training cell of it run end to end through the benchmark's
own drivers, on the CPU, with ``correct`` true, and the benchmark's own
readers count its work."""
import contextlib
import io
import json
import os
import pathlib
import re
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402
from perfbench.harness import device, manifest  # noqa: E402

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny")
TINY_MANIFEST = os.path.join(TINY, "BENCHMARK.tiny.json")
MANIFEST = manifest.load()
SERVE, TRAIN = "hf-serve", "hf-train"
# the benchmark's own per-layer metrics, by the kind of cell that reports them
OF_KIND = {TRAIN: (".train",), SERVE: (".decode", ".tpot", ".ttft")}
_RAN = {}


def _ran(cell):
    """One ``--trace 0`` run of ``cell``, made once: the result line, and
    the driver's ``run`` and the harness's ``ctx`` as the ``run`` hook saw
    them."""
    if cell not in _RAN:
        seen, out = {}, io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench.main(
                ["--workload", cell, "--seed", str(2 ** 31 + 29), "--seconds", "1",
                 "--trace", "0"], manifest_path=TINY_MANIFEST, data_dir=TINY,
                require_chip=False, t0=time.time(),
                hooks={"run": lambda run, ctx: seen.update(run=run, ctx=ctx)})
        assert rc == 0
        _RAN[cell] = (json.loads(out.getvalue().strip().splitlines()[-1]),
                      seen["run"], seen["ctx"])
    return _RAN[cell]


def _metrics(cell, keep):
    return [m["name"] for m in MANIFEST["per_layer"]
            if m["name"].endswith(OF_KIND[cell]) and keep(m)]


def _reader(metric):
    cells = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)["workloads"]
    return manifest.Cell(MANIFEST, cells[0]).reader(metric).read


def test_the_second_family_shares_no_model_key_with_gpt2():
    gpt2 = manifest.Cell(MANIFEST, "xl-serve-decode-closed").model
    for cell in (SERVE, TRAIN):
        c = manifest.Cell(manifest.load(TINY_MANIFEST), cell, data_dir=TINY)
        assert c.model["family"] == "hfkeys"
        assert c.family_path.startswith(TINY), "a test's data_dir brings its own families"
        # what both state is the harness's or every family's, never a shape
        assert set(c.model) & set(gpt2) <= {"family", "vocab_size", "param_dtype",
                                            "serving", "assumed"}
        assert c.model["intermediate_size"] != 4 * c.model["hidden_size"]
        assert c.family().vocab_size(c.model) < c.model["vocab_size"]
    assert manifest.Cell(manifest.load(TINY_MANIFEST), SERVE,
                         data_dir=TINY).model["param_dtype"] == "bfloat16"


# What every family and cell shares: the command, the builder's tools, the
# harness, the drivers and the metric readers. ``configs/`` and
# ``families/`` are the families' own, and a family whose published keys are
# HF's (EvaByte's ``hidden_size``, ...) states them there letter for letter.
SHARED = ("run.py", "control.py", "faults.py", "harness", "drivers", "metrics")


def _knowing_the_second_family(bench_dir):
    """``(file, word)`` for every shared file under ``bench_dir`` that holds
    the toy family's name or one of its model keys."""
    own = json.load(open(os.path.join(TINY, "configs", "hfkeys-tiny.json")))
    words = ["hfkeys"] + [k for k in own if k not in (
        "family", "vocab_size", "param_dtype", "serving", "assumed")]
    assert "hidden_size" in words and "num_hidden_layers" in words
    found = []
    for top in SHARED:
        top = pathlib.Path(bench_dir, top)
        assert top.exists(), top
        for path in [top] if top.is_file() else sorted(top.rglob("*")):
            if path.suffix in (".py", ".json"):
                text = path.read_text(encoding="utf-8")
                found += [(path.name, word) for word in words
                          if re.search(rf"\b{word}\b", text)]
    return found


def test_no_file_of_perfbench_knows_the_second_family():
    assert _knowing_the_second_family(manifest.BENCH_DIR) == []


def test_a_shared_file_that_learns_a_familys_key_is_found(tmp_path):
    """The walk above on a copy of the shared files with one key of the
    second family written into ``harness/``: it must be found, there and in
    no other file."""
    for top in SHARED:
        src = os.path.join(manifest.BENCH_DIR, top)
        if os.path.isdir(src):
            shutil.copytree(src, tmp_path / top,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, tmp_path / top)
    assert _knowing_the_second_family(str(tmp_path)) == []
    with open(tmp_path / "harness" / "counts.py", "a", encoding="utf-8") as f:
        f.write('\nWIDTH = lambda model: model["hidden_size"]\n')
    assert _knowing_the_second_family(str(tmp_path)) == [("counts.py", "hidden_size")]


@pytest.mark.parametrize("cell,metric", [(SERVE, "serve_tok_s"), (TRAIN, "train_tok_s_chip")])
def test_a_cell_of_the_second_family_runs_end_to_end_and_is_correct(cell, metric):
    line, run, ctx = _ran(cell)
    assert line["correct"] is True
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for value, limit in line["checks"].values():
        assert value is not None and value <= limit
    assert ctx["cell"].model["family"] == "hfkeys"
    if cell == SERVE:
        vocab = ctx["cell"].family().vocab_size(ctx["cell"].model)
        assert run["data"]["tokens"] > 0 and vocab == 4096
        assert line["checks"]["out_of_vocab"] == [0.0, 0]


@pytest.mark.parametrize("cell,metric", [
    (c, m) for c in (SERVE, TRAIN)
    for m in _metrics(c, lambda m: m["source"] == "device_trace")])
def test_trace_side_readers_read_none_without_a_trace(cell, metric):
    _, run, ctx = _ran(cell)
    assert run["trace"] is None
    assert _reader(metric)(run, dict(ctx, peaks=device.PEAKS["TPU v5 lite"])) is None


@pytest.mark.parametrize("cell,metric", [
    (c, m) for c in (SERVE, TRAIN) for m in _metrics(c, lambda m: "mfu" in m["name"])])
def test_mfu_readers_count_through_the_second_familys_keys(cell, metric):
    """With the chip's peaks handed in, the share is a count of the
    family's required work over this CPU run's time: never a device
    number, only proof that the reader asks the family and no GPT-2 key."""
    _, run, ctx = _ran(cell)
    assert _reader(metric)(run, ctx) is None, "no peaks on the CPU, no share"
    value = _reader(metric)(run, dict(ctx, peaks=device.PEAKS["TPU v5 lite"]))
    assert value is not None and 0 < value < 100


@pytest.mark.parametrize("cell,metric", [
    (SERVE, "kernel.paged_decode_roofline.decode"), (SERVE, "model.prefill_busy_share.decode"),
    (TRAIN, "kernel.flash_roofline.train")])
def test_trace_readers_read_a_profile_through_the_second_familys_kernel_work(cell, metric):
    oracle = manifest.load_module(os.path.join(
        ROOT, "tests", "perfbench", "test_selection.py"), "perfbench_selection_oracle")
    _, run, ctx = _ran(cell)
    traced = dict(run, trace=oracle.chip_like_trace(), trace_window_s=1.0)
    value = _reader(metric)(traced, dict(ctx, peaks=device.PEAKS["TPU v5 lite"]))
    assert value is not None and 0 < value < 100
