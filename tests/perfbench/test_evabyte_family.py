"""Family ``evabyte`` through the benchmark's own driver, on the CPU at a
tiny size (``tiny/configs/evabyte-tiny.json``: 2 layers, hidden 64, window
64, chunk = page 16, bfloat16 leaves): the cell runs ``correct``; the fp8
control and the two planted faults (every summary left out; a window's
summaries shown one window early) read above the limit; the configuration
file is the catalog row; the counts against a hand count on each side of a
window boundary; every metric file this family brings reads a number."""
import contextlib
import io
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import run as bench  # noqa: E402
from perfbench.harness import compare, device, manifest, trace  # noqa: E402

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny")
TINY_MANIFEST = os.path.join(TINY, "BENCHMARK.evabyte.json")
MANIFEST = manifest.load()
CELL, TINY_CELL = "evabyte-serve-decode-long", "evabyte-tiny-serve"
FAM = manifest.Cell(MANIFEST, CELL).family()
MODEL = manifest.Cell(MANIFEST, CELL).model
PEAKS = device.PEAKS["TPU v5 lite"]
OWN = [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
_RAN = {}

# architectures.jsonl beside the model-configs guide, row "EvaByte", `config`
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768, "max_seq_length": 32768,
    "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
    "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
    "vocab_size": 320, "window_size": 2048}


def _ran():
    """One ``--trace 0`` run of the tiny cell with the control and both
    faults read beside it, made once."""
    if not _RAN:
        seen, out = {}, io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench.main(
                ["--workload", TINY_CELL, "--seed", str(2 ** 31 + 30), "--seconds", "2",
                 "--trace", "0"], manifest_path=TINY_MANIFEST, data_dir=TINY,
                require_chip=False, t0=time.time(),
                hooks={"control_precisions": ("fp8",) + FAM.FAULTS,
                       "run": lambda run, ctx: seen.update(run=run, ctx=ctx)})
        assert rc == 0, out.getvalue()[-2000:]
        _RAN.update(line=json.loads(out.getvalue().strip().splitlines()[-1]), **seen)
    return _RAN["line"], _RAN["run"], _RAN["ctx"]


def test_the_tiny_cell_runs_through_serve_closed_and_is_correct():
    line, run, ctx = _ran()
    assert line["correct"] is True
    assert set(line["metrics"]) == {"serve_tpot_tail_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert ctx["cell"].family() is FAM, "the benchmark's own family file"
    eng = run["data"]["engine"]
    # the program's chunk, held to the toy's window
    assert eng["page_len"] == 16 and eng["prefill_chunk"] == 64
    # n_slots x (4 ring + 2 summary pages): every stated row at max_len
    assert eng["n_pages"] == 8 * (4 + 2)
    # decoding, not only prefill, crossed window boundaries
    ticks = [s.attrs for s in run["data"]["spans"] if s.name == "serve.tick_metrics"]
    assert ticks[-1]["window_rolls_decode"] > ticks[0]["window_rolls_decode"]


@pytest.mark.parametrize("control", ("fp8",) + FAM.FAULTS)
def test_the_control_and_both_faults_read_not_correct(control):
    line, run, ctx = _ran()
    numbers = dict(run["numbers"], logit_gap=run["numbers"][f"control.{control}.logit_gap"])
    correct, checks = compare.decide(numbers, ctx["cell"].limits)
    assert not correct
    assert checks["logit_gap"][0] > 2 * checks["logit_gap"][1] > 4 * line["checks"]["logit_gap"][0]


def test_check_config_holds_the_file_to_the_catalogs_widths():
    FAM.check_config(MODEL, ["num_hidden_layers"])
    for key in ("hidden_size", "intermediate_size", "window_size", "chunk_size",
                "num_attention_heads", "vocab_size", "num_pred_heads"):
        with pytest.raises(ValueError, match=key):
            FAM.check_config(dict(MODEL, **{key: MODEL[key] // 2}), ["num_hidden_layers"])
        with pytest.raises(ValueError, match="never name a width"):
            FAM.check_config(MODEL, ["num_hidden_layers", key])
    with pytest.raises(ValueError, match="never name a width"):
        FAM.check_config(MODEL, ["head_dim"])


def test_the_configuration_file_is_the_catalog_row_outside_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "evabyte-6.5b-l16")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    for key, value in CATALOG.items():
        assert key in MODEL, key
        if key not in entry["reduced"]:
            assert MODEL[key] == value and type(MODEL[key]) is type(value), key
    assert MODEL["num_hidden_layers"] == 16 and MODEL["published"] == {"num_hidden_layers": 32}
    assert MODEL["serving"] == {"n_slots": 4, "max_len": 32768}
    assert MODEL["param_dtype"] == MODEL["compute_dtype"] == "bfloat16"
    assert "two pipeline stages" in MODEL["deployment"] and MODEL["assumed"]
    mix = manifest.Cell(MANIFEST, CELL).traffic
    from perfbench.harness import traffic

    pairs = traffic.base_lengths(mix)
    assert sum(p % 2048 >= 2048 - o for p, o in pairs) >= 2, "decoding crosses a boundary"
    assert min(p for p, _ in pairs) >= 4096 - 64 and max(p for p, _ in pairs) <= 24576


def test_counts_against_a_hand_count_on_each_side_of_a_boundary():
    d, f, layers, v = 4096, 11008, 16, 320
    per_layer = 4 * d * d + 3 * d * f
    assert per_layer == 202_375_168 and FAM.matmul_params(MODEL) == layers * per_layer
    assert FAM.entries(MODEL, 2047) == 2048 and FAM.entries(MODEL, 2048) == 1 + 128
    assert FAM.entries(MODEL, 24575) == 2048 + 11 * 128 and FAM.entries(MODEL, 24576) == 1 + 12 * 128
    fixed = 2 * layers * per_layer + layers * 8 * d + 2 * d * v
    # the query at position 2047 (context 2048) sees its whole window; the
    # next one, the first of window 1, one key and 128 summaries
    assert FAM.decode_flops(MODEL, 2048) == fixed + layers * 4 * d * 2048
    assert FAM.decode_flops(MODEL, 2049) == fixed + layers * 4 * d * 129
    assert FAM.entries_sum(MODEL, 2049) == 2048 * 2049 // 2 + 129
    assert FAM.entries_sum(MODEL, 5000) == sum(FAM.entries(MODEL, p) for p in range(5000))
    for prompt in (2048, 2049):
        assert FAM.prefill_flops(MODEL, prompt) == (
            2 * layers * per_layer * prompt + layers * 4 * d * FAM.entries_sum(MODEL, prompt)
            + layers * 8 * d * prompt + 2 * d * v)
    # one layer's call: rows x entries x (4 d operations, 16 KB of key and value)
    flops, bytes_ = FAM.kernel_work("eva_paged_attention", MODEL, {"rows": 4, "entries": 129})
    assert (flops, bytes_) == (4 * 129 * 4 * d, 4 * 129 * 16384)
    # a mean context alone: the window's phase taken as uniform
    flops, bytes_ = FAM.kernel_work("eva_paged_attention", MODEL,
                                    {"rows": 3.5, "context": 10000.5, "engine": {}})
    assert bytes_ == pytest.approx(3.5 * (2049 / 2 + 4 * 128) * 16384)
    flops, bytes_ = FAM.kernel_work("eva_paged_attention", MODEL, {
        "rows": 1, "queries": 512, "entries": 1000.0, "entries_read": 1800})
    assert (flops, bytes_) == (512 * 1000 * 4 * d, 1800 * 16384)
    with pytest.raises(KeyError):
        FAM.kernel_work("paged_attention", MODEL, {"rows": 1, "entries": 1})
    assert FAM.served_entries(MODEL, [(2047, 3)]) == (2048 + 129) / 2
    assert FAM.served_entries(MODEL, [(100, 1)]) is None


def _eva_trace():
    """Two chunk runs and three decode runs, two layers' kernel calls in
    each, on one device."""
    ops, modules, t = [], [], 0.1
    for name, q in (("jit_serve_prefill_chunk(1)", 32), ("jit_serve_decode_step(2)", 1),
                    ("jit_serve_decode_step(2)", 1), ("jit_serve_prefill_chunk(1)", 32),
                    ("jit_serve_decode_step(2)", 1)):
        start = t
        for layer in range(2):
            ops.append((f"%fusion.{layer} = bf16[8,64]{{1,0}} fusion(...)", t, 0.002))
            t += 0.002
            ops.append((f"%eva_paged_attention.{layer} = bf16[8,4,{q},16]{{3,2,1,0}} "
                        "custom-call(...)", t, 0.001))
            t += 0.001
        modules.append((name, start, t - start))
        t += 0.004
    return trace.Trace({0: ops}, {0: modules}, [("$profiler.py:101 start_trace", 0.0, 0.1)])


@pytest.mark.parametrize("metric", OWN)
def test_every_metric_file_of_the_family_reads_a_number(metric):
    assert len(OWN) == 6
    _, run, ctx = _ran()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    read = ctx["cell"].reader(metric).read
    traced = dict(run, trace=_eva_trace(), trace_window_s=1.0,
                  memory_peak_bytes=10_817_067_008)
    value = read(traced, dict(ctx, peaks=PEAKS))
    assert value is not None
    if entry["unit"] == "%":
        assert 0 <= value <= 100
    if entry["source"] == "device_trace":
        assert value > 0
        assert read(dict(run, trace=None), dict(ctx, peaks=PEAKS)) is None, "no trace, nothing read"
    # a program without this PR's span attributes (the parent): nothing,
    # and no raise
    bare = dict(traced, data=dict(run["data"], spans=[
        s for s in run["data"]["spans"]
        if s.name != "serve.decode_step" and s.name != "serve.prefill_chunk"]))
    if "roofline" in metric:
        assert read(bare, dict(ctx, peaks=PEAKS)) is None


def test_the_accepted_readers_read_the_cell_through_this_familys_counts():
    _, run, ctx = _ran()
    # the cell reports the gap's tail alone, yet the family's counts serve
    # every accepted reader of a serving cell
    for metric in ("model.mfu.decode", "model.mfu.tpot"):
        value = ctx["cell"].reader(metric).read(run, dict(ctx, peaks=PEAKS))
        assert value is not None and 0 < value < 100
    assert ctx["cell"].reader("engine.page_util_peak.decode").read(run, ctx) > 0
    assert [m["name"] for m in manifest.Cell(MANIFEST, CELL).end_to_end] == [
        "serve_tpot_tail_s", "setup_s"]
