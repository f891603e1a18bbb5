"""Family ``kimi_k2`` through the benchmark's own driver, on the CPU at a
tiny size (``tiny/configs/kimi-tiny.json``: 1 dense + 2 expert layers,
hidden 64, 4 of 16 experts held, 4 a token, float32 leaves): the cell runs
``correct``; the fp8 control and every planted fault read above the limit;
the configuration file is the catalog row outside ``reduced``;
``check_config`` holds it to the catalog's widths; the counts against a
hand count; every metric file this family brings reads a number; the
accepted readers read the cell through this family's counts."""
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
TINY_MANIFEST = os.path.join(TINY, "BENCHMARK.kimi.json")
MANIFEST = manifest.load()
CELL, TINY_CELL = "kimi-serve-decode-closed-32", "kimi-tiny-serve"
FAM = manifest.Cell(MANIFEST, CELL).family()
MODEL = manifest.Cell(MANIFEST, CELL).model
PEAKS = device.PEAKS["TPU v5 lite"]
OWN = [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
SHARED = [m["name"] for m in MANIFEST["per_layer"]
          if CELL in m.get("workloads", ()) and m["name"] not in OWN]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
_RAN = {}

# architectures.jsonl beside the model-configs guide, row "Kimi-K2.6", `config`
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 384, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 50000, "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
    "seq_aux": True, "tf_legacy_loss": False, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


def _ran():
    """One ``--trace 0`` run of the tiny cell with the control and the
    faults read beside it, made once."""
    if not _RAN:
        seen, out = {}, io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench.main(
                ["--workload", TINY_CELL, "--seed", str(2 ** 31 + 36), "--seconds", "2",
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
    assert set(line["metrics"]) == {"serve_tok_s", "serve_tpot_tail_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert ctx["cell"].family() is FAM, "the benchmark's own family file"
    eng = run["data"]["engine"]
    # the program's own page and chunk; every stated row at max_len
    assert eng["page_len"] == 128 and eng["prefill_chunk"] == 512
    assert eng["n_pages"] == 8 * (1024 // 128)
    # what only the device knows reached the spans and the counters
    steps = [s.attrs for s in run["data"]["spans"] if s.name == "serve.decode_step"]
    assert steps and all(0 <= a["moe_experts_hit"] <= min(8, a["moe_pairs"]) for a in steps)
    ticks = [s.attrs for s in run["data"]["spans"] if s.name == "serve.tick_metrics"]
    assert ticks[-1]["moe_steps"] - ticks[0]["moe_steps"] >= len(steps) - 1
    assert ticks[-1]["moe_pairs"] > ticks[0]["moe_pairs"]


@pytest.mark.parametrize("control", ("fp8",) + FAM.FAULTS)
def test_the_control_and_every_fault_read_not_correct(control):
    line, run, ctx = _ran()
    numbers = dict(run["numbers"], logit_gap=run["numbers"][f"control.{control}.logit_gap"])
    correct, checks = compare.decide(numbers, ctx["cell"].limits)
    assert not correct
    assert checks["logit_gap"][0] > 1.5 * checks["logit_gap"][1] > 3 * line["checks"]["logit_gap"][0]


def test_check_config_holds_the_file_to_the_catalogs_widths():
    FAM.check_config(MODEL, REDUCED)
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_attention_heads", "num_experts_per_tok"):
        with pytest.raises(ValueError, match=key):
            FAM.check_config(dict(MODEL, **{key: MODEL[key] // 2}), REDUCED)
        with pytest.raises(ValueError, match="may name only"):
            FAM.check_config(MODEL, REDUCED + [key])
    with pytest.raises(ValueError, match="n_shared_experts"):
        FAM.check_config(dict(MODEL, n_shared_experts=2), REDUCED)
    with pytest.raises(ValueError, match="rope_scaling"):
        FAM.check_config(dict(MODEL, rope_scaling=dict(MODEL["rope_scaling"], factor=32)),
                         REDUCED)
    with pytest.raises(ValueError, match="share"):
        FAM.check_config(dict(MODEL, n_routed_experts=8), REDUCED)


def test_the_configuration_file_is_the_catalog_row_outside_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "kimi-k2.6-ep32-l7")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == "https://huggingface.co/moonshotai/Kimi-K2.6/blob/main/config.json"
    for key, value in CATALOG.items():
        assert key in MODEL, key
        if key not in REDUCED:
            assert MODEL[key] == value and type(MODEL[key]) is type(value), key
    assert (MODEL["num_hidden_layers"], MODEL["n_routed_experts"], MODEL["vocab_size"]) \
        == (7, 12, 20480)
    assert MODEL["published"] == {k: CATALOG[k] for k in REDUCED}
    assert MODEL["share"]["chips_per_layer"] == 32 and FAM.experts_held(MODEL) == (0, 12)
    assert FAM.routed_experts(MODEL) == 384, "the router keeps its 384 outputs"
    assert MODEL["serving"] == {"n_slots": 32, "max_len": 8192}
    assert MODEL["param_dtype"] == MODEL["compute_dtype"] == "bfloat16"
    assert "32 chips share each layer" in MODEL["deployment"] and MODEL["assumed"]
    cfg = FAM.program_config(MODEL)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (384, (0, 12), 20480)
    mix = manifest.Cell(MANIFEST, CELL).traffic
    from perfbench.harness import traffic

    pairs = traffic.base_lengths(mix)
    assert mix["clients"] == 32 == MODEL["serving"]["n_slots"] and len(pairs) == 8
    assert min(p for p, _ in pairs) == 512 and max(p for p, _ in pairs) == 3072
    assert max(p + o for p, o in pairs) <= 3840
    # the cell reports what the issue named, the gap's tail among it
    assert [m["name"] for m in manifest.Cell(MANIFEST, CELL).end_to_end] == [
        "serve_tok_s", "serve_tpot_tail_s", "setup_s"]
    assert 80 < mix["tail_percentile"] < 99, "among the ticks that carry a chunk"
    # the parameters as the deployment states them: 9.70 GB in bfloat16
    import jax

    n = sum(x.size for x in jax.tree.leaves(FAM.param_shapes(MODEL)))
    assert n == pytest.approx(4849.6e6, rel=2e-4)
    assert jax.tree.leaves(FAM.param_shapes(MODEL))[0].dtype == "bfloat16"


def test_counts_against_a_hand_count():
    d, h, layers, v = 7168, 64, 7, 20480
    attn = d * 1536 + 1536 * h * 192 + d * 576 + h * 128 * d
    one = 3 * d * 2048
    expert = d * 384 + one * (1 + 8 * 12 / 384)
    params = layers * attn + 3 * d * 18432 + 6 * expert
    assert attn == 92_733_440 and one == 44_040_192
    assert FAM.matmul_params(MODEL) == params == 1_392_312_320
    absorb = 2 * h * 512 * (128 + 128)
    assert FAM.decode_flops(MODEL, 2400) == (
        2 * params + layers * (absorb + 2 * h * (512 + 64 + 512) * 2400) + 2 * d * v)
    assert FAM.decode_flops(MODEL, 2400) == pytest.approx(5.535e9, rel=1e-3)
    expand = 2 * 512 * h * 256
    assert FAM.prefill_flops(MODEL, 1000) == (
        2 * params * 1000 + layers * (expand * 1000 + 2 * h * (192 + 128) * 1000 * 1001 // 2)
        + 2 * d * v)
    # one layer's call of the latent kernel: every row of the pool once
    flops, bytes_ = FAM.kernel_work("mla_paged_attention", MODEL, {
        "rows": 32, "context": 2400.0, "engine": {}})
    assert (flops, bytes_) == (32 * 2400 * 2 * h * 1088, 32 * 2400 * 576 * 2)
    assert flops / bytes_ == pytest.approx(121, abs=0.5), "near the chip's ridge"
    # one projection of the grouped product: the experts hit, once; the
    # facts are a step's, summed over the 6 expert layers
    flops, bytes_ = FAM.kernel_work("gmm", MODEL, {
        "pairs": 6 * 8, "experts_hit": 6 * 5.5})
    assert flops == 8 * 2 * d * 2048
    assert bytes_ == (5.5 * d * 2048 + 8 * (d + 2048)) * 2
    with pytest.raises(KeyError):
        FAM.kernel_work("paged_attention", MODEL, {"rows": 1, "context": 1})
    assert FAM.held_expert_slots(MODEL) == 72
    assert FAM.softmax_scale(MODEL) == pytest.approx(0.14468, abs=1e-5)


def test_the_references_rotation_is_the_programs():
    """Two copies of YaRN's arithmetic, the family's and the program's."""
    import numpy as np

    from autodist_tpu.models import kimi_k2 as K

    np.testing.assert_allclose(FAM.yarn_inv_freq(MODEL),
                               K.yarn_inv_freq(FAM.program_config(MODEL)), rtol=1e-6)


def _chip_like_trace():
    """Two chunk runs and three decode runs on one device: the decode
    program calls the latent kernel once a layer and the grouped product
    three times an expert layer; the chunk program the grouped product."""
    ops, modules, t = [], [], 0.1
    for name in ("jit_serve_prefill_chunk(1)", "jit_serve_decode_step(2)",
                 "jit_serve_decode_step(2)", "jit_serve_prefill_chunk(1)",
                 "jit_serve_decode_step(2)"):
        start = t
        for layer in range(3):
            ops.append((f"%fusion.{layer} = bf16[8,64]{{1,0}} fusion(...)", t, 0.002))
            t += 0.002
            if "decode" in name:
                ops.append((f"%mla_paged_attention.{layer} = bf16[8,4,32]{{2,1,0}} "
                            "custom-call(...)", t, 0.0005))
                t += 0.0005
            for j in range(3 if layer else 0):
                ops.append((f"%gmm.{layer * 3 + j} = bf16[256,48]{{1,0}} "
                            "custom-call(...)", t, 0.0004))
                t += 0.0004
        modules.append((name, start, t - start))
        t += 0.004
    return trace.Trace({0: ops}, {0: modules}, [("$profiler.py:101 start_trace", 0.0, 0.1)])


@pytest.mark.parametrize("metric", OWN)
def test_every_metric_file_of_the_family_reads_a_number(metric):
    assert len(OWN) == 5
    _, run, ctx = _ran()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    read = ctx["cell"].reader(metric).read
    traced = dict(run, trace=_chip_like_trace(), trace_window_s=1.0)
    value = read(traced, dict(ctx, peaks=PEAKS))
    assert value is not None and 0 < value <= 100 and entry["unit"] == "%"
    if entry["source"] == "device_trace":
        assert read(dict(run, trace=None), dict(ctx, peaks=PEAKS)) is None, "no trace, nothing read"
    # a program without this PR's span attributes and counters (the
    # parent): nothing, and no raise
    bare = dict(traced, data=dict(run["data"], spans=[
        s for s in run["data"]["spans"]
        if s.name not in ("serve.decode_step", "serve.tick_metrics")]))
    if "moe_experts" in metric:
        assert read(bare, dict(ctx, peaks=PEAKS)) is None
    # and one whose trace holds no such kernel
    other = dict(traced, trace=trace.Trace({0: [("%fusion.1 = f32[2]{0} fusion()", 0.2, 0.01)]},
                                           {0: [("jit_serve_decode_step(2)", 0.2, 0.01)]}, []))
    if entry["source"] == "device_trace":
        assert read(other, dict(ctx, peaks=PEAKS)) is None


def test_the_hit_share_is_the_counters_increase_over_the_window():
    _, run, ctx = _ran()
    ticks = [s.attrs for s in run["data"]["spans"] if s.name == "serve.tick_metrics"]
    steps = ticks[-1]["moe_steps"] - ticks[0]["moe_steps"]
    hit = ticks[-1]["moe_experts_hit"] - ticks[0]["moe_experts_hit"]
    value = ctx["cell"].reader("model.moe_experts_hit_share.decode").read(run, ctx)
    assert value == pytest.approx(100.0 * hit / steps / 8), "4 held x 2 expert layers"


@pytest.mark.parametrize("metric", SHARED)
def test_the_accepted_readers_read_the_cell_through_this_familys_counts(metric):
    assert len(SHARED) == 18
    _, run, ctx = _ran()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    read = ctx["cell"].reader(metric).read
    if entry["source"] in ("device_trace", "program_span"):
        assert read(run, dict(ctx, peaks=PEAKS)) is None, "no trace on the CPU"
        return
    value = read(dict(run, memory_peak_bytes=11_300_000_000), dict(ctx, peaks=PEAKS))
    assert value is not None and value >= 0
    if "mfu" in metric:
        assert 0 < value < 100
