"""Family ``nemotron_h`` through the benchmark's own driver, on the CPU at a
tiny size (``tiny/configs/nemotron-tiny.json``: one 9-layer period of the
published pattern, hidden 64, 4 of 16 experts held, SSD blocks of 8,
float32 leaves): the cell runs ``correct``; the fp8 control and every
planted fault read above the limit; the configuration file is the catalog
row outside ``reduced``; ``check_config`` holds it to the catalog's widths
and the published pattern; the counts against a hand count; every metric
file this family brings reads a number; the accepted readers, the experts'
among them, read the cell through this family's counts."""
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
TINY_MANIFEST = os.path.join(TINY, "BENCHMARK.nemotron.json")
MANIFEST = manifest.load()
CELL, TINY_CELL = "nemotron-serve-decode-closed-128", "nemotron-tiny-serve"
FAM = manifest.Cell(MANIFEST, CELL).family()
MODEL = manifest.Cell(MANIFEST, CELL).model
PEAKS = device.PEAKS["TPU v5 lite"]
OWN = [m["name"] for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
SHARED = [m["name"] for m in MANIFEST["per_layer"]
          if CELL in m.get("workloads", ()) and m["name"] not in OWN]
EXPERTS = ("kernel.moe_experts_roofline.decode", "kernel.moe_share.decode",
           "model.moe_experts_hit_share.decode")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"]
_RAN = {}

# architectures.jsonl beside the model-configs guide, row
# "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16", `config`
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def _ran():
    """One ``--trace 0`` run of the tiny cell with the control and the
    faults read beside it, made once."""
    if not _RAN:
        seen, out = {}, io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = bench.main(
                ["--workload", TINY_CELL, "--seed", str(2 ** 31 + 39), "--seconds", "2",
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
    spans = run["data"]["spans"]
    dispatch = [s.attrs for s in spans if s.name == "serve.decode_dispatch"]
    steps = [s.attrs for s in spans if s.name == "serve.decode_step"]
    assert dispatch and all(0 < d["ssm_rows"] <= 8 for d in dispatch)
    # summed over the 4 expert layers of 4 held experts each
    assert steps and all(0 <= a["moe_experts_hit"] <= min(16, a["moe_pairs"]) for a in steps)
    ticks = [s.attrs for s in spans if s.name == "serve.tick_metrics"]
    assert ticks[-1]["ssm_rows"] - ticks[0]["ssm_rows"] >= sum(
        d["ssm_rows"] for d in dispatch[1:])
    assert ticks[-1]["moe_steps"] - ticks[0]["moe_steps"] >= len(steps) - 1


@pytest.mark.parametrize("control", ("fp8",) + FAM.FAULTS)
def test_the_control_and_every_fault_read_not_correct(control):
    line, run, ctx = _ran()
    numbers = dict(run["numbers"], logit_gap=run["numbers"][f"control.{control}.logit_gap"])
    correct, checks = compare.decide(numbers, ctx["cell"].limits)
    assert not correct
    assert checks["logit_gap"][0] > 1.5 * checks["logit_gap"][1] > 3 * line["checks"]["logit_gap"][0]


def test_check_config_holds_the_file_to_the_catalogs_widths():
    FAM.check_config(MODEL, REDUCED)
    for key in ("hidden_size", "head_dim", "mamba_num_heads", "mamba_head_dim",
                "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
                "moe_intermediate_size", "moe_shared_expert_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok"):
        with pytest.raises(ValueError, match=key):
            FAM.check_config(dict(MODEL, **{key: MODEL[key] // 2}), REDUCED)
        with pytest.raises(ValueError, match="may name only"):
            FAM.check_config(MODEL, REDUCED + [key])
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        FAM.check_config(dict(MODEL, mlp_hidden_act="silu"), REDUCED)
    with pytest.raises(ValueError, match="first layers"):
        FAM.check_config(dict(MODEL, hybrid_override_pattern="MEMEM*EMEMEM*EMEEM"), REDUCED)
    with pytest.raises(ValueError, match="num_hidden_layers"):
        FAM.check_config(dict(MODEL, num_hidden_layers=17), REDUCED)
    with pytest.raises(ValueError, match="missing"):
        FAM.check_config(dict(MODEL, hybrid_override_pattern="MEMEM", num_hidden_layers=5),
                         REDUCED)
    with pytest.raises(ValueError, match="share"):
        FAM.check_config(dict(MODEL, n_routed_experts=8), REDUCED)


def test_the_configuration_file_is_the_catalog_row_outside_reduced():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "nemotron-3-nano-ep8-l18")
    assert entry["reduced"] == REDUCED
    assert entry["source"] == ("https://huggingface.co/nvidia/"
                               "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    for key, value in CATALOG.items():
        assert key in MODEL, key
        if key not in REDUCED:
            assert MODEL[key] == value and type(MODEL[key]) is type(value), key
    assert (MODEL["num_hidden_layers"], MODEL["hybrid_override_pattern"],
            MODEL["n_routed_experts"], MODEL["vocab_size"]) == (
                18, "MEMEM*EMEMEM*EMEME", 16, 16384)
    assert MODEL["published"] == {k: CATALOG[k] for k in REDUCED}
    assert [MODEL["hybrid_override_pattern"].count(c) for c in "ME*"] == [8, 8, 2]
    assert MODEL["share"]["chips_per_layer"] == 8 and FAM.experts_held(MODEL) == (0, 16)
    assert FAM.routed_experts(MODEL) == 128, "the router keeps its 128 outputs"
    assert MODEL["serving"] == {"n_slots": 128, "max_len": 8192}
    assert MODEL["param_dtype"] == MODEL["compute_dtype"] == "bfloat16"
    assert "8 chips" in MODEL["deployment"] and "rotary" in MODEL["assumed"]
    cfg = FAM.program_config(MODEL)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.vocab_size) == (128, (0, 16), 16384)
    assert cfg.num_hidden_layers == 18 and cfg.expert_act == "relu2"
    mix = manifest.Cell(MANIFEST, CELL).traffic
    from perfbench.harness import traffic

    pairs = traffic.base_lengths(mix)
    assert mix["clients"] == 128 == MODEL["serving"]["n_slots"] and len(pairs) == 8
    assert min(p for p, _ in pairs) == 256 and max(p for p, _ in pairs) == 1536
    assert min(o for _, o in pairs) == 256 and max(o for _, o in pairs) == 768
    assert [m["name"] for m in manifest.Cell(MANIFEST, CELL).end_to_end] == [
        "serve_tok_s", "setup_s"]
    assert "tail_percentile" not in mix, "p90 lies on a class edge here"
    # the parameters as the deployment states them: 3.77 GB in bfloat16
    import jax

    leaves = jax.tree.leaves(FAM.param_shapes(MODEL))
    assert sum(x.size for x in leaves) == pytest.approx(1884e6, rel=1e-3)
    assert leaves[0].dtype == "bfloat16"
    # the state a slot carries: 2.134 MB a Mamba layer, 2.19 GB at 128 slots
    from autodist_tpu.models import nemotron_h as N

    state = jax.eval_shape(lambda: N.init_slot_state(cfg, 128))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state)) == \
        128 * 8 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


def test_counts_against_a_hand_count():
    d, v = 2688, 16384
    mamba = d * (4096 + 6144 + 64) + 4096 * d
    attn = d * (4096 + 2 * 256) + 4096 * d
    expert = d * 128 + 2 * d * 3712 + 6 * 16 / 128 * 2 * d * 1856
    params = 8 * mamba + 2 * attn + 8 * expert
    assert mamba == 38_707_200 and attn == 23_396_352
    assert FAM.matmul_params(MODEL) == pytest.approx(params)
    per_token = 2 * params + 8 * (2 * 4 * 6144 + 5 * 64 * 64 * 128)
    assert FAM.decode_flops(MODEL, 1200) == pytest.approx(
        per_token + 2 * (2 * 32 * 2 * 128) * 1200 + 2 * d * v)
    assert FAM.prefill_flops(MODEL, 1000) == pytest.approx(
        per_token * 1000 + 2 * (2 * 32 * 2 * 128) * 1000 * 1001 // 2 + 2 * d * v)
    # one layer's state update over 128 rows: each row's float32 state read
    # and written once, 5 operations an element: memory bounds it
    flops, bytes_ = FAM.kernel_work("ssm_state_update", MODEL, {"ssm_rows": 128})
    assert flops == 128 * 5 * 64 * 64 * 128
    assert bytes_ == 128 * 4 * (2 * 64 * 64 * 128 + 2 * 64 * 64 + 64 + 2 * 8 * 128)
    assert flops / bytes_ < 1
    # one projection of the grouped product, the facts a step's over the 8
    # expert layers
    flops, bytes_ = FAM.kernel_work("gmm", MODEL, {"pairs": 8 * 96, "experts_hit": 8 * 15.5})
    assert flops == 96 * 2 * d * 1856
    assert bytes_ == (15.5 * d * 1856 + 96 * (d + 1856)) * 2
    # one attention layer's paged call over 128 rows at context 1,200: the
    # two KV heads' keys and values read once, 32 query heads' two products
    flops, bytes_ = FAM.kernel_work("paged_attention", MODEL, {"rows": 128, "context": 1200})
    assert flops == 128 * 1200 * 32 * 2 * 2 * 128
    assert bytes_ == 128 * 1200 * 2 * 2 * 128 * 2
    with pytest.raises(KeyError):
        FAM.kernel_work("mla_paged_attention", MODEL, {"rows": 1, "context": 1})
    assert FAM.held_expert_slots(MODEL) == 128


def _chip_like_trace():
    """Two chunk runs and three decode runs on one device: the decode
    program updates the state once a Mamba layer, calls the grouped
    product twice an expert layer and the paged kernel once in the last
    layer; the chunk program the grouped product."""
    ops, modules, t = [], [], 0.1
    for name in ("jit_serve_prefill_chunk(1)", "jit_serve_decode_step(2)",
                 "jit_serve_decode_step(2)", "jit_serve_prefill_chunk(1)",
                 "jit_serve_decode_step(2)"):
        start = t
        for layer in range(4):
            ops.append((f"%fusion.{layer} = bf16[8,64]{{1,0}} fusion(...)", t, 0.002))
            t += 0.002
            if "decode" in name and layer % 2 == 0:
                ops.append((f"%ssm_state_update.{layer} = (f32[8,8,8]{{2,1,0}}, "
                            "f32[8,8,8,16]{3,2,1,0}) custom-call(...)", t, 0.0005))
                t += 0.0005
            for j in range(2 if layer % 2 else 0):
                ops.append((f"%gmm.{layer * 2 + j} = bf16[256,48]{{1,0}} "
                            "custom-call(...)", t, 0.0004))
                t += 0.0004
        if "decode" in name:
            ops.append(("%paged_attention.9 = bf16[8,16,2,16]{3,2,1,0} custom-call(...)",
                        t, 0.0003))
            t += 0.0003
        modules.append((name, start, t - start))
        t += 0.004
    return trace.Trace({0: ops}, {0: modules}, [("$profiler.py:101 start_trace", 0.0, 0.1)])


@pytest.mark.parametrize("metric", OWN)
def test_every_metric_file_of_the_family_reads_a_number(metric):
    assert len(OWN) == 2
    _, run, ctx = _ran()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    read = ctx["cell"].reader(metric).read
    traced = dict(run, trace=_chip_like_trace(), trace_window_s=1.0)
    value = read(traced, dict(ctx, peaks=PEAKS))
    assert value is not None and 0 < value <= 100 and entry["unit"] == "%"
    assert read(dict(run, trace=None), dict(ctx, peaks=PEAKS)) is None, "no trace, nothing read"
    # a program without this PR's span attributes (the parent): nothing,
    # and no raise
    bare = dict(traced, data=dict(run["data"], spans=[
        s for s in run["data"]["spans"] if s.name != "serve.decode_dispatch"]))
    if "roofline" in metric:
        assert read(bare, dict(ctx, peaks=PEAKS)) is None
    # and one whose trace holds no such kernel
    other = dict(traced, trace=trace.Trace({0: [("%fusion.1 = f32[2]{0} fusion()", 0.2, 0.01)]},
                                           {0: [("jit_serve_decode_step(2)", 0.2, 0.01)]}, []))
    assert read(other, dict(ctx, peaks=PEAKS)) is None


def test_the_roofline_is_the_rows_updated_over_the_calls_time():
    _, run, ctx = _ran()
    traced = dict(run, trace=_chip_like_trace(), trace_window_s=1.0)
    read = ctx["cell"].reader("kernel.ssm_decode_roofline.decode").read
    rows = [s.attrs["ssm_rows"] for s in run["data"]["spans"]
            if s.name == "serve.decode_dispatch"]
    from perfbench.harness import counts

    least = counts.roofline_seconds(*FAM.kernel_work(
        "ssm_state_update", ctx["cell"].model, {"ssm_rows": sum(rows) / len(rows)}), PEAKS)[0]
    assert read(traced, dict(ctx, peaks=PEAKS)) == pytest.approx(100.0 * 6 * least / (6 * 0.0005))


@pytest.mark.parametrize("metric", EXPERTS)
def test_the_experts_readers_read_the_cell_through_this_familys_counts(metric):
    """The readers Kimi-K2.6's cell brought read this family's runs as
    they stand: the facts on the spans, ``held_expert_slots``, ``kernel_work``."""
    _, run, ctx = _ran()
    read = ctx["cell"].reader(metric).read
    traced = dict(run, trace=_chip_like_trace(), trace_window_s=1.0)
    value = read(traced, dict(ctx, peaks=PEAKS))
    assert value is not None and 0 < value <= 100
    if metric == "model.moe_experts_hit_share.decode":
        ticks = [s.attrs for s in run["data"]["spans"] if s.name == "serve.tick_metrics"]
        steps = ticks[-1]["moe_steps"] - ticks[0]["moe_steps"]
        hit = ticks[-1]["moe_experts_hit"] - ticks[0]["moe_experts_hit"]
        assert value == pytest.approx(100.0 * hit / steps / 16), "4 held x 4 expert layers"


@pytest.mark.parametrize("metric", SHARED)
def test_the_accepted_readers_read_the_cell_through_this_familys_counts(metric):
    assert len(SHARED) == 16
    _, run, ctx = _ran()
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    read = ctx["cell"].reader(metric).read
    if entry["source"] in ("device_trace", "program_span"):
        assert read(run, dict(ctx, peaks=PEAKS)) is None, "no trace on the CPU"
        if metric.startswith("kernel.paged_"):
            # the grouped-KV fold's calls, through this family's kernel_work
            traced = dict(run, trace=_chip_like_trace(), trace_window_s=1.0)
            value = read(traced, dict(ctx, peaks=PEAKS))
            assert value is not None and 0 < value <= 100
        return
    value = read(dict(run, memory_peak_bytes=9_000_000_000), dict(ctx, peaks=PEAKS))
    assert value is not None and value >= 0
    if "mfu" in metric:
        assert 0 < value < 100
