"""The benchmark's own parts, on the CPU: the manifest and the files it
names (each configuration's family among them), the traffic generator, the
counts of operations and bytes, the trace reduction, and the plain
reference against the program's forward."""
import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import (compare, counts, device, manifest, readers,  # noqa: E402
                               runtime, trace, traffic)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = manifest.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
PER_LAYER = [m["name"] for m in MANIFEST["per_layer"]]
TINY_DIR = os.path.join(ROOT, "tests", "perfbench", "tiny")
TINY_MANIFEST = manifest.load(os.path.join(TINY_DIR, "BENCHMARK.tiny.json"))
GPT2 = manifest.load_family(os.path.join(manifest.BENCH_DIR, "families", "gpt2.py"))


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MANIFEST["end_to_end"])
    for w in MANIFEST["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = manifest.Cell(MANIFEST, cell)
    assert os.path.isfile(c.driver_path)
    assert hasattr(c.driver(), "run")
    assert c.traffic["driver"] and c.limits
    c.family().check_config(c.model, c.config["reduced"])
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert any("mfu" in m["name"] and m["moves"] != "setup_s" for m in c.per_layer)
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_cells_report_what_it_moves(metric):
    m = next(x for x in MANIFEST["per_layer"] if x["name"] == metric)
    e2e = {x["name"]: x for x in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e
    moved = e2e[m["moves"]]
    cells_of_moved = set(moved.get("workloads", CELLS))
    assert set(m.get("workloads", cells_of_moved)) <= cells_of_moved
    assert os.path.isfile(os.path.join(manifest.BENCH_DIR, "metrics", metric + ".py"))
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"


def _cells_of_every_manifest():
    out = [pytest.param(MANIFEST, None, c, id=c) for c in CELLS]
    for name in sorted(os.listdir(TINY_DIR)):
        if name.startswith("BENCHMARK.") and name.endswith(".json"):
            mf = manifest.load(os.path.join(TINY_DIR, name))
            out += [pytest.param(mf, TINY_DIR, w["name"], id=name[10:-5] + "." + w["name"])
                    for w in mf["workloads"]]
    return out


@pytest.mark.parametrize("mf,data_dir,cell", _cells_of_every_manifest())
def test_no_rename_drops_a_reader_of_a_cell(mf, data_dir, cell):
    """``Cell.per_layer`` leaves out an entry whose ``moves`` the cell does
    not report. So every entry's ``moves`` names an end-to-end entry that
    each of the entry's cells reports, in the benchmark's manifest and the
    tests' own: an end-to-end metric renamed in one place and not the other
    fails here, where it would only silence a reader in a run."""
    e2e = {m["name"]: m for m in mf["end_to_end"]}
    cells = [w["name"] for w in mf["workloads"]]
    for m in mf["per_layer"]:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting, m["name"]
        assert set(m.get("workloads", ())) <= set(cells), m["name"]
    c = manifest.Cell(mf, cell, data_dir=data_dir)
    listed = [m["name"] for m in mf["per_layer"] if cell in m.get(
        "workloads", e2e[m["moves"]].get("workloads", cells))]
    assert [m["name"] for m in c.per_layer] == listed


@pytest.mark.parametrize("mf,data_dir,cell", _cells_of_every_manifest())
def test_a_cell_that_reports_the_gaps_tail_states_its_percentile(mf, data_dir, cell):
    """``serve_tpot_tail_s`` is the gap at the percentile the cell's traffic
    file states, 90, 95 or 99; and the band's mean beside it reads whatever
    gaps a run holds."""
    c = manifest.Cell(mf, cell, data_dir=data_dir)
    if "serve_tpot_tail_s" not in {m["name"] for m in c.end_to_end}:
        return
    assert c.traffic["driver"] == "serve-closed"
    assert c.traffic["tail_percentile"] in (90, 95, 99)
    assert "sched.gap_p90_p99_mean_ms.tpot" in {m["name"] for m in c.per_layer}
    read = c.reader("sched.gap_p90_p99_mean_ms.tpot").read
    assert read({"data": {"gaps": []}}, {}) is None
    assert read({"data": {"gaps": _spikes(0.047)}}, {}) == pytest.approx(
        1e3 * runtime.band_mean(_spikes(0.047)))


def test_every_config_file_is_under_paths_and_used():
    used = {w["config"] for w in MANIFEST["workloads"]}
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        cell = manifest.Cell(MANIFEST, next(
            w["name"] for w in MANIFEST["workloads"] if w["config"] == c["name"]))
        assert cell.model == manifest.load_json(os.path.join(ROOT, c["file"]))
        cell.family().check_config(cell.model, c["reduced"])


# What a cell's driver asks of its configuration's family, beside what
# every cell asks; and the kernels the roofline readers of this tree name.
ASKED_BY_ALL = ("check_config", "vocab_size", "make_params", "make_leaf",
                "param_shapes", "reference_params", "kernel_work")
ASKED_BY_DRIVER = {
    "serve-closed": ("decode_model", "next_token_logits", "logit_gaps",
                     "prefill_flops", "decode_flops"),
    "train-windows": ("loss_fn", "row_shardings", "change_norms", "adam_reference",
                      "train_flops_per_token"),
}
KERNELS_OF_METRIC = {"kernel.paged_decode_roofline.decode": readers.PAGED_KERNELS,
                     "kernel.flash_roofline.train": readers.FLASH_KERNELS}
FACTS_OF_DRIVER = {"serve-closed": {"rows": 3.5, "context": 200.5, "engine": {}},
                   "train-windows": {"rows": 4, "seq": 128}}


def _cells_by_config():
    out = []
    for mf, data_dir in ((MANIFEST, None), (TINY_MANIFEST, TINY_DIR)):
        for c in mf["configs"]:
            cells = [w["name"] for w in mf["workloads"] if w["config"] == c["name"]]
            out.append(pytest.param(mf, data_dir, cells, id=c["name"]))
    return out


@pytest.mark.parametrize("mf,data_dir,cells", _cells_by_config())
def test_every_configs_family_file_gives_what_its_cells_ask(mf, data_dir, cells):
    assert cells, "a configuration no cell uses"
    for name in cells:
        c = manifest.Cell(mf, name, data_dir=data_dir)
        assert os.path.isfile(c.family_path), c.family_path
        assert os.path.basename(c.family_path) == c.model["family"] + ".py"
        fam, driver = c.family(), c.traffic["driver"]
        assert fam is c.family(), "loaded once a process"
        for asked in ASKED_BY_ALL + ASKED_BY_DRIVER[driver]:
            assert callable(getattr(fam, asked, None)), (c.model["family"], asked)
        fam.check_config(c.model, c.config["reduced"])
        assert fam.vocab_size(c.model) > 0
        import jax

        dtypes = {str(leaf.dtype) for leaf in jax.tree.leaves(fam.param_shapes(c.model))}
        assert c.model.get("param_dtype", "float32") in dtypes
        # every kernel that a roofline reader of this cell names
        for metric in c.per_layer:
            for kernel in KERNELS_OF_METRIC.get(metric["name"], ()):
                flops, bytes_ = fam.kernel_work(kernel, c.model, FACTS_OF_DRIVER[driver])
                assert flops > 0 and bytes_ > 0, kernel
        with pytest.raises(KeyError):
            fam.kernel_work("no_such_kernel", c.model, FACTS_OF_DRIVER[driver])
        if driver == "train-windows":
            assert fam.train_flops_per_token(c.model, 128) > 0
        else:
            assert fam.decode_flops(c.model, 64) < fam.prefill_flops(c.model, 64)


def test_gpt2_refuses_a_file_that_is_not_gpt2_and_a_reduced_width():
    model = manifest.Cell(MANIFEST, CELLS[0]).model
    GPT2.check_config(model, ["layer_norm_epsilon"])
    for reduced in (["n_embd"], ["n_inner"], ["head_dim"], ["kv_lora_rank"]):
        with pytest.raises(ValueError):
            GPT2.check_config(model, reduced)
    with pytest.raises(ValueError):
        GPT2.check_config(dict(model, n_inner=model["n_inner"] + 64), [])
    with pytest.raises(ValueError):
        GPT2.check_config(dict(model, n_head=model["n_head"] + 1), [])


# ------------------------------------------------------------------ traffic
def _serve_mix():
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "decode-closed-4.json"))


def test_serve_traffic_same_lengths_for_every_seed_other_ids():
    mix = _serve_mix()
    seen = []
    for seed in (1, 2 ** 31 + 12345, 987654321):
        gen = traffic.ServeTraffic(mix, 50257, seed)
        lengths, ids = [], []
        for client in range(gen.clients):
            for _ in range(len(gen.base)):
                prompt, n_new = gen.next_request(client)
                lengths.append((len(prompt), n_new))
                ids.append(prompt)
        seen.append((sorted(lengths), lengths, np.concatenate(ids)))
    assert seen[0][0] == seen[1][0] == seen[2][0]
    assert seen[0][1] != seen[1][1], "the seed permutes the order"
    assert not np.array_equal(seen[0][2], seen[1][2])
    again = traffic.ServeTraffic(mix, 50257, 1)
    assert np.array_equal(again.next_request(0)[0],
                          traffic.ServeTraffic(mix, 50257, 1).next_request(0)[0])
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert {p for p, _ in seen[0][0]} <= set(range(lo, hi + 1))
    assert min(p for p, _ in seen[0][0]) == lo and max(p for p, _ in seen[0][0]) == hi


def test_stagger_spreads_the_clients_phases():
    gen = traffic.ServeTraffic(_serve_mix(), 50257, 5)
    outs = [gen.stagger_request(c)[1] for c in range(gen.clients)]
    assert outs == sorted(outs) and len(set(outs)) == gen.clients
    assert outs[-1] == _serve_mix()["stagger_output"]


def test_train_rows_from_seed():
    mix = {"rows": 16, "seq_len": 32}
    a, b = traffic.train_rows(mix, 1000, 7), traffic.train_rows(mix, 1000, 7)
    assert np.array_equal(a, b) and a.shape == (16, 32) and a.dtype == np.int32
    assert not np.array_equal(a, traffic.train_rows(mix, 1000, 8))
    assert len({r.tobytes() for r in a}) == 16, "rows all differ"


# ------------------------------------------------------------------- counts
MEDIUM = dict(n_layer=24, n_embd=1024, n_head=16, n_inner=4096, vocab_size=50257)
XL = dict(n_layer=48, n_embd=1600, n_head=25, n_inner=6400, vocab_size=50257)


@pytest.mark.parametrize("model,mm,train_gf,decode_gf", [
    (MEDIUM, 301_989_888, 2.2716, 0.73207),
    (XL, 1_474_560_000, 9.8017, 3.18856),
])
def test_flop_counts_against_hand_worked_values(model, mm, train_gf, decode_gf):
    assert GPT2.matmul_params(model) == mm
    d, v, layers = model["n_embd"], model["vocab_size"], model["n_layer"]
    # by hand: 2 N per token, attention 4 d per pair per layer over
    # S (S + 1) / 2 pairs, the head at S - 1 of S positions; x 3 for training
    s = 1024
    fwd = 2 * mm * s + layers * 4 * d * (s * (s + 1) // 2) + 2 * d * v * (s - 1)
    assert GPT2.train_flops_per_token(model, s) == pytest.approx(3 * fwd / s)
    assert GPT2.train_flops_per_token(model, s) / 1e9 == pytest.approx(train_gf, rel=1e-3)
    assert GPT2.decode_flops(model, 256) == 2 * mm + layers * 4 * d * 256 + 2 * d * v
    assert GPT2.decode_flops(model, 256) / 1e9 == pytest.approx(decode_gf, rel=1e-4)
    assert GPT2.prefill_flops(model, 512) == (
        2 * mm * 512 + layers * 4 * d * (512 * 513 // 2) + 2 * d * v)
    assert GPT2.decode_kv_bytes(model, 256) == layers * 2 * 256 * d * 2
    assert GPT2.flash_flops(model, 2, s, False) == 4 * d * (s * (s + 1) // 2) * 2
    assert GPT2.flash_flops(model, 2, s, True) == 2 * GPT2.flash_flops(model, 2, s, False)
    assert GPT2.flash_bytes(model, 2, s, False) == 4 * 2 * s * d * 2
    # a kernel's work is one layer's, whatever the depth; the two backward
    # kernels together are the whole backward
    one = dict(model, n_layer=1)
    train = {"rows": 2, "seq": s}
    assert GPT2.kernel_work("flash_fwd", model, train) == (
        GPT2.flash_flops(one, 2, s, False), GPT2.flash_bytes(one, 2, s, False))
    back = [GPT2.kernel_work(k, model, train) for k in ("flash_bwd_dkv", "flash_bwd_dq")]
    assert tuple(map(sum, zip(*back))) == (
        GPT2.flash_flops(one, 2, s, True), GPT2.flash_bytes(one, 2, s, True))
    assert GPT2.kernel_work("paged_attention", model, {"rows": 3.5, "context": 200.5}) == (
        3.5 * 4 * d * 200.5, 3.5 * 2 * 200.5 * d * 2)


def test_roofline_says_which_peak_bounds():
    peaks = device.PEAKS["TPU v5 lite"]
    t, bound = counts.roofline_seconds(197e12, 1.0, peaks)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = counts.roofline_seconds(1.0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(device.NoChip):
        device.peaks_for("cpu")


# -------------------------------------------------------------------- trace
def _toy_trace():
    ops = {0: [("fusion.1", 0.0, 1.0), ("fusion.2", 0.5, 1.0),
               ("all-gather.3", 2.0, 0.5), ("all-gather-done.3", 2.25, 0.5),
               ("%copy.7 = bf16[48,337,16]{2,1,0} copy(%p)", 4.0, 1.0)],
           1: [("fusion.1", 0.0, 2.0)]}
    host = [("tick", 0.0, 10.0), ("device_put", 2.9, 1.0), ("sleep", 1.4, 0.7)]
    return trace.Trace(ops, {0: [("jit_a(1)", 0.0, 1.5), ("jit_a(1)", 2.0, 1.0)]}, host)


def test_trace_reduction_on_a_small_trace():
    tr = _toy_trace()
    assert tr.busy_intervals(0) == [[0.0, 1.5], [2.0, 2.75], [4.0, 5.0]]
    assert tr.busy_seconds() == pytest.approx((3.25 + 2.0) / 2)
    assert tr.collective_seconds() == pytest.approx(0.75)
    top = dict(tr.top_ops())
    assert top["fusion"] == pytest.approx(2.0)
    assert top["copy_bf16_48_337_16"] == pytest.approx(1.0)
    assert tr.seconds_matching(r"all-gather") == (pytest.approx(1.0), 2)
    gaps = dict(tr.idle_gaps())
    assert gaps["device_put"] == pytest.approx(1.25)   # 2.75 -> 4.0
    assert gaps["sleep"] == pytest.approx(0.5)         # 1.5 -> 2.0
    assert tr.module_seconds()["jit_a(1)"] == (pytest.approx(2.5), 2)


def test_trace_reader_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.Trace.from_file(trace.find_xplane(str(tmp_path)))
    assert tr.busy_seconds() == 0.0, "a CPU profile holds no TPU plane"
    assert tr.top_ops() == [] and tr.idle_gaps() == []


def _spikes(top_share, n=7000):
    """Gaps of three classes of tick, as the XL decode cell has them:
    22.6 ms, 29.9 ms for 18% of gaps, 34.5 ms for ``top_share`` of them."""
    top, mid = round(n * top_share), round(n * 0.18)
    return [0.0345] * top + [0.0299] * mid + [0.0226] * (n - top - mid)


@pytest.mark.parametrize("case", [
    "percentile", "percentile_of_one", "median",
    "band_of_a_hundred", "band_of_one", "band_shorter_than_it_needs",
    "band_sets_the_slowest_hundredth_aside", "band_takes_any_order",
    "band_is_continuous_where_p95_jumps", "p90_holds_where_p95_jumps"])
def test_percentile_and_median(case):
    if case == "percentile":
        assert runtime.percentile(list(range(1, 101)), 95) == 95
    elif case == "percentile_of_one":
        assert runtime.percentile([3.0], 95) == 3.0
    elif case == "median":
        assert runtime.median([1, 3, 2, 10]) == 2.5
    elif case == "band_of_a_hundred":
        # the values at p90 ... p99, both in it: 90, 91, ..., 99
        assert runtime.band_mean(list(range(1, 101))) == 94.5
        assert runtime.band_mean(list(range(1, 101)), 50, 60) == 55.0
    elif case == "band_of_one":
        assert runtime.band_mean([3.0]) == 3.0
    elif case == "band_shorter_than_it_needs":
        # ten values: p90 is the ninth, p99 the tenth; five: both the fifth
        assert runtime.band_mean([float(i) for i in range(1, 11)]) == 9.5
        assert runtime.band_mean([1.0, 2.0, 3.0, 4.0, 5.0]) == 5.0
    elif case == "band_sets_the_slowest_hundredth_aside":
        calm = [0.02] * 1000
        stalled = [0.02] * 990 + [2.5] * 10     # a machine standing still
        assert runtime.band_mean(stalled) == runtime.band_mean(calm) == 0.02
        assert runtime.band_mean([0.02] * 989 + [2.5] * 11) > 0.02
    elif case == "band_takes_any_order":
        xs = _spikes(0.05)
        mixed = list(np.random.default_rng(0).permutation(xs))
        assert runtime.band_mean(mixed) == pytest.approx(runtime.band_mean(sorted(xs)))
    elif case == "p90_holds_where_p95_jumps":
        # a percentile four points inside a class reads that class whichever
        # side of p95 the top class's edge falls
        assert [runtime.percentile(_spikes(top), 90)
                for top in (0.044, 0.047, 0.053, 0.06)] == [0.0299] * 4
    else:
        # the top class at 4.7% and at 5.3% of 7,000 gaps lies to either
        # side of the 95th percentile; the band's mean moves with its share
        below, above = _spikes(0.047), _spikes(0.053)
        p95 = [runtime.percentile(xs, 95) for xs in (below, above)]
        assert p95 == [0.0299, 0.0345] and p95[1] / p95[0] > 1.15
        tail = [runtime.band_mean(xs) for xs in (below, above)]
        assert tail[0] < tail[1] < 1.01 * tail[0]
        assert 0.0299 < tail[0] and tail[1] < 0.0345


# ---------------------------------------------------------------- reference
TINY = dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, vocab_size=211,
            n_positions=32, layer_norm_epsilon=1e-6)


def test_reference_matches_the_programs_forward_and_loss():
    import jax.numpy as jnp
    from autodist_tpu.models import transformer as T

    params = GPT2.make_params(TINY, 2 ** 31 + 77)
    cfg = T.TransformerConfig(vocab_size=211, num_layers=2, d_model=64, num_heads=4,
                              d_ff=256, max_seq_len=32, dtype=jnp.float32,
                              attention_impl="dot")
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 211, (3, 32)), jnp.int32)
    got = np.asarray(GPT2.logits(params, tokens, TINY))
    want = np.asarray(T.forward(params, tokens, cfg))
    assert np.abs(got - want).max() < 1e-5
    s, n = GPT2.loss_sum(params, tokens, TINY)
    assert float(s) / n == pytest.approx(float(T.loss_fn(params, {"tokens": tokens}, cfg)), rel=1e-5)
    again = GPT2.change_norms(TINY, 2 ** 31 + 77, params)
    assert len(again) == 36 and max(again) < 1e-6, "a leaf made again alone is the same leaf"
    assert max(GPT2.change_norms(TINY, 5, params)) > 0
    path = ("layers_1", "mlp", "fc2", "kernel")
    alone = GPT2.make_leaf(TINY, 2 ** 31 + 77, path)
    assert np.array_equal(np.asarray(alone), np.asarray(params["layers_1"]["mlp"]["fc2"]["kernel"]))
    half = GPT2.make_leaf(dict(TINY, param_dtype="bfloat16"), 2 ** 31 + 77, path)
    assert half.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(half), np.asarray(alone.astype(jnp.bfloat16)))


def test_reference_adam_matches_optax():
    import jax
    import optax

    params = GPT2.make_params(TINY, 11)
    rows = traffic.train_rows({"rows": 12, "seq_len": 32}, 211, 3)
    batches = [rows[0:4], rows[4:8], rows[8:12]]
    losses, grads, change = GPT2.adam_reference(
        params, batches, TINY, learning_rate=3e-4, block_rows=2)
    tx = optax.adam(3e-4)
    p, o = params, tx.init(params)

    def loss(q, b):
        s, n = GPT2.loss_sum(q, b, TINY)
        return s / n

    for i, b in enumerate(batches):
        l, g = jax.value_and_grad(loss)(p, b)
        assert float(l) == pytest.approx(losses[i], rel=1e-5)
        if i == 0:
            want = [float(np.sqrt((np.asarray(x) ** 2).sum())) for x in jax.tree.leaves(g)]
            assert np.allclose(grads, want, rtol=1e-4)
        u, o = tx.update(g, o, p)
        p = optax.apply_updates(p, u)
    want = [float(np.sqrt(((np.asarray(a) - np.asarray(b)) ** 2).sum()))
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(params))]
    keep = compare.moving_leaves(grads)   # a key's bias moves by round-off alone
    assert not all(keep)
    assert compare.worst_leaf_gap(change, want, keep=keep) < 2e-3


def test_compare_worst_leaf_and_limits():
    assert compare.worst_leaf_gap([1.0, 2.0, 0.0], [1.0, 2.2, 1e-9]) == pytest.approx(0.2 / 2.2)
    # a leaf that is all but zero is held against the median leaf
    assert compare.worst_leaf_gap([1.0, 1.0, 0.1], [1.0, 1.0, 0.0]) == pytest.approx(0.1)
    assert compare.moving_leaves([1.0, 1.0, 1e-5]) == [True, True, False]
    ok, checks = compare.decide({"a": 0.1, "b": None}, {"a": 0.2, "b": 1.0, "_note": "x"})
    assert not ok and checks == {"a": [0.1, 0.2], "b": [None, 1.0]}
    assert compare.decide({"a": 0.1}, {"a": 0.2})[0]
    assert not compare.decide({"a": float("nan")}, {"a": 0.2})[0]
