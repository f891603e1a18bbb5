"""The selection oracle. Until PR 29 the readers found the two serving
programs and the paged and flash kernels' calls by GPT-2's shapes in an
operation's text; now they find them by the names the program gives them.
The old selection lives on here (``old_*``: the parent's code, with the
keys it read), and on hand-made traces that carry operation texts of the
kind a chip profile holds, old and new pick the same operations and read
the same share. Where the old one could read nothing (chunk = slots, a pool
of another shape, a flash kernel that takes ``[b, s, h, d]``), the new one
still reads. ``perfbench/control.py --selections`` prints both selections
of a real traced run on the chip.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import counts, device, manifest, readers, trace  # noqa: E402

MANIFEST = manifest.load()
DECODE, PREFILL, TRAIN = ("xl-serve-decode-closed", "xl-serve-prefill-single",
                          "medium-train-1chip")
PEAKS = device.PEAKS["TPU v5 lite"]


# ------------------------------------------- the old selection, by shape
def old_serving_modules(run, model):
    """PR 25's ``readers._serving_modules``: the prefill-chunk program
    works on ``[chunk, d]`` activations, the decode program on ``[slots,
    d]``; None where the two sizes are equal."""
    tr = run.get("trace")
    if tr is None:
        return None
    eng, d = run["data"]["engine"], model["n_embd"]
    if eng["prefill_chunk"] == eng["n_slots"]:
        return None
    prefill, decode = [], []
    for name, runs in tr.module_runs().items():
        texts = [op[0] for op in tr.ops_within(runs[:1])]
        if any(f"[{eng['prefill_chunk']},{d}]" in t for t in texts):
            prefill += runs
        elif any(f"[{eng['n_slots']},{d}]" in t for t in texts):
            decode += runs
    return prefill, decode


def old_paged_calls(run, model):
    """PR 25's paged selection: custom calls inside the decode program
    that read an operand of the pool's shape."""
    found = old_serving_modules(run, model)
    if not found or not found[1]:
        return []
    eng = run["data"]["engine"]
    hd = model["n_embd"] // model["n_head"]
    pool = f"[{eng['n_pages'] + 1},{eng['page_len']},{model['n_head']},{hd}]"
    return [op for op in run["trace"].ops_within(found[1])
            if " custom-call(" in op[0] and pool in op[0]]


def old_flash_calls(run, model):
    """PR 25's flash selection: custom calls whose first operand is
    ``bf16[rows x heads, seq, head size]``."""
    tr, d = run.get("trace"), run["data"]
    if tr is None or not tr.device_ops:
        return []
    rows = d["batch"] // d["chips"]
    hd = model["n_embd"] // model["n_head"]
    operand = f"custom-call(bf16[{rows * model['n_head']},{d['seq']},{hd}]"
    return [op for op in tr.device_ops[min(tr.device_ops)] if operand in op[0]]


def old_paged_roofline(run, model, fam, peaks):
    calls, eng = old_paged_calls(run, model), run["data"]["engine"]
    ticks, c = run["data"]["ticks"], readers.mean_context(run)
    rows = sum(t[2] for t in ticks) / len(ticks) if ticks else eng["n_slots"]
    one_layer = dict(model, n_layer=1)
    least, _ = counts.roofline_seconds(
        rows * fam.attention_flops(one_layer, c),
        rows * fam.decode_kv_bytes(one_layer, c), peaks)
    return 100.0 * least * len(calls) / sum(op[2] for op in calls)


def old_flash_roofline(run, model, fam, peaks):
    calls, d = old_flash_calls(run, model), run["data"]
    rows = d["batch"] // d["chips"]
    steps = sum(len(r) for r in run["trace"].module_runs().values())
    one_layer = dict(model, n_layer=1)
    least = 0.0
    for backward in (False, True):
        least += counts.roofline_seconds(
            fam.flash_flops(one_layer, rows, d["seq"], backward),
            fam.flash_bytes(one_layer, rows, d["seq"], backward), peaks)[0]
    return 100.0 * least * model["n_layer"] * steps / sum(op[2] for op in calls)


# ------------------------------------------------------- a chip-like trace
def _serve_ops(t0, rows, d, pool, heads=25, layers=3, copy=True):
    """The operations of one serving program's run from ``t0``: per layer
    the whole-pool copy (it reads the pool's shape and is no kernel call),
    the layer's slice of the pool, the paged kernel's call and a matmul on
    the ``[rows, d]`` activations."""
    whole = f"[{layers},{pool[1:]}"
    q = f"bf16[{rows},1,{heads},64]" if rows != 16 else f"bf16[1,16,{heads},64]"
    ops, t = [], t0
    for i in range(layers):
        if copy:
            ops.append((f"%copy.{i} = bf16{whole}{{4,3,2,1,0}} copy(bf16{whole}{{4,3,2,1,0}} "
                        f"%param.{i})", t, 0.020))
            t += 0.020
        ops.append((f"%slice_bitcast_fusion.{i} = bf16{pool}{{3,2,1,0}} fusion(bf16{whole} "
                    f"%copy.{i}), kind=kLoop", t, 0.004))
        ops.append((f"%paged_attention.{i} = {q}{{3,2,1,0}} custom-call({q}{{3,2,1,0}} %q.{i}, "
                    f"bf16{pool}{{3,2,1,0}} %slice_bitcast_fusion.{i}, s32[{rows},64]{{1,0}} "
                    f"%tables), custom_call_target=\"tpu_custom_call\"", t + 0.004, 0.003 + 0.0001 * i))
        ops.append((f"%convolution_add_fusion.{i} = bf16[{rows},{d}]{{1,0}} fusion(bf16[{rows},{d}]"
                    f"{{1,0}} %x.{i}, f32[{d},{d}]{{1,0}} %w.{i}), kind=kOutput", t + 0.008, 0.002))
        t += 0.010
    ops.append((f"%sort.1 = f32[{rows},50257]{{1,0}} sort(f32[{rows},50257]{{1,0}} %logits)",
                t, 0.001))
    return ops, t + 0.001


def _train_ops(t0, bh=192, seq=1024, layers=2, folded=True):
    """One train window from ``t0``: per layer the three flash calls, the
    fold transposes around them and a float32 fusion."""
    qkv = (f"bf16[{bh},{seq},64]{{2,1,0}}" if folded
           else f"bf16[{bh // 16},{seq},16,64]{{3,2,1,0}}")
    stat = f"f32[{bh},1,{seq}]{{2,1,0}}"
    ops, t = [], t0
    for i in range(layers):
        ops += [
            (f"%copy.{90 + i} = {qkv} copy(bf16[12,{seq},16,64]{{3,2,1,0}} %q.{i})", t, 0.001),
            (f"%flash_fwd.{i} = ({qkv}, {stat}) custom-call({qkv} %q, {qkv} %k, {qkv} %v), "
             f"custom_call_target=\"tpu_custom_call\"", t + 0.001, 0.0010 + 0.0001 * i),
            (f"%fusion.{40 + i} = f32[1024,1024]{{1,0}} fusion(f32[12,{seq},1024] %dy), "
             f"kind=kOutput", t + 0.003, 0.002),
            (f"%flash_bwd_dkv.{i} = ({qkv}, {qkv}) custom-call({qkv} %q, {qkv} %k, {qkv} %v, "
             f"{qkv} %do, {stat} %lse, {stat} %delta)", t + 0.005, 0.0012),
            (f"%flash_bwd_dq.{i} = {qkv} custom-call({qkv} %q, {qkv} %k, {qkv} %v, {qkv} %do, "
             f"{stat} %lse, {stat} %delta)", t + 0.007, 0.0009),
        ]
        t += 0.008
    return ops, t


def chip_like_trace(chunk=16, slots=4, pool="[257,16,25,64]", copy=True, folded=True,
                    serving=True, train_layers=2):
    """Two prefill-chunk runs, three decode-step runs and two train
    windows on one device, with a small program of another name between.
    (``serving=False`` and all of the model's layers: what a training
    cell's profile holds, and what the old flash arithmetic took for
    granted: nothing but train windows, every layer's calls in each.)"""
    ops, modules, t = [], [], 0.1
    for name, rows in (("jit_serve_prefill_chunk(11)", chunk), ("jit_serve_decode_step(22)", slots),
                       ("jit_serve_decode_step(22)", slots), ("jit_serve_prefill_chunk(11)", chunk),
                       ("jit_serve_decode_step(22)", slots)) if serving else ():
        run_ops, end = _serve_ops(t, rows, 1600, pool, copy=copy)
        ops += run_ops
        modules.append((name, t, end - t))
        t = end + 0.005
    if serving:
        ops.append(("%fusion.77 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", t, 0.001))
        modules.append(("jit_convert_element_type(33)", t, 0.001))
        t += 0.01
    for _ in range(2):
        run_ops, end = _train_ops(t, layers=train_layers, folded=folded)
        ops += run_ops
        modules.append(("jit_train_window(44)", t, end - t))
        t = end + 0.001
    host = [("$profiler.py:101 start_trace", 0.0, 0.1)]
    return trace.Trace({0: ops}, {0: modules}, host)


def serve_run(tr, chunk=16, slots=4, n_pages=256):
    return {"trace": tr, "trace_window_s": 1.0, "data": {
        "kind": "serve", "ticks": [(0.05, False, 4), (0.09, True, 3)],
        "finished": [(64, 96), (192, 160)],
        "engine": {"page_len": 16, "prefill_chunk": chunk, "n_pages": n_pages,
                   "n_slots": slots, "max_len": 1024}}}


def train_run(tr):
    return {"trace": tr, "trace_window_s": 1.0,
            "data": {"kind": "train", "batch": 12, "chips": 1, "seq": 1024}}


def _ctx(cell):
    return {"cell": manifest.Cell(MANIFEST, cell), "peaks": PEAKS}


def _flat(calls):
    return sorted(op for ops in calls.values() for op in ops)


def _as_ops(runs):
    """Device runs ``(start, end)`` in an operation's form, ``(name, start,
    seconds)``, so that every selection adds up the same way."""
    return sorted(("run", s, e - s) for s, e in runs)


def selections(run, ctx):
    """``{what: (old operations, new operations)}`` for one run: what
    ``control.py --selections`` prints of a chip profile and the cases
    below compare on the hand-made one."""
    model = ctx["cell"].model
    if run["data"]["kind"] == "train":
        return {"flash calls": (sorted(old_flash_calls(run, model)),
                                _flat(readers.kernel_calls(run, readers.FLASH_KERNELS)))}
    old = old_serving_modules(run, model) or ([], [])
    new = readers.serving_runs(run) or ([], [])
    return {"prefill-chunk runs": (_as_ops(old[0]), _as_ops(new[0])),
            "decode-step runs": (_as_ops(old[1]), _as_ops(new[1])),
            "paged calls in the decode program": (
                sorted(old_paged_calls(run, model)),
                _flat(readers.kernel_calls(run, readers.PAGED_KERNELS, readers.DECODE_PROGRAM)))}


# ----------------------------------------------------------------- the cases
@pytest.mark.parametrize("cell,what,count", [
    (DECODE, "prefill-chunk runs", 2), (DECODE, "decode-step runs", 3),
    (DECODE, "paged calls in the decode program", 9),
    (PREFILL, "prefill-chunk runs", 2), (PREFILL, "paged calls in the decode program", 9),
    (TRAIN, "flash calls", 12),
])
def test_old_and_new_selection_pick_the_same_operations(cell, what, count):
    tr = chip_like_trace()
    run = train_run(tr) if cell == TRAIN else serve_run(tr)
    old, new = selections(run, _ctx(cell))[what]
    assert len(old) == count, "the old selection reads this trace"
    assert new == old


@pytest.mark.parametrize("cell", [DECODE, TRAIN])
def test_rooflines_read_what_the_old_arithmetic_read(cell):
    ctx, tr = _ctx(cell), chip_like_trace()
    fam, model = ctx["cell"].family(), ctx["cell"].model
    if cell == TRAIN:
        run = train_run(chip_like_trace(serving=False, train_layers=model["n_layer"]))
        old, new = old_flash_roofline(run, model, fam, PEAKS), readers.flash_roofline(run, ctx)
    else:
        run = serve_run(tr)
        old, new = old_paged_roofline(run, model, fam, PEAKS), readers.paged_decode_roofline(run, ctx)
    assert 0 < old < 100 and new == pytest.approx(old, rel=1e-12)


def test_prefill_busy_share_reads_what_the_old_selection_read():
    run = serve_run(chip_like_trace())
    pre, dec = old_serving_modules(run, _ctx(DECODE)["cell"].model)
    old = 100.0 * sum(e - s for s, e in pre) / sum(e - s for s, e in pre + dec)
    assert readers.prefill_busy_share(run, _ctx(DECODE)) == pytest.approx(old, rel=1e-12)


@pytest.mark.parametrize("case,kwargs,run_kwargs", [
    # S3: the chunk as wide as the slots; both programs work on [4, d]
    ("chunk = slots", {"chunk": 4}, {"chunk": 4}),
    # S1: a pool per layer, never copied whole, of another shape
    ("a pool of another shape", {"pool": "[65,64,25,64]", "copy": False}, {}),
])
def test_serving_readers_read_where_the_old_selection_could_not(case, kwargs, run_kwargs):
    run, ctx = serve_run(chip_like_trace(**kwargs), **run_kwargs), _ctx(DECODE)
    sel = selections(run, ctx)
    assert sel["paged calls in the decode program"][0] == [], case
    assert len(sel["paged calls in the decode program"][1]) == 9
    assert [len(sel[k][1]) for k in ("prefill-chunk runs", "decode-step runs")] == [2, 3]
    assert 0 < readers.paged_decode_roofline(run, ctx) < 100
    assert 0 < readers.prefill_busy_share(run, ctx) < 100


def test_flash_reader_reads_a_kernel_that_takes_unfolded_heads():
    run, ctx = train_run(chip_like_trace(folded=False)), _ctx(TRAIN)
    old, new = selections(run, ctx)["flash calls"]
    assert old == [] and len(new) == 12
    folded = readers.flash_roofline(train_run(chip_like_trace()), ctx)
    assert readers.flash_roofline(run, ctx) == pytest.approx(folded)


def test_no_trace_and_no_such_name_read_none():
    ctx = _ctx(DECODE)
    assert readers.paged_decode_roofline(serve_run(None), ctx) is None
    assert readers.prefill_busy_share(serve_run(None), ctx) is None
    assert readers.flash_roofline(train_run(None), _ctx(TRAIN)) is None
    unnamed = trace.Trace(
        {0: [("%_lambda_.3 = bf16[4,1,25,64]{3,2,1,0} custom-call(bf16[257,16,25,64] %p)", 0.6, 0.2)]},
        {0: [("jit__lambda(107)", 0.6, 0.5)]}, [])
    assert readers.paged_decode_roofline(serve_run(unnamed), ctx) is None
    assert readers.prefill_busy_share(serve_run(unnamed), ctx) is None
    assert readers.kernel_roofline(serve_run(chip_like_trace()), dict(ctx, peaks=None),
                                   readers.PAGED_KERNELS) is None
