"""The readers that take the program's own spans, modules and kernels out
of a profile (``harness/spanread.py`` and the metric files on it), on
hand-made ``Trace`` objects: the four idle shares add up to the idle
share, and a name the trace does not hold reads None."""
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest, spanread, trace  # noqa: E402

MANIFEST = manifest.load()
DECODE, PREFILL, TRAIN = ("xl-serve-decode-closed", "xl-serve-prefill-single",
                          "medium-train-1chip")
W0 = 0.5            # the traced window opens where start_trace returns
WINDOW = 2.0


def _serve_trace():
    """Two ticks in a window of 2 s that opens at 0.5. Busy: 0.6-0.9 and
    0.95-1.4 (tick 1), 1.6-2.0 (tick 2). Idle 2.0 - 1.15 = 0.85 s in four
    gaps: 0.5-0.6, 0.9-0.95, 1.4-1.6 and 2.0-2.5."""
    paged = "%paged_attention.3 = bf16[4,1,25,64]{3,2,1,0} custom-call(%p.1)"
    reads = "%fusion.9 = bf16[4,25,64]{2,1,0} fusion(%paged_attention.3), kind=kLoop"
    ops = {0: [("%fusion.1 = f32[8]{0} fusion(%a)", 0.6, 0.2), (paged, 0.8, 0.1),
               (reads, 0.95, 0.45), ("%copy.2 = bf16[48,257]{1,0} copy(%c)", 1.6, 0.3),
               (paged, 1.9, 0.1)]}
    modules = {0: [("jit_serve_prefill_chunk(77)", 0.6, 0.3),
                   ("jit_serve_decode_step(99)", 0.95, 0.45),
                   ("jit_serve_decode_step(99)", 1.6, 0.4)]}
    host = [
        ("$runtime.py:45 start", 0.0, 0.5), ("$profiler.py:101 start_trace", 0.0, 0.5),
        ("serve.tick", 0.52, 0.93),                 # 0.52 - 1.45
        ("serve.prefill_chunk", 0.53, 0.1),
        ("serve.decode_step", 0.7, 0.7),            # 0.7 - 1.4
        ("serve.decode_dispatch", 0.7, 0.1),
        ("serve.token_fetch", 0.8, 0.6),
        ("serve.emit", 1.4, 0.02),
        ("serve.tick_metrics", 1.42, 0.02),
        ("serve.on_tick", 1.45, 0.05),              # 1.45 - 1.5
        ("Linearize", 1.49, 0.02),
        ("serve.tick", 1.55, 0.7),                  # 1.55 - 2.25
        ("serve.decode_step", 1.56, 0.5),
        ("serve.decode_dispatch", 1.56, 0.03),
        ("serve.token_fetch", 1.6, 0.46),
        ("serve.emit", 2.1, 0.1),
    ]
    return trace.Trace(ops, modules, host)


def _run(tr, spans=()):
    return {"trace": tr, "trace_window_s": WINDOW, "data": {"spans": list(spans)}}


def _ctx(said=None):
    return {"say": (said.append if said is not None else (lambda m: None))}


def test_program_spans_flatten_to_the_innermost_span():
    tr = _serve_trace()
    spans = spanread.program_spans(tr)
    assert all(n.startswith(("serve.", "train.", "input.")) for n, _, _ in spans)
    pieces = spanread.innermost(spans)
    assert all(a[1] <= b[0] + 1e-9 for a, b in zip(pieces, pieces[1:])), "disjoint, in order"
    pieces = [p for p in pieces if p[1] - p[0] > 1e-9]    # 0.7 + 0.1 < 0.8
    assert [(round(s, 2), round(e, 2), n) for s, e, n in pieces[:5]] == [
        (0.52, 0.53, "serve.tick"), (0.53, 0.63, "serve.prefill_chunk"),
        (0.63, 0.7, "serve.tick"), (0.7, 0.8, "serve.decode_dispatch"),
        (0.8, 1.4, "serve.token_fetch")]
    # a child that reaches past its parent is cut to it
    cut = spanread.innermost([("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0)])
    assert cut == [(0.0, 0.5, "a"), (0.5, 1.0, "b"), (3.0, 4.0, "c")]
    own = spanread.self_seconds(tr)
    # tick 1: 0.93 - chunk 0.1 - step 0.7 - emit 0.02 - metrics 0.02;
    # tick 2: 0.7 - step 0.5 - emit 0.1
    assert own["serve.tick"] == pytest.approx(0.09 + 0.1)
    assert own["serve.decode_step"] == pytest.approx(0.0 + 0.01)
    assert own["serve.token_fetch"] == pytest.approx(0.6 + 0.46)
    assert sum(own.values()) == pytest.approx(0.93 + 0.05 + 0.7)


def test_the_four_idle_shares_add_up_to_the_idle_share():
    tr, said = _serve_trace(), []
    run = _run(tr)
    assert spanread.window(run) == (W0, W0 + WINDOW)
    gaps = spanread.idle_gaps(run)
    assert gaps == [(0.5, 0.6), (0.9, 0.95), (1.4, 1.6), (2.0, 2.5)]
    # every gap is split over the innermost spans it overlaps: the third
    # runs through emit, tick_metrics, the tick's end, on_tick, the loop,
    # the next tick's start, its dispatch and 0.01 s of decode_step
    by_span = spanread.idle_by_span(run)
    assert by_span["serve.prefill_chunk"] == pytest.approx(0.07)
    assert by_span["serve.decode_dispatch"] == pytest.approx(0.03)
    assert by_span["serve.token_fetch"] == pytest.approx(0.05 + 0.06)
    assert by_span["serve.on_tick"] == pytest.approx(0.05)
    assert by_span["serve.emit"] == pytest.approx(0.02 + 0.1)
    assert by_span["serve.tick"] == pytest.approx(0.01 + 0.01 + 0.01 + 0.04 + 0.05)
    assert by_span[spanread.OUTSIDE] == pytest.approx(0.02 + 0.05 + 0.25)
    split = spanread.idle_split(run, _ctx(said))
    assert split["dispatch"] == pytest.approx(5.0)
    assert split["fetch"] == pytest.approx(5.5)
    assert split["bookkeeping"] == pytest.approx(16.0)
    assert split["outside"] == pytest.approx(16.0)
    idle = 100.0 * (1.0 - tr.busy_seconds() / WINDOW)
    assert sum(split.values()) == pytest.approx(idle) == pytest.approx(42.5)
    assert len(said) == 3 and said[0].startswith("idle seconds") and "self seconds" in said[1]
    spanread.idle_split(run, _ctx(said))
    assert len(said) == 3, "printed once a run"


def test_a_gap_under_no_span_is_outside_and_a_bad_sum_reads_none():
    tr = _serve_trace()
    tr.host_events = [e for e in tr.host_events if e[0] != "serve.on_tick"]
    split = spanread.idle_split(_run(tr), _ctx())
    assert split["outside"] == pytest.approx(16.0 + 2.5)
    assert split["bookkeeping"] == pytest.approx(16.0 - 2.5)
    # busy time the window does not hold (the trace ran on after its end):
    # the shares cannot add up, so none is reported
    tr2 = _serve_trace()
    tr2.device_ops[0].append(("%fusion.1 = f32[8]{0} fusion(%a)", 2.6, 0.4))
    assert spanread.idle_split(_run(tr2), _ctx()) is None
    # a program from before the spans
    tr3 = _serve_trace()
    tr3.host_events = [e for e in tr3.host_events if not e[0].startswith("serve.")]
    assert spanread.idle_split(_run(tr3), _ctx()) is None
    assert spanread.tick_idle_p50_ms(_run(tr3), _ctx()) is None


def test_tick_idle_is_span_length_less_busy_time():
    # tick 1 (0.52-1.45): busy 0.3 + 0.45 -> idle 0.18; tick 2 (1.55-2.25):
    # busy 0.4 -> idle 0.3
    assert spanread.tick_idle_p50_ms(_run(_serve_trace()), _ctx()) == pytest.approx(240.0)


def test_modules_and_kernels_are_found_by_their_names():
    run = _run(_serve_trace())
    assert spanread.module_p50_ms(run, "jit_serve_decode_step") == pytest.approx(425.0)
    assert spanread.module_p50_ms(run, "jit_serve_prefill_chunk") == pytest.approx(300.0)
    assert spanread.module_p50_ms(run, "jit_serve_decode") is None
    assert spanread.module_p50_ms(run, "jit__lambda") is None
    # two calls of 0.1 s of 1.15 s busy; the fusion that reads the kernel's
    # result is not the kernel
    assert spanread.kernel_share(run, "paged_attention") == pytest.approx(100 * 0.2 / 1.15)
    assert spanread.kernel_share(run, "flash_fwd") is None
    wrapped = trace.Trace({0: [
        ("%jvp_flash_fwd_.1 = (bf16[32,1024,64]{2,1,0}) custom-call(%b)", 0.0, 1.0),
        ("%transpose_jvp_flash_bwd_dkv__.1 = (bf16[32,1024,64]) custom-call(%b)", 1.0, 2.0),
        ("%transpose_jvp_flash_bwd_dq__.1 = bf16[32,1024,64] custom-call(%b)", 3.0, 1.0),
        ("%fusion.4 = f32[1024,1024]{1,0} fusion(%jvp_flash_fwd_.1)", 4.0, 4.0)]}, {}, [])
    share = spanread.kernel_share({"trace": wrapped}, "flash_fwd", "flash_bwd_dkv",
                                  "flash_bwd_dq")
    assert share == pytest.approx(50.0)


def test_span_lengths_and_shares():
    host = [("$profiler.py:101 start_trace", 0.0, 0.5)]
    for i in range(4):
        host += [("input.next", 1.0 + i, 0.01), ("input.stage", 1.02 + i, 0.03),
                 ("train.window_dispatch", 1.1 + i, 0.002 * (i + 1))]
    run = {"trace": trace.Trace({}, {}, host), "trace_window_s": 8.0, "data": {}}
    assert spanread.span_p50_ms(run, "train.window_dispatch") == pytest.approx(5.0)
    assert spanread.span_share(run, "input.next", "input.stage") == pytest.approx(2.0)
    assert spanread.span_p50_ms(run, "serve.tick") is None
    assert spanread.span_share(run, "serve.tick") is None
    chunk = lambda dur, final: SimpleNamespace(  # noqa: E731
        name="serve.prefill_chunk", dur_s=dur, attrs={"final": final})
    spans = [chunk(0.002, False), chunk(0.004, False), chunk(0.050, True),
             SimpleNamespace(name="serve.decode_step", dur_s=0.04, attrs={})]
    assert spanread.dispatch_only_p50_ms({"data": {"spans": spans}}) == pytest.approx(3.0)
    old = [SimpleNamespace(name="serve.prefill_chunk", dur_s=0.002, attrs={"start": 0})]
    assert spanread.dispatch_only_p50_ms({"data": {"spans": old}}) is None


NEW = {
    DECODE: ["sched.tick_idle_p50_ms.decode", "sched.idle_in_dispatch_share.decode",
             "engine.idle_in_fetch_share.decode", "sched.idle_in_bookkeeping_share.decode",
             "sched.idle_outside_tick_share.decode", "engine.decode_step_device_p50_ms.decode",
             "engine.prefill_chunk_device_p50_ms.tpot", "kernel.paged_share.decode"],
    PREFILL: ["engine.prefill_chunk_device_p50_ms.ttft", "engine.prefill_dispatch_p50_ms.ttft",
              "kernel.paged_share.ttft"],
    TRAIN: ["kernel.flash_share.train", "step.dispatch_p50_ms.train", "input.host_share.train"],
}


@pytest.mark.parametrize("cell,metric", [(c, m) for c, ms in NEW.items() for m in ms])
def test_new_reader_reads_none_from_a_program_without_the_names(cell, metric):
    """What the parent commit's traced run gives: device operations and
    modules under the old names, no program span, spans without ``final``."""
    c = manifest.Cell(MANIFEST, cell)
    assert metric in [m["name"] for m in c.per_layer]
    old = trace.Trace(
        {0: [("%_lambda_.3 = bf16[4,1,25,64]{3,2,1,0} custom-call(%p.1)", 0.6, 0.2),
             ("%jvp__.1 = (bf16[192,1024,64]{2,1,0}) custom-call(%b)", 0.9, 0.2)]},
        {0: [("jit__lambda(10774884847161282411)", 0.6, 0.5), ("jit_multi(5)", 1.2, 0.5)]},
        [("$profiler.py:101 start_trace", 0.0, 0.5), ("$batcher.py:716 _tick", 0.5, 1.0),
         ("Linearize", 0.7, 0.01)])
    span = SimpleNamespace(name="serve.prefill_chunk", dur_s=0.002, attrs={"start": 0})
    read = c.reader(metric).read
    assert read(_run(old, [span]), _ctx()) is None
    assert read({"trace": None, "trace_window_s": 0.0, "data": {"spans": []}}, _ctx()) is None


@pytest.mark.parametrize("metric", NEW[DECODE])
def test_new_decode_readers_read_the_toy_trace(metric):
    value = manifest.Cell(MANIFEST, DECODE).reader(metric).read(_run(_serve_trace()), _ctx())
    assert value is not None and value >= 0.0
