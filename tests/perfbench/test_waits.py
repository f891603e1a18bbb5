"""The readers of the host's waits for the device (``harness/waits.py`` and
the three metric files on it), on a hand-made ``Trace``: each reads the
value worked out by hand over the traced window alone, each reads None
where no ``wait.tokens`` span is found, and the phase readers of
``spanread`` read the same with the wait spans in the trace as without
them."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.harness import manifest, spanread, trace, waits  # noqa: E402

MANIFEST = manifest.load()
CELL = "xl-serve-decode-closed"
NEW = ("sched.host_busy_p50_ms.decode", "engine.tokens_ready_lag_p50_ms.decode",
       "engine.token_copy_p50_ms.decode")
PHASES = ("sched.tick_idle_p50_ms.decode", "sched.idle_in_dispatch_share.decode",
          "engine.idle_in_fetch_share.decode", "sched.idle_in_bookkeeping_share.decode",
          "sched.idle_outside_tick_share.decode", "device.idle_share.decode")
WINDOW = 2.0        # opens at 0.5, where start_trace returns


def _trace(with_waits=True):
    """Two decode ticks and a tick of chunks alone in a window of 2 s from
    0.5. Device: a chunk 0.6-0.9, decode runs 0.95-1.35 and 1.6-2.0. The
    host learns of the first run at 1.37 and of the second at 2.05 (lags
    0.02 and 0.05 s); a final chunk's wait at 2.2-2.23, inside the second
    tick, and the third tick's at 2.32-2.4 lie outside any decode step."""
    ops = {0: [("%fusion.1 = f32[8]{0} fusion(%a)", 0.6, 0.3),
               ("%fusion.2 = f32[8]{0} fusion(%b)", 0.95, 0.4),
               ("%fusion.2 = f32[8]{0} fusion(%b)", 1.6, 0.4)]}
    modules = {0: [("jit_serve_prefill_chunk(7)", 0.6, 0.3),
                   ("jit_serve_decode_step(9)", 0.95, 0.4),
                   ("jit_serve_decode_step(9)", 1.6, 0.4)]}
    host = [
        ("$profiler.py:101 start_trace", 0.0, 0.5),
        ("serve.tick", 0.52, 0.93),                 # 0.52 - 1.45
        ("serve.prefill_chunk", 0.53, 0.1),
        ("serve.decode_step", 0.7, 0.7),            # 0.7 - 1.4
        ("serve.decode_dispatch", 0.7, 0.1),
        ("serve.token_fetch", 0.8, 0.6),            # 0.8 - 1.4
        ("wait.tokens", 0.8, 0.57),                 # 0.8 - 1.37
        ("_array.py:631__value", 0.81, 0.5),
        ("serve.emit", 1.4, 0.02),
        ("serve.tick_metrics", 1.42, 0.02),
        ("serve.on_tick", 1.45, 0.05),
        ("serve.tick", 1.55, 0.7),                  # 1.55 - 2.25
        ("serve.decode_step", 1.56, 0.5),           # 1.56 - 2.06
        ("serve.decode_dispatch", 1.56, 0.03),
        ("serve.token_fetch", 1.6, 0.46),           # 1.6 - 2.06
        ("wait.tokens", 1.6, 0.45),                 # 1.6 - 2.05
        ("serve.emit", 2.1, 0.1),
        ("serve.token_fetch", 2.2, 0.04),
        ("wait.tokens", 2.2, 0.03),
        ("serve.tick", 2.3, 0.15),                  # 2.3 - 2.45, no step
        ("serve.prefill_chunk", 2.3, 0.02),
        ("serve.token_fetch", 2.32, 0.1),
        ("wait.tokens", 2.32, 0.08),
    ]
    if not with_waits:
        host = [e for e in host if not e[0].startswith("wait.")]
    return trace.Trace(ops, modules, host)


def _run(with_waits=True, tr=True, window=WINDOW):
    return {"trace": _trace(with_waits) if tr else None,
            "trace_window_s": window if tr else 0.0, "data": {"spans": []}}


def _read(metric, run):
    return manifest.Cell(MANIFEST, CELL).reader(metric).read(run, {"say": lambda m: None})


def test_the_three_readers_list_the_xl_decode_cell_alone():
    for name in NEW:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["source"] == "program_span"
        assert (entry["better"], entry["moves"], entry["unit"]) == ("lower", "serve_tok_s", "ms")


def test_host_busy_is_the_decode_tick_less_its_waits():
    # 0.93 - 0.57 and 0.70 - 0.45 - 0.03 (the final chunk's wait is in the
    # tick); the third tick holds no step
    assert waits.tick_host_busy_s(_run()) == pytest.approx([0.36, 0.22])
    assert _read("sched.host_busy_p50_ms.decode", _run()) == pytest.approx(290.0)
    # a window that ends at 1.5 holds the first tick alone
    assert _read("sched.host_busy_p50_ms.decode", _run(window=1.0)) == pytest.approx(360.0)


def test_token_copy_is_the_decode_fetch_less_its_wait():
    # 0.6 - 0.57 and 0.46 - 0.45; the chunks' fetches are not a step's
    assert _read("engine.token_copy_p50_ms.decode", _run()) == pytest.approx(20.0)
    assert _read("engine.token_copy_p50_ms.decode", _run(window=1.0)) == pytest.approx(30.0)


def test_tokens_ready_lag_pairs_each_wait_with_the_run_before_it():
    # 1.37 - 1.35 and 2.05 - 2.0; the chunk's wait at 2.23 is in no step
    assert _read("engine.tokens_ready_lag_p50_ms.decode", _run()) == pytest.approx(35.0)
    # the first wait alone in the window: 20 ms
    assert _read("engine.tokens_ready_lag_p50_ms.decode", _run(window=1.0)) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_without_wait_spans_reads_none(metric):
    assert _read(metric, _run(with_waits=False)) is None
    assert _read(metric, {"trace": None, "trace_window_s": 0.0, "data": {"spans": []}}) is None
    assert _read(metric, {"trace": None, "trace_window_s": 0.0, "data": {}}) is None
    assert _read(metric, _run(tr=False)) is None, "no trace, no clock"
    # the waits outside the window are not found: 0.1 s from 0.5 holds none
    assert _read(metric, _run(window=0.1)) is None


@pytest.mark.parametrize("metric", PHASES)
def test_the_phase_readers_read_the_same_with_and_without_waits(metric):
    with_waits, without = _read(metric, _run()), _read(metric, _run(with_waits=False))
    assert with_waits is not None and with_waits == without


def test_waits_are_not_program_phases():
    tr = _trace()
    assert not [n for n, _, _ in spanread.program_spans(tr) if n.startswith("wait.")]
    assert spanread.idle_by_span(_run()) == spanread.idle_by_span(_run(with_waits=False))
    split = spanread.idle_split(_run(), {"say": lambda m: None})
    idle = 100.0 * (1.0 - tr.busy_seconds() / WINDOW)
    assert sum(split.values()) == pytest.approx(idle)
