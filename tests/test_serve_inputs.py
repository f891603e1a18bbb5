"""The serving programs' small inputs stay on the device between calls.

The engine keeps a device copy of each host mirror its two programs read
(tokens, lengths, decode tables and sampling arrays of the decode batch; a
row's chunk table and sampling rows) and puts one again only when a write
to its mirror made it stale; the decode step hands back the next tokens and
lengths itself. These tests hold the copies to the mirrors before every
call, the tokens to those of the put-everything path, and the ``puts``
counts to what each transition made stale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import metrics as M
from autodist_tpu.api import AutoDist
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.serve.batcher import ContinuousBatcher
from autodist_tpu.serve.engine import Slot
from autodist_tpu.serve.sampling import SamplingParams
from autodist_tpu.strategy import AllReduce

SAMP = ("temperature", "top_k", "top_p", "key_hi", "key_lo")


def _gpt2(prefix_cache):
    from autodist_tpu.serve.server import _tiny_engine

    return _tiny_engine(n_slots=4, n_pages=56, prefix_cache=prefix_cache)[0]


def _kimi(prefix_cache):
    """A model whose programs append ``step_facts`` to their tokens."""
    from autodist_tpu.models import kimi_k2 as K
    from tests.test_kimi_k2 import _cfg, _params

    cfg = _cfg()
    return AutoDist(strategy_builder=AllReduce()).build_inference(
        _params(cfg), decode_model=K.decode_model(cfg), n_slots=4,
        max_len=128, prefix_cache=prefix_cache)


def _nemotron(prefix_cache):
    """A model that carries ``slot_state`` (no prefix sharing over it)."""
    from autodist_tpu.models import nemotron_h as N
    from tests.test_nemotron_h import _cfg, _params

    cfg = _cfg()
    return AutoDist(strategy_builder=AllReduce()).build_inference(
        _params(cfg), decode_model=N.decode_model(cfg), n_slots=4,
        max_len=128)


def _host(engine, idx=None):
    """The host mirrors a decode step (``idx`` None) or row ``idx``'s chunk
    reads, as the programs take them."""
    if idx is None:
        return (engine._last_token, engine._lengths, engine._decode_table_np,
                tuple(engine._samp[k] for k in SAMP))
    return (engine._table_np[idx],
            tuple(engine._samp[k][idx:idx + 1] for k in SAMP))


def _same(device, host):
    return all(a.dtype == b.dtype and np.array_equal(np.asarray(a), b)
               for a, b in zip(jax.tree_util.tree_leaves(device),
                               jax.tree_util.tree_leaves(host)))


def _watch(engine, reference):
    """Wrap the engine's two compiled programs. On the engine's own path
    (``reference`` False) assert before every call that the device copies
    it passes equal the host mirrors; on the reference path replace them
    with ``jnp.asarray`` of the mirrors, put anew for every call as the
    engine did before it kept copies. Returns the compiled programs."""
    engine._compile()
    decode, prefill = engine._decode_fn, engine._prefill_fn
    fresh = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(np.array(a)), tree)

    def decode_fn(p, tokens, lengths, cache, tables, samp, *state):
        host = _host(engine)
        if reference:
            tokens, lengths, tables, samp = fresh(host)
        else:
            assert _same((tokens, lengths, tables, samp), host)
        return decode(p, tokens, lengths, cache, tables, samp, *state)

    def prefill_fn(p, chunk, cache, table, samp, *state):
        c = engine.prefill_chunk
        start, length, idx = (int(v) for v in np.asarray(chunk)[c:])
        assert start == engine._prefill_pos[idx]
        assert length == len(engine._prompts[idx])
        host = _host(engine, idx)
        if reference:
            table, samp = fresh(host)
        else:
            assert _same((table, samp), host)
        return prefill(p, chunk, cache, table, samp, *state)

    engine._decode_fn, engine._prefill_fn = decode_fn, prefill_fn
    return decode, prefill


def _drive(engine, requests):
    """The batcher's order of work, scripted: each tick admits what has
    arrived, advances every prefilling row one chunk, steps the decode
    batch and releases the rows that are done. ``requests``: ``(tick,
    id, prompt, max_new, sampling)``. Returns each request's tokens and
    whether its admission matched a cached prefix."""
    pending, live, out, cached = list(requests), {}, {}, {}
    want = {rid: n for _, rid, _, n, _ in requests}
    tick = 0
    while pending or live:
        while pending and pending[0][0] <= tick:
            _, rid, prompt, n, sampling = pending.pop(0)
            slot = engine.admit(prompt, n, request_id=rid, sampling=sampling)
            assert isinstance(slot, Slot), slot
            live[slot], out[rid] = rid, []
            cached[rid] = engine.slot_cached(slot)
        for slot in engine.prefill_pending():
            first = engine.prefill_step(slot)
            if first is not None:
                out[live[slot]].append(first)
        for slot, tok in engine.step().items():
            out[live[slot]].append(tok)
        for slot, rid in list(live.items()):
            if len(out[rid]) >= want[rid]:
                engine.release(slot)
                del live[slot]
        tick += 1
    return out, cached


@pytest.mark.parametrize("build,prefix", [(_gpt2, True), (_kimi, True),
                                          (_nemotron, False)],
                         ids=["gpt2", "kimi_step_facts", "nemotron_slot_state"])
def test_device_copies_equal_the_mirrors_and_tokens_the_put_everything_path(
        build, prefix):
    """Mid-batch joins, a retirement whose row is taken again, a
    prefix-cache hit where the model allows one, greedy and sampled rows:
    before every call the copies equal the mirrors, the tokens equal those
    of the same programs fed the mirrors anew, and nothing compiles a
    third program."""
    vocab = 64
    a = np.arange(3, 24) % vocab
    requests = [
        (0, "a", a, 10, None),
        (0, "b", np.arange(10, 15) % vocab, 6,
         SamplingParams(temperature=0.9, top_k=8, seed=1)),
        (3, "c", np.concatenate([a[:16], np.arange(40, 50) % vocab]), 7,
         SamplingParams(temperature=0.7, top_p=0.9, seed=2)),
        (5, "d", np.arange(30, 39) % vocab, 5, None),
    ]
    runs = []
    for reference in (False, True):
        try:
            engine = build(prefix)
        finally:
            AutoDist.reset_default()
        decode, prefill = _watch(engine, reference)
        runs.append(_drive(engine, requests))
        if not reference:
            assert decode._cache_size() == prefill._cache_size() == 1
            assert engine.input_puts > 0
    (got, cached), (want, _) = runs
    assert got == want
    assert [len(got[r]) for r in "abcd"] == [10, 6, 7, 5]
    assert cached["c"] == prefix and not cached["a"]


def _puts(spans, name):
    return [s.attrs["puts"] for s in spans if s.name == name]


def test_a_call_puts_only_what_a_transition_made_stale():
    """Steady decode puts nothing and a chunk of a row already placed puts
    its tokens alone; an admission, a prefill completion and a release
    each make the next call put exactly the arrays they made stale."""
    from autodist_tpu.serve.server import _tiny_engine

    engine = _tiny_engine(n_slots=4, n_pages=56)[0]
    tracer = obs_spans.get_tracer()

    def calls(fn, *args):
        tracer.clear()
        fn(*args)
        spans = tracer.spans()
        return (_puts(spans, "serve.decode_dispatch"),
                _puts(spans, "serve.prefill_chunk"))

    try:
        a = engine.admit(np.arange(1, 6), 30, request_id="a")
        # a row's first chunk places its table and five sampling rows
        assert calls(engine.prefill_step, a) == ([], [1 + 1 + 5])
        # the first step puts all eight; then nothing while nothing changes
        assert calls(engine.step) == ([8], [])
        for _ in range(3):
            assert calls(engine.step) == ([0], [])
        # an admission: the decode batch's sampling arrays (5); behind the
        # step goes the row's first chunk (7); its next one puts its
        # tokens alone, and so does its final one
        b = engine.admit(np.arange(1, 21), 4, request_id="b",
                         sampling=SamplingParams(temperature=0.5, seed=3))
        assert calls(engine.step) == ([5], [7])
        assert calls(engine.prefill_step, b) == ([], [])   # went out ahead
        assert calls(engine.step) == ([0], [1])
        assert calls(engine.prefill_step, b) == ([], [])
        assert calls(engine.prefill_step, b) == ([], [1])  # the final one
        # a prefill completion: lengths and tables, and the tokens unless
        # the first one is the 0 the row held
        first = int(engine._last_token[b.index])
        assert calls(engine.step) == ([2 + (first != 0)], [])
        assert calls(engine.step) == ([0], [])
        # a release: lengths, tables, the five sampling arrays, and the
        # tokens unless the row's last is 0
        last = int(engine._last_token[b.index])
        engine.release(b)
        assert calls(engine.step) == ([7 + (last != 0)], [])
        assert calls(engine.step) == ([0], [])
        assert engine.compiled_programs == 2
    finally:
        AutoDist.reset_default()


def test_the_input_puts_counter_is_the_sum_of_the_spans():
    """``serve_input_puts_total`` is the sum of the ``puts`` the dispatch
    and chunk spans carry; its reading rides ``serve.tick_metrics``."""
    from autodist_tpu.serve.server import _tiny_engine

    engine = _tiny_engine(n_slots=4, n_pages=56)[0]
    tracer = obs_spans.get_tracer()
    tracer.clear()
    registry = M.MetricsRegistry()
    batcher = ContinuousBatcher(engine, registry=registry)
    batcher.start()
    try:
        reqs = [batcher.submit(np.arange(2, 2 + n), max_new_tokens=6)
                for n in (20, 5, 11)]
        for r in reqs:
            assert r.wait(120) and len(r.tokens) == 6
    finally:
        batcher.stop(drain=False, timeout_s=30)
        AutoDist.reset_default()
    spans = tracer.spans()
    decode = _puts(spans, "serve.decode_dispatch")
    chunks = _puts(spans, "serve.prefill_chunk")
    assert 0 in decode and len(chunks) == 3 + 1 + 2
    total = sum(decode) + sum(chunks)
    assert total == engine.input_puts == registry.counter(
        "serve_input_puts_total").value
    last = [s.attrs for s in spans if s.name == "serve.tick_metrics"][-1]
    assert last["input_puts"] == total
