"""Counter-based stochastic sampling (ISSUE 18).

- **params at the edge**: :class:`SamplingParams` validation (typed
  :class:`InvalidSamplingParams` for temperature < 0 / top_p outside
  (0,1] / top_k < 0 — a ValueError, so every existing 4xx edge catches
  it), greedy identity at temperature=0, journal dict round-trip,
  per-tenant defaults vs explicit body fields, the HTTP 400 contract;
- **the transform**: temperature=0 rows reduce bit-exactly to argmax,
  top-k/top-p masks never leak a banned token, the same
  ``(key, counter)`` reproduces the same draw and different counters
  decorrelate, the chi-square helper accepts the true distribution and
  rejects a disjoint one;
- **engine semantics**: seeded replay bit-identity, seed divergence,
  ``sampling=None`` bit-identical to the legacy greedy path, spec-decode
  streams bit-identical to the plain stochastic control (coupled
  shared-Gumbel draft — docs/serving.md § stochastic sampling),
  temperature=0 spec reducing to greedy spec, prefix-cache hit vs
  cold-start bit-identity;
- **plumbing**: drain-journal persistence round-trip, SLO
  acceptance-by-temperature-bucket report keys, sampled-vs-greedy
  stream counts.

All CPU-sim (``JAX_PLATFORMS=cpu``); the ``--selftest-sampling`` CLI
run proves the calibration / failover / program-pin bars — this file
pins semantics.
"""
import asyncio
import json

import numpy as np
import pytest

from autodist_tpu.serve.sampling import (
    InvalidSamplingParams,
    SamplingParams,
    chi_square_fits,
    request_key,
    sample_tokens,
    temperature_bucket,
)

MAX_NEW = 6


# ------------------------------------------------------------ unit: params
class TestSamplingParams:
    def test_default_is_greedy(self):
        sp = SamplingParams()
        assert sp.greedy and sp.temperature == 0.0
        assert not SamplingParams(temperature=0.7).greedy

    @pytest.mark.parametrize("kw", [
        dict(temperature=-0.1),
        dict(top_p=0.0),
        dict(top_p=1.5),
        dict(top_k=-1),
    ])
    def test_validate_rejects_typed(self, kw):
        with pytest.raises(InvalidSamplingParams):
            SamplingParams(**kw).validate()
        # the typed error IS a ValueError: every existing 4xx edge
        # (batcher submit, router submit, drain replay) catches it
        assert issubclass(InvalidSamplingParams, ValueError)

    def test_dict_round_trip(self):
        sp = SamplingParams(temperature=0.8, top_k=40, top_p=0.9, seed=7)
        assert SamplingParams.from_dict(sp.to_dict()) == sp
        assert SamplingParams.from_dict(None) is None
        assert SamplingParams.from_dict({}) is None

    def test_request_key_stable_and_distinct(self):
        a = request_key("req-1", 3)
        assert a == request_key("req-1", 3)
        assert a != request_key("req-2", 3)
        assert a != request_key("req-1", 4)
        assert all(0 <= w < 2**32 for w in a)

    def test_temperature_buckets(self):
        assert temperature_bucket(0.0) == "greedy"
        assert temperature_bucket(0.5) == "low"
        assert temperature_bucket(1.0) == "mid"
        assert temperature_bucket(1.7) == "high"


# --------------------------------------------------------- unit: transform
def _samp(n, sp, rid="t"):
    import jax.numpy as jnp

    hi, lo = request_key(rid, sp.seed)
    return (jnp.full(n, sp.temperature, jnp.float32),
            jnp.full(n, sp.top_k, jnp.int32),
            jnp.full(n, sp.top_p, jnp.float32),
            jnp.full(n, hi, jnp.uint32), jnp.full(n, lo, jnp.uint32))


class TestSampleTokens:
    def test_greedy_rows_bit_exact_argmax(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        logits = rng.normal(0, 2, (8, 32)).astype(np.float32)
        toks = sample_tokens(jnp.asarray(logits),
                             jnp.arange(8, dtype=jnp.int32),
                             _samp(8, SamplingParams()))
        assert np.array_equal(np.asarray(toks), np.argmax(logits, axis=-1))

    def test_top_k_never_leaks(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(1)
        row = rng.normal(0, 1.5, 32).astype(np.float32)
        allowed = set(np.argsort(row)[-4:].tolist())
        toks = np.asarray(sample_tokens(
            jnp.broadcast_to(jnp.asarray(row), (256, 32)),
            jnp.arange(256, dtype=jnp.int32),
            _samp(256, SamplingParams(temperature=1.3, top_k=4))))
        assert set(toks.tolist()) <= allowed

    def test_top_p_never_leaks(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(2)
        row = rng.normal(0, 2.0, 32).astype(np.float32)
        p = np.exp(row - row.max())
        p /= p.sum()
        order = np.argsort(-p)
        keep, acc = set(), 0.0
        for t in order:       # exclusive-prefix nucleus rule
            keep.add(int(t))
            acc += p[t]
            if acc >= 0.7:
                break
        toks = np.asarray(sample_tokens(
            jnp.broadcast_to(jnp.asarray(row), (256, 32)),
            jnp.arange(256, dtype=jnp.int32),
            _samp(256, SamplingParams(temperature=1.0, top_p=0.7))))
        assert set(toks.tolist()) <= keep

    def test_counter_replay_and_decorrelation(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(0, 1.5, (64, 32)).astype(np.float32))
        samp = _samp(64, SamplingParams(temperature=1.0, seed=5))
        ctr = jnp.arange(64, dtype=jnp.int32)
        a = np.asarray(sample_tokens(logits, ctr, samp))
        assert np.array_equal(a, np.asarray(sample_tokens(logits, ctr, samp)))
        # shifted counters give a different stream over the same logits
        b = np.asarray(sample_tokens(logits, ctr + 1000, samp))
        assert not np.array_equal(a, b)

    def test_chi_square_helper(self):
        rng = np.random.default_rng(4)
        p = np.asarray([0.5, 0.3, 0.15, 0.05])
        counts = np.bincount(rng.choice(4, size=8000, p=p), minlength=4)
        ok, _, _ = chi_square_fits(counts, p)
        assert ok
        bad, _, _ = chi_square_fits(counts, p[::-1].copy())
        assert not bad


# ------------------------------------- unit: the draw runs only to sample
def _primitives(jaxpr, skip=None):
    """Every primitive name in ``jaxpr`` and the jaxprs nested in it,
    leaving out the subtree ``skip``."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if sub is not skip and hasattr(sub, "eqns"):
                    names |= _primitives(sub, skip)
    return names


_VOCAB_WORK = {"sort", "cumsum", "random_bits"}


class TestGreedyBranch:
    def test_vocab_work_only_in_the_draw_branch(self):
        """The sorts, the cumulative sum and the random bits of the
        transform lie in the ``cond``'s true branch alone: a call whose
        rows are all greedy runs none of them."""
        import jax
        import jax.numpy as jnp

        jaxpr = jax.make_jaxpr(sample_tokens)(
            jnp.zeros((4, 64), jnp.float32), jnp.arange(4, dtype=jnp.int32),
            _samp(4, SamplingParams())).jaxpr
        [cond] = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
        greedy, draw = (b.jaxpr for b in cond.params["branches"])
        assert _VOCAB_WORK <= _primitives(draw)
        assert not _VOCAB_WORK & _primitives(jaxpr, skip=draw)
        assert not _VOCAB_WORK & _primitives(greedy)

    @pytest.mark.parametrize("verify", [False, True], ids=["decode", "verify"])
    @pytest.mark.parametrize("sp", [
        SamplingParams(temperature=1.0, seed=2),
        SamplingParams(temperature=0.7, top_k=5, seed=3),
        SamplingParams(temperature=1.3, top_p=0.8, seed=4),
        SamplingParams(temperature=0.9, top_k=8, top_p=0.9, seed=5),
    ], ids=["plain", "top_k", "top_p", "top_k_p"])
    def test_row_token_independent_of_its_batch(self, sp, verify):
        """Under ``jit``, a stochastic row draws the same token alone,
        beside greedy rows and beside other stochastic rows; greedy rows
        are ``argmax`` bit for bit in an all-greedy batch and a mixed one.
        ``verify`` gives each row ``k+1`` positions, as spec verify does."""
        import jax
        import jax.numpy as jnp

        pick = jax.jit(sample_tokens)
        b, k1, vocab, row = 4, 3, 48, 2
        rng = np.random.default_rng(43)
        tail = (k1,) if verify else ()
        logits = rng.normal(0, 2.0, (b,) + tail + (vocab,)).astype(np.float32)
        counters = (np.arange(b * k1).reshape(b, k1) if verify
                    else np.arange(b)).astype(np.int32) + 7

        def batch(params, rows):
            cols = [_samp(1, p, rid=f"r{i}") for i, p in zip(rows, params)]
            samp = tuple(jnp.concatenate(c) for c in zip(*cols))
            return np.asarray(pick(jnp.asarray(logits[rows]),
                                   jnp.asarray(counters[rows]), samp))

        alone = batch([sp], [row])[0]
        others = [SamplingParams(temperature=1.2, seed=9),
                  SamplingParams(temperature=0.5, top_k=3, seed=8),
                  SamplingParams(temperature=0.8, top_p=0.7, seed=7)]
        mixed = [SamplingParams()] * b
        mixed[row] = sp
        beside_greedy = batch(mixed, list(range(b)))
        stochastic = others[:row] + [sp] + others[row:]
        beside_stochastic = batch(stochastic, list(range(b)))
        assert np.array_equal(beside_greedy[row], alone)
        assert np.array_equal(beside_stochastic[row], alone)
        want = np.argmax(logits, axis=-1)
        greedy_rows = [i for i in range(b) if i != row]
        assert np.array_equal(beside_greedy[greedy_rows], want[greedy_rows])
        assert np.array_equal(
            batch([SamplingParams()] * b, list(range(b))), want)


# ------------------------------------------------- engine rig (CPU-sim)
@pytest.fixture(scope="module")
def rig():
    """One tiny plan; a plain engine, a spec engine over the same target
    weights (coupling makes it bit-identical for ANY draft), and a
    divergent-draft spec engine with real rejections."""
    from autodist_tpu.serve.spec import _SelftestRig

    return _SelftestRig()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(18)
    return [rng.integers(1, 127, size=n).astype(np.int32) for n in (5, 9, 17)]


class TestEngineSampling:
    def test_seeded_replay_bit_identical(self, rig, prompts):
        sp = SamplingParams(temperature=1.0, top_p=0.9, seed=1)
        for i, p in enumerate(prompts):
            a = rig.plain.generate(p, MAX_NEW, request_id=f"r{i}",
                                   sampling=sp)
            assert rig.plain.generate(p, MAX_NEW, request_id=f"r{i}",
                                      sampling=sp) == a

    def test_seed_diverges(self, rig, prompts):
        outs = {rig.plain.generate(
            prompts[1], 8, request_id="s",
            sampling=SamplingParams(temperature=1.2, seed=s))[0]
            for s in range(8)}
        assert len(outs) > 1     # some first-token draw differs

    def test_none_matches_legacy_greedy(self, rig, prompts):
        for p in prompts:
            legacy = rig.plain.generate(p, MAX_NEW)
            assert rig.plain.generate(p, MAX_NEW, request_id="g",
                                      sampling=None) == legacy
            assert rig.plain.generate(
                p, MAX_NEW, request_id="g",
                sampling=SamplingParams()) == legacy

    def test_spec_bit_identical_to_plain(self, rig, prompts):
        eng = rig.spec_engine(spec_k=2, same_draft=False)
        sp = SamplingParams(temperature=1.1, top_p=0.9, seed=6)
        for i, p in enumerate(prompts):
            rid = f"spec{i}"
            want = rig.plain.generate(p, MAX_NEW, request_id=rid,
                                      sampling=sp)
            assert eng.generate(p, MAX_NEW, request_id=rid,
                                sampling=sp) == want

    def test_spec_temp0_reduces_to_greedy(self, rig, prompts):
        eng = rig.spec_engine(spec_k=2, same_draft=True)
        for p in prompts:
            assert eng.generate(p, MAX_NEW, request_id="z",
                                sampling=SamplingParams()) == \
                rig.plain.generate(p, MAX_NEW)


class TestPrefixSampling:
    def test_cache_hit_vs_cold_bit_identical(self):
        from autodist_tpu.serve.server import _tiny_engine

        rng = np.random.default_rng(23)
        shared = rng.integers(1, 127, size=24).astype(np.int32)
        sp = SamplingParams(temperature=1.0, top_p=0.9, seed=4)
        warm, _, _ = _tiny_engine(prefix_cache=True)
        warm.generate(shared, MAX_NEW, request_id="warmup", sampling=sp)
        hit = warm.generate(shared, MAX_NEW, request_id="probe",
                            sampling=sp)
        assert warm.prefix_stats()["hits"] > 0
        cold, _, _ = _tiny_engine(prefix_cache=True)
        assert cold.generate(shared, MAX_NEW, request_id="probe",
                             sampling=sp) == hit


class TestSamplerCounters:
    """``sampler_argmax_calls`` / ``sampler_draw_calls`` and the dispatch
    spans' ``sampler`` attribute follow the branch each call takes."""

    @staticmethod
    def _branches(spans):
        return {name: [s.attrs["sampler"] for s in spans if s.name == name]
                for name in ("serve.decode_dispatch", "serve.prefill_chunk")}

    def test_greedy_then_one_stochastic_request(self):
        from autodist_tpu.api import AutoDist
        from autodist_tpu.obs import spans as obs_spans
        from autodist_tpu.serve.server import _tiny_engine

        engine = _tiny_engine(n_slots=4, n_pages=56)[0]
        tracer = obs_spans.get_tracer()
        prompt = np.arange(1, 21, dtype=np.int32)   # three chunks
        try:
            tracer.clear()
            engine.generate(prompt, MAX_NEW, request_id="g")
            greedy = self._branches(tracer.spans())
            calls = sum(map(len, greedy.values()))
            assert calls and all(b == "argmax" for v in greedy.values()
                                 for b in v)
            assert engine.sampler_argmax_calls == calls
            assert engine.sampler_draw_calls == 0

            tracer.clear()
            engine.generate(prompt, MAX_NEW, request_id="s",
                            sampling=SamplingParams(temperature=0.8, seed=1))
            drawn = self._branches(tracer.spans())
            assert drawn["serve.decode_dispatch"] and all(
                b == "draw" for v in drawn.values() for b in v)
            assert engine.sampler_draw_calls == sum(map(len, drawn.values()))
            assert engine.sampler_argmax_calls == calls
            assert engine.stochastic_slots == 0
        finally:
            AutoDist.reset_default()

    def test_mixed_batch_draws_and_greedy_chunk_does_not(self):
        """A decode step over a greedy row and a stochastic one draws; the
        greedy row's own chunk, which takes its row alone, does not; once
        the stochastic row retires the step is back to the argmax."""
        from autodist_tpu.api import AutoDist
        from autodist_tpu.obs import spans as obs_spans
        from autodist_tpu.serve.server import _tiny_engine

        engine = _tiny_engine(n_slots=4, n_pages=56)[0]
        tracer = obs_spans.get_tracer()

        def prefill(slot):
            while engine.prefill_step(slot) is None:
                pass

        try:
            s = engine.admit(np.arange(1, 6), 8, request_id="s",
                             sampling=SamplingParams(temperature=1.0, seed=2))
            g = engine.admit(np.arange(3, 9), 8, request_id="g")
            assert engine.stochastic_slots == 1
            tracer.clear()
            prefill(s)
            prefill(g)
            engine.step()
            got = self._branches(tracer.spans())
            assert got == {"serve.decode_dispatch": ["draw"],
                           "serve.prefill_chunk": ["draw", "argmax"]}
            engine.release(s)
            assert engine.stochastic_slots == 0
            tracer.clear()
            engine.step()
            assert self._branches(tracer.spans())[
                "serve.decode_dispatch"] == ["argmax"]
            engine.release(g)
            assert engine.stochastic_slots == 0
            assert engine.compiled_programs == 2
        finally:
            AutoDist.reset_default()


# ----------------------------------------------------------- HTTP edge
class _CaptureWriter:
    def __init__(self):
        self.data = b""

    def write(self, b):
        self.data += b

    async def drain(self):
        pass

    def close(self):
        pass


def _post_generate(frontend, payload):
    body = json.dumps(payload).encode()
    raw = (b"POST /generate HTTP/1.1\r\nContent-Length: "
           + str(len(body)).encode() + b"\r\n\r\n" + body)

    async def drive():
        reader = asyncio.StreamReader()
        reader.feed_data(raw)
        reader.feed_eof()
        writer = _CaptureWriter()
        await frontend._handle(reader, writer)
        return writer.data

    out = asyncio.run(drive())
    head, _, resp_body = out.partition(b"\r\n\r\n")
    return head.split(b" ", 2)[1].decode(), json.loads(resp_body or b"{}")


class TestHTTPEdge:
    @pytest.mark.parametrize("bad", [
        {"temperature": -1.0},
        {"temperature": 1.0, "top_p": 0.0},
        {"temperature": 1.0, "top_p": 2.0},
        {"top_k": -3},
        {"temperature": "hot"},
    ])
    def test_invalid_params_are_typed_400(self, bad):
        from autodist_tpu.serve.server import ServeFrontend

        # batcher is never reached: params are rejected at the edge
        frontend = ServeFrontend(batcher=object())
        status, body = _post_generate(
            frontend, {"tokens": [1, 2, 3], **bad})
        assert status == "400"
        assert body["type"] == "invalid_sampling_params"

    def test_tenant_defaults_and_override(self):
        from autodist_tpu.serve.server import parse_sampling

        defaults = {"acme": SamplingParams(temperature=0.7, top_p=0.9,
                                           seed=11)}
        got = parse_sampling({"tenant": "acme"}, defaults)
        assert got == defaults["acme"]
        # explicit body fields override the tenant default field-wise
        got = parse_sampling({"tenant": "acme", "temperature": 1.4},
                             defaults)
        assert got.temperature == 1.4 and got.top_p == 0.9
        assert parse_sampling({}, defaults) is None
        assert parse_sampling({"tenant": "other"}, defaults) is None


# --------------------------------------------------------------- plumbing
class TestJournalRoundTrip:
    def test_drain_persist_replay_preserves_sampling(self, tmp_path):
        from autodist_tpu.ft import drain
        from autodist_tpu.serve.batcher import GenRequest

        sp = SamplingParams(temperature=0.9, top_k=20, top_p=0.8, seed=3)
        reqs = [GenRequest(prompt=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=4, request_id="a", sampling=sp),
                GenRequest(prompt=np.asarray([4, 5], np.int32),
                           max_new_tokens=4, request_id="b")]
        path = str(tmp_path / "queue.json")
        assert drain.persist_requests(path, reqs) == 2

        class FakeBatcher:
            calls = []

            def submit(self, prompt, **kw):
                self.calls.append(kw)
                return GenRequest(prompt=np.asarray(prompt, np.int32),
                                  max_new_tokens=kw["max_new_tokens"],
                                  request_id=kw.get("request_id") or "",
                                  sampling=kw.get("sampling"))

        out = drain.replay_requests(path, FakeBatcher())
        by_id = {r.request_id: r for r in out}
        assert by_id["a"].sampling == sp
        assert by_id["b"].sampling is None

    def test_router_journal_carries_sampling(self, tmp_path):
        from autodist_tpu.ft import drain
        from autodist_tpu.serve.batcher import GenRequest

        sp = SamplingParams(temperature=1.1, seed=9)
        req = GenRequest(prompt=np.asarray([7, 8, 9], np.int32),
                         max_new_tokens=4, request_id="j", sampling=sp)
        path = str(tmp_path / "journal.json")
        drain.persist_requests(path, [req])
        entry = drain.merge_journal_entries([path])[0]
        assert SamplingParams.from_dict(entry["sampling"]) == sp


class TestSLOReport:
    def test_acceptance_by_temperature_and_stream_counts(self):
        from autodist_tpu.obs.slo import SLOTracker

        slo = SLOTracker()
        slo.observe(spec_proposed=10, spec_accepted=8, spec_bucket="low")
        slo.observe(spec_proposed=10, spec_accepted=2, spec_bucket="high")
        slo.observe(ok=True, temperature=0.8)
        slo.observe(ok=True, temperature=0.0)
        rep = slo.report()
        accept = rep["measured"]["acceptance_by_temperature"]
        assert accept["low"] == pytest.approx(0.8)
        assert accept["high"] == pytest.approx(0.2)
        assert rep["counts"]["sampled_streams"] == 1
        assert rep["counts"]["greedy_streams"] == 1
