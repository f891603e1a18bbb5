"""Pallas paged-attention kernel + int8 quantized KV pages (ISSUE 20 bars).

- **ops-level parity**: the pallas kernel (interpret mode on CPU — the
  tier-1 correctness vehicle) matches the verbatim gather reference for
  all three entry points — decode step, spec verify (including draft
  windows whose positions clamp to the scratch page), prefill chunk —
  quantized and fp, on lane-dense pages ``[n_pages, page_len, H * D]`` at
  toy widths, at 25 heads of 64 (GPT-2 XL) and at 4 heads of 128;
- **engine-level bit-identity with quant OFF**: kernel-vs-gather token
  STREAMS are bit-equal, greedy and sampled, so flipping the impl can
  never fork a delivered stream (the PR-13/15/17 contracts ride on this);
- **spec losslessness under quant**: draft and verify read the SAME
  quantized pages, so spec streams equal plain streams bit for bit on a
  quantized engine too;
- **bounded quant drift**: teacher-forced max |Δlogit| vs the fp oracle
  stays within the documented bound (docs/serving.md § quantized pages);
- **quantized pool accounting**: pool capacity multiplier, analyzer
  summary, and the scatter/gather round trip;
- **measured crossover**: "auto" resolves kernel-vs-gather per
  (batch, table width, heads) from a recorded sweep, gather off-TPU.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import paged_attention as pa
from autodist_tpu.ops.crossover import (
    DEFAULT_PAGED_CROSSOVER_TIMELINE,
    paged_crossover_timeline,
    resolve_paged_impl,
)

B, P, PAGE_LEN, H, D = 3, 4, 8, 2, 16
N_PAGES = 12


def _lanes(x):
    """``[..., H, D]`` -> ``[..., H * D]``: a page as the pool holds it."""
    return x.reshape(x.shape[:-2] + (-1,))


def _pages(rng, quantized=False, h=H, d=D):
    k = rng.standard_normal((N_PAGES, PAGE_LEN, h, d)).astype(np.float32)
    v = rng.standard_normal((N_PAGES, PAGE_LEN, h, d)).astype(np.float32)
    if not quantized:
        return _lanes(jnp.asarray(k)), _lanes(jnp.asarray(v)), None, None
    kq, ks = pa.quantize_kv(jnp.asarray(k))
    vq, vs = pa.quantize_kv(jnp.asarray(v))
    return _lanes(kq), _lanes(vq), ks, vs


def _tables(rng):
    # Distinct physical pages per row, deliberately out of order: the
    # kernel must follow the table, not the pool layout.
    flat = rng.permutation(N_PAGES)[:B * P].reshape(B, P)
    return jnp.asarray(flat, jnp.int32)


class TestOpsParity:
    """Kernel vs the verbatim gather reference, fp and quantized."""

    @pytest.mark.parametrize("quantized", [False, True])
    def test_decode(self, quantized):
        rng = np.random.default_rng(0)
        kp, vp, ks, vs = _pages(rng, quantized)
        tables = _tables(rng)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        positions = jnp.asarray([0, 7, P * PAGE_LEN - 1], jnp.int32)
        outs = [pa.paged_decode_attention(
            q, kp, vp, tables, positions, k_scale=ks, v_scale=vs,
            impl=impl) for impl in ("gather", "kernel")]
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_verify_with_scratch_clamped_draft_window(self, quantized):
        rng = np.random.default_rng(1)
        kp, vp, ks, vs = _pages(rng, quantized)
        tables = _tables(rng)
        k1 = 5
        q = jnp.asarray(rng.standard_normal((B, k1, H, D)), jnp.float32)
        # Row 2's draft window hangs off the timeline ceiling — exactly
        # the near-max_new_tokens shape forward_paged_verify clamps to
        # the scratch page; its out-of-table queries still attend over
        # every committed position and must match the gather reference.
        base = jnp.asarray([0, 9, P * PAGE_LEN - 2], jnp.int32)
        rows_pos = jnp.minimum(base[:, None] + jnp.arange(k1)[None, :],
                               P * PAGE_LEN - 1)
        outs = [pa.paged_verify_attention(
            q, kp, vp, tables, rows_pos, k_scale=ks, v_scale=vs,
            impl=impl) for impl in ("gather", "kernel")]
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_prefill_chunk(self, quantized):
        rng = np.random.default_rng(2)
        kp, vp, ks, vs = _pages(rng, quantized)
        table = _tables(rng)[0]
        chunk = PAGE_LEN
        q = jnp.asarray(rng.standard_normal((chunk, H, D)), jnp.float32)
        positions = jnp.arange(PAGE_LEN, PAGE_LEN + chunk, dtype=jnp.int32)
        outs = [pa.paged_prefill_attention(
            q, kp, vp, table, positions, k_scale=ks, v_scale=vs,
            impl=impl) for impl in ("gather", "kernel")]
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5, rtol=1e-5)

    def test_kernel_is_jittable(self):
        rng = np.random.default_rng(3)
        kp, vp, _, _ = _pages(rng)
        tables = _tables(rng)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
        positions = jnp.asarray([3, 11, 30], jnp.int32)
        fn = jax.jit(lambda *a: pa.paged_decode_attention(
            *a, impl="kernel", interpret=True))
        np.testing.assert_allclose(
            fn(q, kp, vp, tables, positions),
            pa.paged_decode_attention(q, kp, vp, tables, positions),
            atol=1e-5, rtol=1e-5)


WIDTHS = pytest.mark.parametrize(
    "h, d", [(25, 64), (4, 128)], ids=["25x64", "4x128"])


class TestLaneDenseWidths:
    """Kernel against gather on lane-dense pages at the widths the chip
    serves: the heads are static lane slices at multiples of ``head_dim``,
    so 25 heads of 64 (1,600 lanes, not a multiple of 128) and 4 heads of
    128 take the same code."""

    @WIDTHS
    @pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
    def test_decode(self, h, d, quantized):
        rng = np.random.default_rng(20)
        kp, vp, ks, vs = _pages(rng, quantized, h, d)
        tables = _tables(rng)
        q = jnp.asarray(rng.standard_normal((B, h, d)), jnp.float32)
        positions = jnp.asarray([0, 7, P * PAGE_LEN - 1], jnp.int32)
        outs = [pa.paged_decode_attention(
            q, kp, vp, tables, positions, k_scale=ks, v_scale=vs,
            impl=impl) for impl in ("gather", "kernel")]
        assert outs[1].shape == (B, h, d)
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)

    @WIDTHS
    @pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
    def test_prefill_chunk(self, h, d, quantized):
        rng = np.random.default_rng(21)
        kp, vp, ks, vs = _pages(rng, quantized, h, d)
        table = _tables(rng)[1]
        q = jnp.asarray(rng.standard_normal((PAGE_LEN, h, d)), jnp.float32)
        positions = jnp.arange(2 * PAGE_LEN, 3 * PAGE_LEN, dtype=jnp.int32)
        outs = [pa.paged_prefill_attention(
            q, kp, vp, table, positions, k_scale=ks, v_scale=vs,
            impl=impl) for impl in ("gather", "kernel")]
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)

    @WIDTHS
    def test_gather_reads_heads_where_the_write_put_them(self, h, d):
        # One key per head set apart: the query of head j must find it in
        # lanes [j * d, (j + 1) * d) of the page row, on both paths.
        k = np.zeros((N_PAGES, PAGE_LEN, h, d), np.float32)
        v = np.zeros((N_PAGES, PAGE_LEN, h, d), np.float32)
        for j in range(h):
            v[1, 0, j, :] = j + 1.0
        tables = jnp.asarray([[1, 0, 0, 0]], jnp.int32)
        q = jnp.ones((1, h, d), jnp.float32)
        for impl in ("gather", "kernel"):
            out = pa.paged_decode_attention(
                q, _lanes(jnp.asarray(k)), _lanes(jnp.asarray(v)), tables,
                jnp.asarray([0], jnp.int32), impl=impl)
            np.testing.assert_allclose(
                out[0, :, 0], np.arange(1, h + 1, dtype=np.float32))


# ------------------------------------------------ the grouped walk (PR 35)
# Tables of 32 pages of 8 walk 16 pages (128 keys) a grid step, two groups a
# row; a table of 7 pages (a prime: nothing larger divides it) walks a page
# a step, as the kernel did before it walked groups.
GROUPED = {"grouped": 32, "prime": 7}
ENTRIES = ("decode", "chunk", "verify")


def _walk_case(rng, entry, quantized, n_tables, poison=False):
    """One call of ``entry`` over rows that end at 0 (an idle row on an
    all-scratch table), one short of a group's edge, on it, one past it and
    at the table's last slot. Returns ``run(impl) -> output``. With
    ``poison`` every page past each row's last live group holds NaN (the
    scale planes of an int8 pool)."""
    group, _ = pa.paged_blocking(
        {"decode": 1, "chunk": PAGE_LEN, "verify": 5}[entry], n_tables,
        PAGE_LEN, H * D, 1 if quantized else 4)
    span, timeline = group * PAGE_LEN, n_tables * PAGE_LEN
    ends = [0, span - 1, span, span + 1, timeline - 1]
    n_pages = 1 + len(ends) * n_tables
    k = rng.standard_normal((n_pages, PAGE_LEN, H, D)).astype(np.float32)
    v = rng.standard_normal((n_pages, PAGE_LEN, H, D)).astype(np.float32)
    tables = 1 + rng.permutation(n_pages - 1).reshape(len(ends), n_tables)
    tables[0] = 0                                   # the idle row: scratch
    if quantized:
        kp, ks = pa.quantize_kv(jnp.asarray(k))
        vp, vs = pa.quantize_kv(jnp.asarray(v))
        kp, vp, ks, vs = (np.array(x) for x in (_lanes(kp), _lanes(vp), ks, vs))
    else:
        kp, vp, ks, vs = np.array(_lanes(k)), np.array(_lanes(v)), None, None
    if poison:
        for row, end in zip(tables[1:], ends[1:]):
            dead = row[(end // span + 1) * group:]
            for plane in ((ks, vs) if quantized else (kp, vp)):
                plane[dead] = np.nan
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    scales = dict(k_scale=None if ks is None else jnp.asarray(ks),
                  v_scale=None if vs is None else jnp.asarray(vs))
    tables = jnp.asarray(tables, jnp.int32)
    ends_a = jnp.asarray(ends, jnp.int32)
    if entry == "decode":
        q = jnp.asarray(rng.standard_normal((len(ends), H, D)), jnp.float32)
        return lambda impl: pa.paged_decode_attention(
            q, kp, vp, tables, ends_a, impl=impl, **scales)
    if entry == "verify":
        k1 = 5
        q = jnp.asarray(rng.standard_normal((len(ends), k1, H, D)), jnp.float32)
        rows_pos = jnp.minimum(
            jnp.maximum(ends_a - k1 + 1, 0)[:, None] + jnp.arange(k1)[None],
            ends_a[:, None])
        return lambda impl: pa.paged_verify_attention(
            q, kp, vp, tables, rows_pos, impl=impl, **scales)
    q = jnp.asarray(rng.standard_normal((PAGE_LEN, H, D)), jnp.float32)

    def chunks(impl):
        # a chunk a row, its last query at the row's end
        return jnp.stack([pa.paged_prefill_attention(
            q, kp, vp, tables[i],
            jnp.maximum(end - PAGE_LEN + 1 + jnp.arange(PAGE_LEN), 0),
            impl=impl, **scales) for i, end in enumerate(ends)])
    return chunks


class TestGroupedWalk:
    """The kernel walks a group of pages a grid step and skips the groups
    past a row's last position; against the gather reference at every
    entry point, fp and int8, at a table width the group divides and at
    one only a single page divides."""

    @pytest.mark.parametrize("width", GROUPED.values(), ids=GROUPED.keys())
    @pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("entry", ENTRIES)
    def test_rows_around_a_group_edge(self, entry, quantized, width):
        run = _walk_case(np.random.default_rng(35), entry, quantized, width)
        np.testing.assert_allclose(run("gather"), run("kernel"),
                                   atol=2e-5, rtol=2e-5)

    def test_a_chunk_of_several_query_tiles(self):
        # 40 queries go in three tiles of 16 (the pad repeats the last);
        # a tile walks the groups up to its own last query
        rng = np.random.default_rng(37)
        n_tables, c = 32, 40
        n_pages = 1 + n_tables
        kp = _lanes(jnp.asarray(rng.standard_normal(
            (n_pages, PAGE_LEN, H, D)), jnp.float32))
        vp = _lanes(jnp.asarray(rng.standard_normal(
            (n_pages, PAGE_LEN, H, D)), jnp.float32))
        table = jnp.asarray(1 + rng.permutation(n_tables), jnp.int32)
        q = jnp.asarray(rng.standard_normal((c, H, D)), jnp.float32)
        positions = jnp.arange(100, 100 + c, dtype=jnp.int32)
        outs = [pa.paged_prefill_attention(q, kp, vp, table, positions,
                                           impl=impl)
                for impl in ("gather", "kernel")]
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("entry", ENTRIES)
    def test_skipped_groups_are_never_read(self, entry, quantized):
        clean = _walk_case(np.random.default_rng(36), entry, quantized,
                           GROUPED["grouped"])("kernel")
        dirty = _walk_case(np.random.default_rng(36), entry, quantized,
                           GROUPED["grouped"], poison=True)("kernel")
        assert np.isfinite(np.asarray(dirty)).all()
        np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


# (queries a row, table width, page_len, lanes, item size) -> pages a step
SERVED = {
    "xl-decode": ((1, 64, 16, 1600, 2), 8),
    "xl-chunk": ((16, 64, 16, 1600, 2), 8),
    "xl-verify": ((5, 64, 16, 1600, 2), 8),
    "xl-decode-int8": ((1, 64, 16, 1600, 1), 8),
    "xl-chunk-f32": ((16, 64, 16, 1600, 4), 8),
    "xl-chunk-int8": ((16, 64, 16, 1600, 1), 8),
    "medium-decode": ((1, 64, 16, 1024, 2), 8),
    "medium-chunk": ((16, 64, 16, 1024, 2), 8),
    "4x128-decode": ((1, 64, 16, 512, 2), 8),
    "prime-width": ((1, 61, 16, 1600, 2), 1),
    "narrow-table": ((16, 6, 16, 1600, 2), 6),
    "short-pages": ((1, 64, 4, 1600, 2), 32),
    "wide-lanes": ((1, 64, 16, 8192, 4), 2),
}


class TestBlocking:
    @pytest.mark.parametrize("shape, want", SERVED.values(), ids=SERVED.keys())
    def test_group_divides_the_table_and_fits(self, shape, want):
        n_q, n_tables, page_len, lanes, itemsize = shape
        group, q_tile = pa.paged_blocking(*shape)
        assert group == want and n_tables % group == 0
        assert group * page_len <= 128
        operand = {1: 8, 2: 2, 4: 4}[itemsize]
        blocks = group * page_len * lanes * 2 * (2 * itemsize + operand)
        assert group == 1 or blocks <= pa._VMEM_BUDGET
        assert q_tile == (1 if n_q == 1 else 16 if itemsize == 2 else
                          min(16, -(-n_q // 8) * 8))

    def test_a_smaller_budget_walks_fewer_pages(self):
        shape = (16, 64, 16, 1600, 2)
        assert pa.paged_blocking(*shape, vmem_budget=1 << 20)[0] == 2
        assert pa.paged_blocking(*shape, vmem_budget=1)[0] == 1


class TestQuantization:
    def test_round_trip_error_bounded(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((5, PAGE_LEN, H, D)) * 3.0,
                        jnp.float32)
        q, scale = pa.quantize_kv(x)
        assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
        back = pa.dequantize_kv(q, scale, jnp.float32)
        # int8 symmetric: error <= scale/2 = amax/254 per (pos, head) row.
        bound = np.asarray(scale)[..., None] / 2.0 + 1e-8
        assert np.all(np.abs(np.asarray(back - x)) <= bound)

    def test_zero_rows_stay_zero(self):
        x = jnp.zeros((2, PAGE_LEN, H, D), jnp.float32)
        q, scale = pa.quantize_kv(x)
        assert not np.any(np.asarray(q)) and not np.any(np.asarray(scale))
        assert not np.any(np.asarray(pa.dequantize_kv(q, scale, jnp.float32)))

    def test_quantize_is_deterministic(self):
        # Failover re-prefill must reproduce the dead replica's pages
        # bit-exactly (chaos: kill_mid_quantized_stream).
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((3, PAGE_LEN, H, D)), jnp.float32)
        q1, s1 = pa.quantize_kv(x)
        q2, s2 = pa.quantize_kv(jnp.asarray(np.asarray(x)))
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))


class TestMaskHelper:
    """The ONE shared mask/-1e30 helper all four forward paths use."""

    def test_fp32_mask_value_preserves_bit_identity(self):
        # The historical constant: changing it would fork every pinned
        # fp32 stream in the repo.
        assert pa.mask_value(jnp.float32) == -1e30
        assert pa.mask_value(jnp.float64) == -1e30

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
    def test_half_precision_mask_is_finite(self, dtype):
        # -1e30 overflows fp16 to -inf; -inf minus -inf is NaN in the
        # online-softmax rescale. The helper keeps halves finite.
        mv = pa.mask_value(dtype)
        assert np.isfinite(np.asarray(jnp.asarray(mv, dtype), np.float32))
        assert mv < -1e4

    def test_position_mask_and_apply(self):
        mask = pa.position_mask(4, jnp.asarray([0, 2]))
        np.testing.assert_array_equal(
            np.asarray(mask),
            [[True, False, False, False], [True, True, True, False]])
        logits = jnp.zeros((2, 4), jnp.float32)
        out = np.asarray(pa.apply_mask(logits, mask))
        assert out[0, 1] == -1e30 and out[1, 3] == -1e30 and out[1, 2] == 0


class TestEngineStreams:
    """Kernel-vs-gather and quant bars at the token-stream level."""

    def _prompts(self, seed=7, n=6):
        rng = np.random.default_rng(seed)
        out = [rng.integers(1, 127, size=int(rng.integers(3, 12)))
               .astype(np.int32) for _ in range(n - 1)]
        out.append(rng.integers(1, 127, size=20).astype(np.int32))
        return out

    @pytest.mark.parametrize("kv_quant", [False, True])
    def test_kernel_stream_bit_equal_greedy_and_sampled(self, kv_quant):
        from autodist_tpu.serve.sampling import SamplingParams
        from autodist_tpu.serve.server import _tiny_engine

        gather, _, _ = _tiny_engine(n_slots=4, kv_quant=kv_quant)
        kernel, _, _ = _tiny_engine(n_slots=4, kv_quant=kv_quant,
                                    paged_impl="kernel")
        for i, p in enumerate(self._prompts()):
            assert gather.generate(p, 10) == kernel.generate(p, 10)
            sp = SamplingParams(temperature=0.9, top_k=24, top_p=0.95,
                                seed=i)
            rid = f"kq-{i}"
            assert (gather.generate(p, 10, request_id=rid, sampling=sp)
                    == kernel.generate(p, 10, request_id=rid, sampling=sp))

    def test_quant_off_stream_unchanged_vs_fp(self):
        # kv_quant=False engines must stream exactly what they always
        # streamed — the refactor is bit-preserving for existing serving.
        from autodist_tpu.serve.server import _tiny_engine

        fp, _, _ = _tiny_engine(n_slots=4)
        quant, _, _ = _tiny_engine(n_slots=4, kv_quant=True)
        assert fp.kv_quant is False and quant.kv_quant is True

    def test_spec_lossless_under_quant(self):
        # Draft and verify read the SAME quantized pages: spec streams on
        # a quantized engine equal the plain quantized engine's greedy.
        from autodist_tpu.serve.router import build_test_fleet

        router, control = build_test_fleet(n_replicas=1, spec_decode=True,
                                           kv_quant=True)
        try:
            spec_engine = router.replicas[0].engine_factory()
            assert control.kv_quant and spec_engine.kv_quant
            for p in self._prompts(seed=11, n=4):
                assert (spec_engine.generate(p, 8)
                        == control.generate(p, 8))
        finally:
            router.stop(drain=False)

    def test_quant_drift_bounded(self):
        from autodist_tpu.serve.server import (
            QUANT_LOGIT_DRIFT_BOUND,
            _quant_logit_drift,
            _tiny_engine,
        )

        _, params, cfg = _tiny_engine(n_slots=4)
        drift = _quant_logit_drift(params, cfg)
        assert 0.0 < drift < QUANT_LOGIT_DRIFT_BOUND


class TestQuantPool:
    def test_pool_capacity_multiplier(self):
        from autodist_tpu.serve import pages as serve_pages

        pool = serve_pages.build_pool(10, 8, quantized=True,
                                      bytes_per_page=1280.0,
                                      fp_equiv_bytes_per_page=4096.0)
        assert pool.quantized
        assert pool.physical_bytes == 12800.0
        assert pool.fp_equiv_bytes == 40960.0
        assert pool.quant_capacity_x == pytest.approx(3.2)
        fp_pool = serve_pages.build_pool(10, 8, bytes_per_page=4096.0)
        assert fp_pool.quant_capacity_x == 1.0

    def test_engine_prices_quant_pages(self):
        from autodist_tpu.serve.server import _tiny_engine

        engine, _, _ = _tiny_engine(n_slots=4, kv_quant=True)
        assert engine.kv_quant
        # int8 k/v + f32 scales vs f32 k/v at head_dim 16: 3.2x.
        assert engine.quant_capacity_x == pytest.approx(3.2)
        assert (engine.page_pool_fp_equiv_bytes
                > 3 * engine.page_pool_bytes)

    def test_analyzer_accounts_quant_bytes(self):
        from autodist_tpu.analysis.passes import hbm_budget
        from autodist_tpu.serve.server import _tiny_engine

        engine, _, _ = _tiny_engine(n_slots=4, kv_quant=True)
        _, mem = hbm_budget(engine.plan,
                            serve_pool_bytes=engine.page_pool_bytes,
                            serve_quant_capacity_x=engine.quant_capacity_x)
        # SLM001 prices the PHYSICAL quantized bytes...
        assert mem["serve_pool_gb_per_chip"] * 1e9 == pytest.approx(
            engine.page_pool_bytes)
        # ...and the summary carries the effective-capacity multiplier.
        assert mem["serve_quant_capacity_x"] == pytest.approx(
            engine.quant_capacity_x)
        assert mem["serve_pool_fp_equiv_gb_per_chip"] == pytest.approx(
            mem["serve_pool_gb_per_chip"] * engine.quant_capacity_x)


class TestCrossover:
    def test_explicit_impls_pass_through(self):
        assert resolve_paged_impl("gather", 4, 4, 8, 2) == "gather"
        assert resolve_paged_impl("kernel", 4, 4, 8, 2) == "kernel"
        with pytest.raises(ValueError):
            pa.paged_decode_attention(
                jnp.zeros((1, H, D)), jnp.zeros((2, PAGE_LEN, H * D)),
                jnp.zeros((2, PAGE_LEN, H * D)), jnp.zeros((1, 1), jnp.int32),
                jnp.zeros((1,), jnp.int32), impl="auto")

    def test_auto_is_gather_off_tpu(self):
        if jax.default_backend() == "tpu":
            pytest.skip("off-TPU rule")
        assert resolve_paged_impl("auto", 4, 512, 8, 2) == "gather"

    def test_measured_sweep_picks_crossover(self, tmp_path):
        rows = []
        for tl, (g, k) in [(64, (100.0, 50.0)), (256, (80.0, 70.0)),
                           (1024, (60.0, 90.0)), (4096, (40.0, 110.0))]:
            rows.append(dict(batch=8, heads=8, table_pages=tl // 16,
                             page_len=16, impl="gather", tokens_per_sec=g))
            rows.append(dict(batch=8, heads=8, table_pages=tl // 16,
                             page_len=16, impl="kernel", tokens_per_sec=k))
        path = tmp_path / "paged_crossover.json"
        path.write_text(json.dumps({"rows": rows}))
        assert paged_crossover_timeline(8, 8, path=str(path)) == 1024

    def test_nearest_bucket_and_default(self, tmp_path):
        rows = [dict(batch=1, heads=2, table_pages=2, page_len=16,
                     impl=i, tokens_per_sec=t)
                for i, t in [("gather", 10.0), ("kernel", 20.0)]]
        rows += [dict(batch=32, heads=8, table_pages=64, page_len=16,
                      impl=i, tokens_per_sec=t)
                 for i, t in [("gather", 30.0), ("kernel", 40.0)]]
        path = tmp_path / "paged_crossover.json"
        path.write_text(json.dumps({"rows": rows}))
        # batch 2 is nearest the (1, 2) bucket: crossover at its timeline.
        assert paged_crossover_timeline(2, 2, path=str(path)) == 32
        # batch 40 is nearest the (32, 8) bucket.
        assert paged_crossover_timeline(40, 8, path=str(path)) == 1024
        # Missing file -> packaged default.
        missing = tmp_path / "nope.json"
        assert (paged_crossover_timeline(8, 8, path=str(missing))
                == DEFAULT_PAGED_CROSSOVER_TIMELINE)
