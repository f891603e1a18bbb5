"""attention_impl="auto": measured-crossover flash/dot selection.

The transformer's default attention now auto-selects the Pallas flash
kernel at and above the crossover sequence length recorded by the device
sweep (``docs/measured/flash_crossover.json``), and XLA's fused dot
attention below it; explicit "dot"/"flash"/"ring" are always honored.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops import crossover as X
from autodist_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)


class TestCrossoverRule:
    def test_measured_file_yields_crossover(self):
        # The checked-in v5e sweep: flash ties dot at 1024 and wins beyond.
        assert X.flash_crossover_seq() == 1024

    def test_missing_file_falls_back_to_default(self, tmp_path):
        X._cache.pop(str(tmp_path / "nope.json"), None)
        assert (X.flash_crossover_seq(str(tmp_path / "nope.json"))
                == X.DEFAULT_FLASH_CROSSOVER_SEQ)

    def test_corrupt_file_falls_back(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        assert X.flash_crossover_seq(str(p)) == X.DEFAULT_FLASH_CROSSOVER_SEQ

    def test_crossover_requires_flash_to_stay_winning(self, tmp_path):
        # flash wins at 512 but loses again at 1024 -> the crossover is
        # where it wins AND never loses after (2048 here).
        import json

        p = tmp_path / "sweep.json"
        rows = [
            {"seq": 512, "impl": "dot", "tokens_per_sec": 90.0},
            {"seq": 512, "impl": "flash", "tokens_per_sec": 100.0},
            {"seq": 1024, "impl": "dot", "tokens_per_sec": 100.0},
            {"seq": 1024, "impl": "flash", "tokens_per_sec": 90.0},
            {"seq": 2048, "impl": "dot", "tokens_per_sec": 80.0},
            {"seq": 2048, "impl": "flash", "tokens_per_sec": 120.0},
        ]
        p.write_text(json.dumps({"rows": rows}))
        assert X.flash_crossover_seq(str(p)) == 2048

    def test_resolve(self, monkeypatch):
        monkeypatch.setattr(X, "flash_crossover_seq", lambda: 1024)
        assert X.resolve_attention_impl("auto", 512) == "dot"
        assert X.resolve_attention_impl("auto", 1024) == "flash"
        assert X.resolve_attention_impl("auto", 2048) == "flash"
        # Above the crossover but not block-aligned: the kernel would fall
        # back to the jnp reference anyway — stay on the fused dot path.
        assert X.resolve_attention_impl("auto", 1100) == "dot"
        # Explicit impls pass through untouched.
        for impl in ("dot", "flash", "ring", "ulysses"):
            assert X.resolve_attention_impl(impl, 4096) == impl


class TestAutoForward:
    def _setup(self, seq, impl):
        cfg = TransformerConfig(
            vocab_size=128, num_layers=1, d_model=32, num_heads=4,
            max_seq_len=seq, d_ff=64, attention_impl=impl)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = (jnp.arange(2 * seq, dtype=jnp.int32).reshape(2, seq)
                  % cfg.vocab_size)
        return cfg, params, tokens

    def test_default_is_auto(self):
        assert TransformerConfig().attention_impl == "auto"

    def test_auto_matches_dot_below_crossover(self):
        cfg_a, params, tokens = self._setup(64, "auto")
        cfg_d, _, _ = self._setup(64, "dot")
        np.testing.assert_array_equal(
            np.asarray(forward(params, tokens, cfg_a)),
            np.asarray(forward(params, tokens, cfg_d)))

    def test_auto_matches_flash_above_crossover(self, monkeypatch):
        # Shrink the crossover so the flash path engages at a test-sized
        # seq (128: block-aligned, so the pallas kernel really runs —
        # interpret mode on CPU).
        monkeypatch.setattr(X, "flash_crossover_seq", lambda: 128)
        cfg_a, params, tokens = self._setup(128, "auto")
        cfg_f, _, _ = self._setup(128, "flash")
        out_auto = np.asarray(forward(params, tokens, cfg_a))
        out_flash = np.asarray(forward(params, tokens, cfg_f))
        np.testing.assert_array_equal(out_auto, out_flash)
        # ...and the flash path differs bit-wise from dot (different
        # reduction order), proving auto actually switched kernels.
        cfg_d, _, _ = self._setup(128, "dot")
        out_dot = np.asarray(forward(params, tokens, cfg_d))
        np.testing.assert_allclose(out_auto, out_dot, atol=2e-2)

    def test_explicit_impls_still_work(self):
        for impl in ("dot", "flash"):
            cfg, params, tokens = self._setup(128, impl)
            out = forward(params, tokens, cfg)
            assert np.isfinite(np.asarray(out)).all()

    def test_unknown_impl_raises(self):
        cfg, params, tokens = self._setup(64, "nope")
        with pytest.raises(ValueError, match="unknown attention_impl"):
            forward(params, tokens, cfg)

    def test_explicit_flash_on_unaligned_seq_raises(self):
        # The op falls back to the jnp reference for direct callers; a
        # MODEL configured for the kernel must not quietly train another
        # program. ("auto" never resolves to flash on such a shape.)
        cfg, params, tokens = self._setup(96, "flash")
        with pytest.raises(ValueError, match="multiple of 128"):
            forward(params, tokens, cfg)
        cfg_a, _, _ = self._setup(96, "auto")
        assert np.isfinite(np.asarray(forward(params, tokens, cfg_a))).all()
