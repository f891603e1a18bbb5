"""Counter-based stochastic sampling — the ONE sampling home.

Every serve path was pinned to greedy argmax; this module adds
temperature / top-k / top-p sampling WITHOUT giving up the stack's
strongest invariants (journaled exactly-once failover, bit-identical
replay, compiled-program pins). Three pieces:

1. :class:`SamplingParams` — the per-request record (temperature,
   ``top_k``, ``top_p``, ``seed``; greedy is the ``temperature=0``
   degenerate case). It rides a request from the HTTP edge through
   admission, the batcher's slot state, the router journal (format-v2
   ``"sampling"`` entry key) and per-tenant defaults, validated ONCE at
   the edge (:meth:`SamplingParams.validate` raises the typed
   :class:`InvalidSamplingParams`, a ``ValueError`` the HTTP front ends
   map to 400, never 500).

2. **Stateless counter-based RNG** — the random draw for the token at
   absolute sequence position ``t`` of request ``r`` is a pure function
   of ``(r.request_id, r.seed, t)``: a blake2b-derived threefry key
   (:func:`request_key`) folded with the position counter. No RNG state
   is ever carried between steps, so a failover replay on a survivor, a
   drain-journal replay after restart, and a prefix-cache hit all
   reproduce the identical stream — the router's overlap-token
   bit-identity assertion holds for stochastic streams unchanged.

3. :func:`sample_tokens` — the on-device batched transform (temperature
   scale → top-k mask → top-p nucleus mask → categorical draw via
   Gumbel-argmax from the counter key), applied per-slot INSIDE the
   existing compiled decode/verify/prefill programs with params as
   per-slot arrays: nothing recompiles per request and the
   2-plain/5-spec compiled-program pins hold. Rows with
   ``temperature <= 0`` return exactly the old ``argmax`` token, so an
   all-greedy batch is bit-identical to the pre-sampling engine.

Speculative decode stays **lossless for any draft** via the classic
accept/resample rule (Leviathan et al., arXiv 2211.17192) realized as a
*maximal coupling*: the target's emitted token at position ``t`` is
always ``argmax(filtered_logits/T + gumbel(key_r, t))`` — an exact
categorical sample from the target's filtered distribution — and the
draft proposes with the SAME ``(key_r, t)`` noise over its own filtered
distribution. Verify accepts the leading draft proposals that match the
target's own draw (the reject path's emission IS the residual resample:
it is the target's sample at that position, untouched by the draft), so
emitted tokens are exact target samples for ANY draft, spec streams are
bit-identical to plain stochastic streams, and when draft == target the
shared noise makes acceptance 1 (the optimal transport coupling).
``temperature=0`` reduces bit-identically to the greedy accept/reject
shipped in PR 15.

``tools/check_patterns.py`` rule 10 bans any second sampling-RNG
construction (``jax.random.categorical/gumbel/fold_in/bernoulli``) in
``serve/`` or ``models/`` outside this module — same single-home
discipline as the page allocator (rule 8) and the radix tree (rule 9).

``python -m autodist_tpu.serve --selftest-sampling`` is the CPU proof:
chi-square calibration of the transform, spec-vs-plain bit-identity
across temperature × top_p × k for good and garbage drafts, greedy
reduction, prefix hit-vs-cold bit-identity, mid-decode kills with every
resumed stream bit-identical to its uninterrupted control, and the
program pins (docs/serving.md § stochastic sampling).
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

__all__ = [
    "InvalidSamplingParams",
    "SamplingParams",
    "request_key",
    "sample_tokens",
    "slot_arrays",
    "temperature_bucket",
    "TEMPERATURE_BUCKETS",
    "chi_square_fits",
    "selftest_sampling",
]


class InvalidSamplingParams(ValueError):
    """Typed rejection for malformed sampling params — a ``ValueError``
    subclass so the HTTP front ends' existing 400 mapping catches it
    (invalid user input must never surface as a 500)."""


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling record; ``temperature=0`` means greedy.

    ``top_k <= 0`` disables the top-k mask; ``top_p`` must lie in
    ``(0, 1]`` (1.0 disables the nucleus mask). ``seed`` feeds
    :func:`request_key` next to the request id, so retrying the same id
    with a different seed draws a fresh stream while a failover replay
    of the same ``(request_id, seed)`` is bit-identical.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    def validate(self) -> "SamplingParams":
        """Return self or raise the typed :class:`InvalidSamplingParams`."""
        if not math.isfinite(self.temperature) or self.temperature < 0.0:
            raise InvalidSamplingParams(
                f"temperature must be a finite float >= 0, got "
                f"{self.temperature!r}")
        if self.top_k < 0:
            raise InvalidSamplingParams(
                f"top_k must be >= 0 (0 disables), got {self.top_k!r}")
        if not (0.0 < self.top_p <= 1.0):
            raise InvalidSamplingParams(
                f"top_p must be in (0, 1], got {self.top_p!r}")
        return self

    # ------------------------------------------------- journal serde
    def to_dict(self) -> Dict[str, float]:
        return {"temperature": float(self.temperature),
                "top_k": int(self.top_k),
                "top_p": float(self.top_p),
                "seed": int(self.seed)}

    @classmethod
    def from_dict(cls, d: Optional[dict]) -> Optional["SamplingParams"]:
        """Rebuild from a journal entry; ``None``/``{}`` -> ``None``
        (greedy). Malformed values raise the typed error, which the
        drain replay's drop-with-warning path already tolerates."""
        if not d:
            return None
        try:
            return cls(temperature=float(d.get("temperature", 0.0)),
                       top_k=int(d.get("top_k", 0)),
                       top_p=float(d.get("top_p", 1.0)),
                       seed=int(d.get("seed", 0))).validate()
        except (TypeError, AttributeError) as err:
            raise InvalidSamplingParams(f"malformed sampling dict: {err}")


def request_key(request_id: str, seed: int) -> Tuple[int, int]:
    """Derive the per-request threefry key (two uint32 words) from the
    stable request identity. Pure function of ``(request_id, seed)`` —
    the whole replay contract rests on this never depending on engine,
    replica, cache or batch state."""
    h = hashlib.blake2b(f"{request_id}\x00{int(seed)}".encode("utf-8"),
                        digest_size=8).digest()
    return (int.from_bytes(h[:4], "little"),
            int.from_bytes(h[4:], "little"))


# Temperature buckets for SLO acceptance-rate attribution: greedy is its
# own bucket (coupled acceptance behaves differently at T=0), the rest
# split at the conventional 0.5 / 1.0 knees.
TEMPERATURE_BUCKETS = ("greedy", "low", "mid", "high")


def temperature_bucket(temperature: float) -> str:
    t = float(temperature)
    if t <= 0.0:
        return "greedy"
    if t <= 0.5:
        return "low"
    if t <= 1.0:
        return "mid"
    return "high"


def slot_arrays(n_slots: int):
    """Fresh host-side per-slot sampling arrays at the greedy defaults
    (the engine owns one set; a released slot resets its row here)."""
    import numpy as np

    return {"temperature": np.zeros(n_slots, np.float32),
            "top_k": np.zeros(n_slots, np.int32),
            "top_p": np.ones(n_slots, np.float32),
            "key_hi": np.zeros(n_slots, np.uint32),
            "key_lo": np.zeros(n_slots, np.uint32)}


def sample_tokens(logits, counters, samp):
    """The on-device batched sampling transform.

    ``logits``: ``[..., V]`` float array (any float dtype; filtered in
    fp32). ``counters``: ``[...]`` int32 — each entry is the emitted
    token's ABSOLUTE sequence position (prefill final chunk: ``length``;
    decode: ``positions + 1``; verify row ``j``: ``positions + j + 1``).
    ``samp``: 5-tuple of per-slot arrays ``(temperature f32[B], top_k
    i32[B], top_p f32[B], key_hi u32[B], key_lo u32[B])``, broadcast
    against ``counters`` for multi-token rows (verify).

    Rows with ``temperature <= 0`` return exactly ``argmax(logits)`` —
    bit-identical to the pre-sampling greedy programs. Everything here
    is shape-static: params ride as traced arrays, so the surrounding
    compiled program never recompiles per request.

    The stochastic transform (the sorts, the softmax, the cumulative sum
    and the Gumbel draw over the vocabulary) sits in the true branch of
    one ``lax.cond`` on the device: it runs only when some row of the
    call has ``temperature > 0``; a call whose rows are all greedy
    returns the argmax alone. Either branch gives a greedy row its
    argmax and a stochastic row always takes the transform, so every
    row's token is the same whatever the batch around it.
    """
    import jax
    import jax.numpy as jnp

    temperature, top_k, top_p, key_hi, key_lo = samp
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    counters = counters.astype(jnp.int32)
    shape = counters.shape

    def per_slot(a, dtype):
        a = jnp.asarray(a, dtype)
        extra = len(shape) - a.ndim
        return jnp.broadcast_to(a.reshape(a.shape + (1,) * extra), shape)

    temperature = per_slot(temperature, jnp.float32)
    top_k = per_slot(top_k, jnp.int32)
    top_p = per_slot(top_p, jnp.float32)
    key_hi = per_slot(key_hi, jnp.uint32)
    key_lo = per_slot(key_lo, jnp.uint32)

    def transform():
        # Temperature scale (clamped: the T<=0 rows take the greedy
        # branch of the final where, this value is never observed for
        # them).
        scaled = logits / jnp.maximum(temperature, 1e-6)[..., None]

        # Top-k: keep scores >= the k-th largest; top_k<=0 disables.
        sorted_desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
        k_eff = jnp.where(top_k <= 0, vocab, jnp.clip(top_k, 1, vocab))
        kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[..., None],
                                  axis=-1)
        masked = jnp.where(scaled >= kth, scaled, -jnp.inf)

        # Top-p nucleus over the top-k survivors: keep the smallest set of
        # highest-probability tokens whose mass reaches top_p (always >= 1
        # token — the strict '< top_p' on the EXCLUSIVE prefix sum keeps
        # the head even when its own mass already exceeds top_p).
        probs = jax.nn.softmax(masked, axis=-1)
        p_sorted = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
        prefix = jnp.cumsum(p_sorted, axis=-1) - p_sorted
        keep_sorted = prefix < top_p[..., None]
        thresh = jnp.min(jnp.where(keep_sorted, p_sorted, jnp.inf),
                         axis=-1, keepdims=True)
        filtered = jnp.where(probs >= thresh, masked, -jnp.inf)

        # Counter-based categorical draw via Gumbel-argmax: the key is a
        # pure function of (request key, absolute position) — never
        # carried state — so replay anywhere reproduces the identical
        # draw. Raw threefry2x32 key material; fold_in mixes the position
        # counter.
        def draw(hi, lo, counter):
            key = jax.random.fold_in(jnp.stack([hi, lo]), counter)
            return jax.random.gumbel(key, (vocab,), jnp.float32)

        flat = jax.vmap(draw)(key_hi.reshape(-1), key_lo.reshape(-1),
                              counters.reshape(-1))
        gumbel = flat.reshape(shape + (vocab,))
        sampled = jnp.argmax(filtered + gumbel, axis=-1).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy_tok, sampled)

    # The predicate is read from the per-slot arrays the program already
    # takes: no new input, no host round trip, no second program.
    return jax.lax.cond(jnp.any(temperature > 0.0), transform,
                        lambda: greedy_tok)


# --------------------------------------------------------------- stats
def chi_square_fits(observed, expected_probs, alpha_crit: float = 3.0):
    """Pearson chi-square goodness-of-fit without scipy: returns
    ``(fits, stat, crit)`` where ``crit`` is the Wilson–Hilferty
    approximation of the chi-square quantile at ``alpha_crit`` standard
    normal deviations (3.0 ~ the 99.87th percentile — loose enough that
    a seeded, deterministic test never flakes, tight enough that a
    mis-scaled or un-filtered distribution fails by orders of
    magnitude). Bins with expected count < 5 are pooled into the last
    bin (the classic validity rule)."""
    import numpy as np

    obs = np.asarray(observed, np.float64)
    exp = np.asarray(expected_probs, np.float64)
    exp = exp / exp.sum() * obs.sum()
    order = np.argsort(exp)[::-1]
    obs, exp = obs[order], exp[order]
    # Pool the sparse tail so every bin has expected >= 5.
    keep = exp >= 5.0
    if not keep.all():
        first_bad = int(np.argmax(~keep))
        first_bad = max(first_bad, 1)
        obs = np.concatenate([obs[:first_bad], [obs[first_bad:].sum()]])
        exp = np.concatenate([exp[:first_bad], [exp[first_bad:].sum()]])
    dof = max(len(obs) - 1, 1)
    stat = float(((obs - exp) ** 2 / np.maximum(exp, 1e-12)).sum())
    # Wilson–Hilferty: chi2_q(dof) ~ dof * (1 - 2/(9 dof) + z sqrt(2/(9 dof)))^3
    z = float(alpha_crit)
    crit = dof * (1.0 - 2.0 / (9.0 * dof)
                  + z * math.sqrt(2.0 / (9.0 * dof))) ** 3
    return stat <= crit, stat, crit


def _filtered_probs(logits, params: SamplingParams):
    """Host-side reference of the transform's filtered distribution
    (numpy mirror of :func:`sample_tokens`'s masking) for calibration."""
    import numpy as np

    x = np.asarray(logits, np.float64)
    if params.greedy:
        p = np.zeros_like(x)
        p[int(np.argmax(x))] = 1.0
        return p
    scaled = x / max(params.temperature, 1e-6)
    if params.top_k > 0:
        kth = np.sort(scaled)[::-1][min(params.top_k, len(scaled)) - 1]
        scaled = np.where(scaled >= kth, scaled, -np.inf)
    e = np.exp(scaled - np.max(scaled[np.isfinite(scaled)]))
    e = np.where(np.isfinite(scaled), e, 0.0)
    probs = e / e.sum()
    order = np.argsort(probs)[::-1]
    prefix = np.cumsum(probs[order]) - probs[order]
    keep_sorted = prefix < params.top_p
    thresh = probs[order][keep_sorted].min()
    probs = np.where(probs >= thresh, probs, 0.0)
    return probs / probs.sum()


# ------------------------------------------------------------ selftest
def selftest_sampling() -> int:
    """CPU proof of the stochastic-sampling contract. Bars:

    1. transform calibration: chi-square of many counter-keyed draws
       against the analytically filtered softmax, for plain / top-k /
       top-p / combined params; top-k and top-p masks never leak a
       banned token; temperature=0 rows reduce bit-exactly to argmax.
    2. engine replay: the same ``(request_id, seed)`` regenerates the
       identical stream; a different seed diverges.
    3. lossless spec sampling: spec-decode streams bit-identical to the
       plain stochastic control across temperature × top_p × k for a
       same-weights draft, a trained-divergent draft AND a garbage
       draft; chi-square over the pooled spec-vs-plain token counts;
       temperature=0 spec reduces bit-identically to greedy spec.
    4. prefix sharing: cache-hit vs cold-start of the same
       ``(request_id, prompt, seed)`` produce bit-identical streams.
    5. failover: mid-decode replica kills under stochastic traffic —
       every resumed stream bit-identical to its uninterrupted control
       (the router's overlap-token assertion stays armed).
    6. compiled-program pins hold: 2 plain / 5 spec after mixed
       greedy+stochastic traffic.
    """
    import json
    import time

    import numpy as np

    t0 = time.perf_counter()
    bars = {}

    import jax.numpy as jnp

    # ---- bar 1: transform calibration + mask containment -------------
    rng = np.random.default_rng(11)
    vocab = 16
    logits_row = rng.normal(0.0, 1.5, vocab).astype(np.float32)
    n_draws = 4096
    sweep = [
        SamplingParams(temperature=1.0),
        SamplingParams(temperature=0.7, top_k=5),
        SamplingParams(temperature=1.3, top_p=0.8),
        SamplingParams(temperature=0.9, top_k=8, top_p=0.9, seed=3),
    ]
    calib_ok = True
    for sp in sweep:
        hi, lo = request_key("calib", sp.seed)
        samp = (jnp.full(n_draws, sp.temperature, jnp.float32),
                jnp.full(n_draws, sp.top_k, jnp.int32),
                jnp.full(n_draws, sp.top_p, jnp.float32),
                jnp.full(n_draws, hi, jnp.uint32),
                jnp.full(n_draws, lo, jnp.uint32))
        toks = np.asarray(sample_tokens(
            jnp.broadcast_to(jnp.asarray(logits_row), (n_draws, vocab)),
            jnp.arange(n_draws, dtype=jnp.int32), samp))
        ref = _filtered_probs(logits_row, sp)
        if np.any(ref[toks] <= 0.0):
            calib_ok = False  # a masked-out token was drawn
        counts = np.bincount(toks, minlength=vocab)
        fits, stat, crit = chi_square_fits(counts, np.maximum(ref, 1e-300))
        calib_ok = calib_ok and fits
    # greedy reduction: temperature=0 rows == argmax, bit-exact
    b = 8
    glogits = rng.normal(0.0, 2.0, (b, vocab)).astype(np.float32)
    samp0 = (jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.int32),
             jnp.ones(b, jnp.float32), jnp.arange(b, dtype=jnp.uint32),
             jnp.arange(b, dtype=jnp.uint32))
    greedy_ok = bool(np.array_equal(
        np.asarray(sample_tokens(jnp.asarray(glogits),
                                 jnp.arange(b, dtype=jnp.int32), samp0)),
        np.argmax(glogits, axis=-1)))
    bars["transform_calibrated"] = bool(calib_ok)
    bars["greedy_reduction_exact"] = greedy_ok

    # ---- bars 2+3+6: engine sweep over the spec selftest rig ---------
    from autodist_tpu.serve.spec import _SelftestRig

    rig = _SelftestRig()
    prompts = [rng.integers(1, 127, size=n).astype(np.int32).tolist()
               for n in (5, 9, 16, 21)]
    max_new = 8
    grid = [(0.8, 1.0, 2), (1.0, 0.9, 3), (1.5, 0.9, 4)]

    def stream(engine, prompt, rid, sp):
        return engine.generate(prompt, max_new, request_id=rid, sampling=sp)

    replay_ok = True
    seed_diverges = False
    for i, prompt in enumerate(prompts):
        sp = SamplingParams(temperature=1.0, seed=1)
        a = stream(rig.plain, prompt, f"replay-{i}", sp)
        bbb = stream(rig.plain, prompt, f"replay-{i}", sp)
        replay_ok = replay_ok and a == bbb
        c = stream(rig.plain, prompt, f"replay-{i}",
                   SamplingParams(temperature=1.0, seed=2))
        seed_diverges = seed_diverges or a != c
    bars["replay_bit_identical"] = replay_ok
    bars["seed_diverges"] = bool(seed_diverges)

    spec_ok = True
    temp0_ok = True
    pooled_plain = np.zeros(128, np.int64)
    pooled_spec = np.zeros(128, np.int64)
    for same_draft in (True, False):
        for temp, top_p, k in grid:
            eng = rig.spec_engine(spec_k=k, same_draft=same_draft)
            for i, prompt in enumerate(prompts):
                sp = SamplingParams(temperature=temp, top_p=top_p, seed=5)
                rid = f"spec-{same_draft}-{temp}-{top_p}-{k}-{i}"
                want = stream(rig.plain, prompt, rid, sp)
                got = stream(eng, prompt, rid, sp)
                spec_ok = spec_ok and want == got
                np.add.at(pooled_plain, np.asarray(want), 1)
                np.add.at(pooled_spec, np.asarray(got), 1)
            # temperature -> 0 reduces to today's greedy spec decode
            g_want = rig.plain.generate(prompts[0], max_new)
            g_got = eng.generate(prompts[0], max_new,
                                 request_id="g", sampling=SamplingParams())
            temp0_ok = temp0_ok and g_want == g_got
            spec_programs = eng.compiled_programs
    # Garbage draft: chaos-garbled proposals must not perturb the stream.
    from autodist_tpu.chaos import hooks as chaos_hooks

    eng = rig.spec_engine(spec_k=3, same_draft=False)
    sp = SamplingParams(temperature=1.1, top_p=0.9, seed=8)
    want = stream(rig.plain, prompts[2], "garbage-0", sp)
    chaos_hooks.install(chaos_hooks.SEAM_SERVE_DRAFT, lambda **_: "garbage")
    try:
        got = stream(eng, prompts[2], "garbage-0", sp)
    finally:
        chaos_hooks.uninstall(chaos_hooks.SEAM_SERVE_DRAFT)
    garbage_ok = want == got
    np.add.at(pooled_plain, np.asarray(want), 1)
    np.add.at(pooled_spec, np.asarray(got), 1)
    chi_ok, chi_stat, chi_crit = chi_square_fits(
        pooled_spec, np.maximum(pooled_plain, 1e-300))
    bars["spec_bit_identical_to_plain"] = spec_ok
    bars["spec_garbage_draft_bit_identical"] = bool(garbage_ok)
    bars["spec_vs_plain_chi_square"] = bool(chi_ok)
    bars["temp0_reduces_to_greedy_spec"] = temp0_ok
    bars["program_pins"] = (rig.plain.compiled_programs == 2
                            and spec_programs == 5)

    # ---- bar 4: prefix hit vs cold start --------------------------------
    from autodist_tpu.serve.server import _tiny_engine

    warm_engine, _, _ = _tiny_engine(prefix_cache=True)
    shared = rng.integers(1, 127, size=24).astype(np.int32).tolist()
    sp = SamplingParams(temperature=1.0, top_p=0.9, seed=4)
    warm_engine.generate(shared, max_new, request_id="warmup", sampling=sp)
    hit = warm_engine.generate(shared, max_new, request_id="probe",
                               sampling=sp)
    hits = warm_engine.prefix_stats()["hits"] if hasattr(
        warm_engine, "prefix_stats") else None
    cold_engine, _, _ = _tiny_engine(prefix_cache=True)
    cold = cold_engine.generate(shared, max_new, request_id="probe",
                                sampling=sp)
    bars["prefix_hit_vs_cold_bit_identical"] = hit == cold

    # ---- bar 5: mid-decode kills under stochastic traffic ---------------
    import asyncio
    import threading

    from autodist_tpu.serve.router import build_test_fleet
    from autodist_tpu.serve.server import async_generate

    router, control = build_test_fleet(n_replicas=3, spec_decode=True,
                                       spec_k=3)
    kill_grid = [SamplingParams(),  # greedy rides along
                 SamplingParams(temperature=0.8, seed=2),
                 SamplingParams(temperature=1.0, top_p=0.9, seed=3),
                 SamplingParams(temperature=1.4, top_k=40, seed=4)]
    n_req = 16
    kprompts = [rng.integers(1, 127, size=4 + (i % 9)).astype(np.int32)
                .tolist() for i in range(n_req)]
    kparams = [kill_grid[i % len(kill_grid)] for i in range(n_req)]
    rids = [f"kill-{i}" for i in range(n_req)]
    expected = [control.generate(kprompts[i], max_new, request_id=rids[i],
                                 sampling=kparams[i]) for i in range(n_req)]

    stop_evt = threading.Event()

    def killer():
        while not stop_evt.is_set():
            with router._lock:
                armed = [f for f in router._flights.values()
                         if f.replica_id == 1 and len(f.front.tokens) > 0]
            if armed:
                router.replicas[1].kill(
                    "chaos: kill_mid_stochastic_stream")
                return
            stop_evt.wait(0.002)

    async def run():
        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        try:
            return await asyncio.gather(*(
                async_generate(router, kprompts[i], max_new,
                               request_id=rids[i], sampling=kparams[i])
                for i in range(n_req)))
        finally:
            stop_evt.set()
            kt.join(timeout=5)

    router.start()
    try:
        results = asyncio.run(asyncio.wait_for(run(), timeout=300))
        failovers = int(router._c_failovers.value)
        mismatches = int(router._c_mismatch.value)
    finally:
        router.stop()
    streams_ok = all(list(results[i].tokens) == expected[i]
                     for i in range(n_req))
    bars["killed_streams_bit_identical"] = streams_ok
    bars["failovers"] = failovers
    bars["failover_mismatches"] = mismatches
    bars["kill_sweep_ok"] = streams_ok and failovers >= 1 and mismatches == 0

    ok = all(bool(v) for k, v in bars.items()
             if k not in ("failovers", "failover_mismatches"))
    print(json.dumps({"selftest_sampling": {
        **{k: (v if isinstance(v, (int, bool)) else bool(v))
           for k, v in bars.items()},
        "chi_square_stat": round(chi_stat, 2),
        "chi_square_crit": round(chi_crit, 2),
        "prefix_hits": hits,
        "elapsed_s": round(time.perf_counter() - t0, 1),
        "ok": ok,
    }}))
    return 0 if ok else 1
