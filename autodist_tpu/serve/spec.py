"""Speculative decode over the paged KV-cache: draft-propose /
target-verify with lossless greedy equivalence.

Decode is latency-bound: plain greedy serving pays ONE full target-model
program invocation per emitted token per round. Speculative decode (the
Leviathan/Chen draft-verify scheme, rendered onto this repo's paged
serving substrate) breaks that coupling:

1. a small **draft model** — same transformer family, compiled through
   the SAME ``Strategy -> StrategyCompiler -> GraphTransformer ->
   ShardingPlan`` pipeline over the SAME mesh as the target, with its own
   paged KV pool — autoregressively proposes ``k`` tokens per decoding
   slot (``k + 1`` invocations of its one compiled decode program: the
   extra invocation writes the k-th proposal's KV so a fully-accepted
   round leaves the draft cache complete);
2. the **target model** scores all ``k + 1`` positions (the pending
   token plus the k proposals) in ONE compiled batched program over the
   existing ``PagePool``/``PageTable`` state —
   ``models.transformer.forward_paged_verify``, the batched
   generalization of the chunked-prefill program (GSPMD's
   one-compiled-program discipline: verification is a single sharded
   program, never a per-token Python loop);
3. the **greedy accept/reject rule runs on device** inside that same
   program: ``accept[b]`` counts the leading proposals matching the
   target's own argmax at the same position, and the engine emits the
   accepted prefix plus the target's bonus/correction token — 1 to
   ``k + 1`` tokens per slot per round.

**Lossless by construction.** The verify program's query at offset ``j``
attends exactly the timeline plain greedy decode would have seen before
emitting token ``j`` (causal mask ``t <= position + j`` over the same
gathered pages), and every emitted token is the TARGET's own argmax on
that prefix — the draft only decides how many argmaxes one program
invocation gets to reveal. The emitted stream is therefore bit-identical
to plain greedy decode for ANY draft, including a garbage one
(``draft_divergence`` chaos class: acceptance collapses toward 0, output
stays correct, cadence degrades to ~1 token per round). Because the
stream is bit-identical, the router's exactly-once failover contract
(prefix resume, overlap token asserted bit-equal — docs/serving.md §
router) holds unchanged across plain and speculative replicas, and a
journal replay reproduces the same accepted stream.

**Page rollback.** The TARGET keeps the all-or-nothing admission
reservation (liveness is untouched: verification writes only into the
request's own reserved timeline, with positions past the static table
clamped to the scratch page in-kernel). The DRAFT's pool is best-effort:
tables grow incrementally (``PagePool.extend``) as the timeline
advances, and a rejection rewinds the draft table to the accepted
length + 1 (``PageTable.rewind`` + ``PagePool.reclaim``), so rejected
speculation never holds pages — pool accounting balances to zero after
any accept/reject history (``--selftest-spec`` pins it over 1k+ cycles).
Draft-pool exhaustion (or the ``page_exhaustion`` chaos window, which
the extend path rides) starves drafting, never admission: a slot whose
draft table cannot grow keeps serving at plain-decode cadence.

``python -m autodist_tpu.serve --selftest-spec`` is the CPU acceptance
proof: bit-identical streams across draft qualities and k in {1,2,4,8},
>= 2x fewer target-model invocations per emitted token on an
acceptance-friendly workload, and balanced page accounting.
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.chaos import hooks as chaos_hooks
from autodist_tpu.obs import recorder as obs_recorder
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve import prefix as serve_prefix
from autodist_tpu.serve import sampling as serve_sampling
from autodist_tpu.serve.engine import (
    _DECODE,
    _PREFILL,
    AdmissionDenied,
    DecodeModel,
    InferenceEngine,
    Slot,
    place_params,
    tree_bytes,
)

__all__ = ["SpecDecodeEngine", "build_draft_plan", "selftest_spec"]


def build_draft_plan(draft_params: Any, mesh, resource_spec=None,
                     strategy_builder=None):
    """Compile the draft model's :class:`~autodist_tpu.kernel.ShardingPlan`
    over the SAME mesh the target serves on — the second model rides the
    whole Strategy/StrategyCompiler/GraphTransformer stack, it just skips
    the chief/worker strategy-id handoff (the build is deterministic per
    (builder, model, spec), so every replica of a fleet derives the same
    draft plan locally; the target's plan still travels the normal
    handoff)."""
    from autodist_tpu.kernel import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    if resource_spec is None:
        resource_spec = ResourceSpec.from_local_devices()
    builder = strategy_builder or AllReduce()
    model_item = ModelItem.from_params(draft_params)
    strategy = builder.build(model_item, resource_spec)
    compiled = StrategyCompiler(model_item).compile(strategy)
    return GraphTransformer(compiled, model_item, mesh).transform()


class SpecDecodeEngine(InferenceEngine):
    """A paged :class:`InferenceEngine` with a draft model riding along.

    The target half is the plain engine unchanged (admission, chunked
    prefill, page pool, release). The speculative half adds: draft params
    in their own plan shardings over the shared mesh, a second (smaller)
    paged KV pool with incrementally-grown per-slot tables, two compiled
    draft programs (prefill chunk + decode step) and ONE compiled target
    verify program — :attr:`compiled_programs` pins exactly **5** after a
    mixed workload (target decode + target prefill + verify + draft
    decode + draft prefill).

    :meth:`step_many` replaces the one-token decode round with a spec
    round emitting 1..k+1 greedy-identical tokens per decoding slot; the
    inherited :meth:`step` (plain decode) remains available and shares
    all slot state, so the two cadences interleave correctly.
    """

    # prefill_step advances the draft's prefill beside every target chunk,
    # so no chunk may go out without it
    prefill_lookahead = False

    def __init__(
        self,
        params: Any,
        plan: Any,
        draft_params: Any,
        draft_plan: Any,
        decode_model: Optional[DecodeModel] = None,
        draft_decode_model: Optional[DecodeModel] = None,
        spec_k: int = 4,
        draft_n_pages: Optional[int] = None,
        apply_fn: Optional[Callable] = None,
        **engine_kwargs,
    ):
        for dm in (decode_model, draft_decode_model):
            if dm is not None and dm.cache_layout is not None \
                    and dm.cache_layout.window:
                raise serve_pages.CacheFeatureRefused(
                    "speculative verification over a window ring: a "
                    "rejected draft would have to take back ring entries "
                    "it overwrote (ROADMAP.md Queue 2)")
            if dm is not None and dm.cache_layout is not None \
                    and dm.cache_layout.latent:
                raise serve_pages.CacheFeatureRefused(
                    "speculative verification over a latent pool: no "
                    "program verifies several positions a row against "
                    "latent pages (ROADMAP.md Queue 2)")
            if dm is not None and dm.slot_state is not None:
                raise serve_pages.CacheFeatureRefused(
                    "speculative verification over per-slot recurrent "
                    "state: a rejected draft would have to roll the state "
                    "back (ROADMAP.md M4)")
        super().__init__(params, plan, apply_fn=apply_fn,
                         decode_model=decode_model, **engine_kwargs)
        if decode_model is None or decode_model.verify_paged is None:
            raise ValueError(
                "SpecDecodeEngine needs decode_model.verify_paged (the "
                "batched target verification forward — see "
                "models.transformer.forward_paged_verify)")
        if draft_decode_model is None:
            raise ValueError("SpecDecodeEngine needs a draft_decode_model")
        for fn in ("init_paged_cache", "prefill_chunk", "decode_paged"):
            if getattr(draft_decode_model, fn) is None:
                raise ValueError(
                    f"draft_decode_model lacks the paged surface ({fn})")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_k = int(spec_k)
        self.draft_decode_model = draft_decode_model
        self.draft_plan = draft_plan
        # Draft params land in THEIR plan's shardings (device view), in
        # the tree the draft's programs read — the same contract the
        # target params keep; a draft checkpoint restores through
        # InferenceEngine.restore_params with this plan (the
        # Saver.restore_subtree path), see SpecDecodeEngine.build.
        self.draft_params = place_params(
            draft_params, draft_plan, draft_decode_model)
        # Draft pool: its pages are cheap (the draft is small), so default
        # to the target pool's page count — enough to shadow every target
        # timeline. Best-effort by contract: exhaustion starves drafting,
        # never admission.
        dn = int(draft_n_pages) if draft_n_pages else self.pool.n_pages
        dn = max(dn, 2)
        if dn % self._data_degree:
            dn += self._data_degree - dn % self._data_degree
        draft_shaped = jax.eval_shape(
            lambda: draft_decode_model.init_paged_cache(1, self.page_len))
        self.draft_page_bytes = tree_bytes(draft_shaped)
        # Quantized draft pages ride the same detection the target pool
        # uses — spec losslessness under quantization holds because draft
        # and verify both read the SAME quantized page contents.
        self.draft_pool = serve_pages.build_pool(
            dn, self.page_len,
            quantized=isinstance(draft_shaped, dict)
            and "k_scale" in draft_shaped,
            bytes_per_page=float(self.draft_page_bytes))
        self._draft_cache_sh = self._cache_shardings(
            draft_decode_model.init_paged_cache, dn)
        self._draft_cache = jax.device_put(
            draft_decode_model.init_paged_cache(dn, self.page_len),
            self._draft_cache_sh)
        self._draft_tables: List[Optional[serve_pages.PageTable]] = (
            [None] * self.n_slots)
        self._draft_table_np = np.full(
            (self.n_slots, self.max_pages), serve_pages.SCRATCH_PAGE,
            np.int32)
        # Decode view of the draft tables: a slot's row appears here only
        # once it ENTERS decode — the spec round's k+1 draft feeds run
        # over the full batch at position 0 for non-decoding rows, and
        # writing those through a mid-prefill slot's REAL table would
        # permanently garble its prompt KV (the same prefilling-slots-
        # must-never-take-decode-writes contract the target keeps with
        # _decode_table_np).
        self._draft_decode_np = np.full(
            (self.n_slots, self.max_pages), serve_pages.SCRATCH_PAGE,
            np.int32)
        self._draft_prefill_fn = None
        self._draft_decode_fn = None
        self._verify_fn = None
        self._draft_copy_fn = None
        # Prefix sharing spans BOTH pools through ONE tree: each cached
        # block carries a target page and a draft page, so a cached
        # prefix skips the target prefill AND the draft shadow prefill
        # in lockstep (serve/prefix.py). Rebuild the cache the base
        # constructor made (target-only, still empty) over both pools.
        if self._prefix_cache is not None:
            self._prefix_cache = serve_prefix.build_prefix_cache(
                self.pool, self.page_len, draft_pool=self.draft_pool)
        # Spec accounting (cumulative; the batcher computes deltas for the
        # acceptance-rate gauges and the SLO tracker).
        self.verify_invocations = 0
        self.draft_invocations = 0
        self.spec_rounds = 0
        self.proposed_total = 0
        self.accepted_total = 0
        self.spec_tokens_emitted = 0
        self.draft_starved_total = 0
        # Per-temperature-bucket accept/propose counters (cumulative —
        # the batcher deltas them into the SLO tracker's per-bucket
        # acceptance windows; serve/sampling.py names the buckets).
        self.bucket_proposed: Dict[str, int] = {}
        self.bucket_accepted: Dict[str, int] = {}

    # ------------------------------------------------------------ construction
    @classmethod
    def build(
        cls,
        params: Any,
        draft_params: Any,
        decode_model: DecodeModel,
        draft_decode_model: DecodeModel,
        *,
        strategy_builder=None,
        resource_spec=None,
        mesh=None,
        checkpoint: Optional[str] = None,
        draft_checkpoint: Optional[str] = None,
        **engine_kwargs,
    ) -> "SpecDecodeEngine":
        """Standalone two-model construction over one shared mesh.

        Both models run capture -> strategy -> lower; ``checkpoint`` /
        ``draft_checkpoint`` restore each through the Saver's partial
        parallel sharded-read path (:meth:`InferenceEngine.restore_params`,
        which routes a full-train-state checkpoint through
        ``Saver.restore_subtree``)."""
        from autodist_tpu.kernel import GraphTransformer, build_mesh
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        if resource_spec is None and mesh is None:
            resource_spec = ResourceSpec.from_local_devices()
        if mesh is None:
            mesh = build_mesh(resource_spec)
        builder = strategy_builder or AllReduce()
        spec_rs = resource_spec or ResourceSpec.from_local_devices()
        model_item = ModelItem.from_params(params)
        strategy = builder.build(model_item, spec_rs)
        compiled = StrategyCompiler(model_item).compile(strategy)
        plan = GraphTransformer(compiled, model_item, mesh).transform()
        draft_plan = build_draft_plan(draft_params, mesh,
                                      resource_spec=spec_rs,
                                      strategy_builder=builder)
        if checkpoint is not None:
            params = cls.restore_params(checkpoint, params, plan)
        if draft_checkpoint is not None:
            draft_params = cls.restore_params(
                draft_checkpoint, draft_params, draft_plan)
        return cls(params, plan, draft_params, draft_plan,
                   decode_model=decode_model,
                   draft_decode_model=draft_decode_model,
                   resource_spec=resource_spec, **engine_kwargs)

    # --------------------------------------------------------------- programs
    def _compile_spec(self) -> None:
        dm, ddm = self.decode_model, self.draft_decode_model
        token_sh = NamedSharding(self.mesh, P())
        # One target verify program: donate-through the target cache with
        # its output sharding pinned to the canonical pool sharding, the
        # same drift-proofing the plain decode/prefill programs keep. Named
        # functions, so a device trace reads jit_serve_spec_verify etc.
        def serve_spec_verify(p, toks, pos, cache, tables, samp):
            return dm.verify_paged(
                self.plan.unpad_params(p), toks, pos, cache, tables,
                samp=samp)

        def serve_draft_prefill(p, tokens, start, length, cache, table):
            return ddm.prefill_chunk(
                self.draft_plan.unpad_params(p), tokens, start, length,
                cache, table)

        # The draft decode takes the SAME per-slot sampling arrays as the
        # target: proposing with the target's (request key, position)
        # Gumbel noise over its own distribution is the coupling that
        # keeps stochastic spec decode lossless AND high-acceptance
        # (serve/sampling.py — when draft == target the draws coincide).
        def serve_draft_decode(p, tokens, positions, cache, tables, samp):
            return ddm.decode_paged(
                self.draft_plan.unpad_params(p), tokens, positions, cache,
                tables, samp=samp)

        self._verify_fn = jax.jit(
            serve_spec_verify, donate_argnums=(3,),
            out_shardings=(token_sh, token_sh, self._cache_sh))
        self._draft_prefill_fn = jax.jit(
            serve_draft_prefill, donate_argnums=(4,),
            out_shardings=(token_sh, self._draft_cache_sh))
        self._draft_decode_fn = jax.jit(
            serve_draft_decode, donate_argnums=(3,),
            out_shardings=(token_sh, self._draft_cache_sh))

    @property
    def compiled_programs(self) -> int:
        """Real XLA cache entries across ALL serving programs — the spec
        engine's acceptance pin is exactly **5** after a mixed workload:
        target decode + target prefill chunk + target verify + draft
        decode + draft prefill chunk. Same raise-don't-guess discipline
        as the base engine."""
        total = super().compiled_programs
        for fn in (self._verify_fn, self._draft_prefill_fn,
                   self._draft_decode_fn):
            if fn is None:
                continue
            size = getattr(fn, "_cache_size", None)
            if size is None:
                raise RuntimeError(
                    "jax.jit lost _cache_size(); compiled_programs cannot "
                    "count real compilations — update the pin")
            total += int(size())
        return total

    @property
    def page_pool_bytes(self) -> int:
        """Target pool + draft pool device bytes: BOTH static pools are
        tenants of the analyzer's HBM budget (SLM001/002)."""
        return (super().page_pool_bytes
                + int(self.draft_page_bytes) * self.draft_pool.n_pages)

    # --------------------------------------------------------------- admission
    def admit(self, prompt: np.ndarray, max_new_tokens: int,
              request_id: str = "",
              sampling: Optional[serve_sampling.SamplingParams] = None):
        got = super().admit(prompt, max_new_tokens, request_id=request_id,
                            sampling=sampling)
        if isinstance(got, AdmissionDenied):
            return got
        idx = got.index
        prompt_len = len(self._prompts[idx])
        # Draft reservation is BEST-EFFORT and incremental: cover the
        # prompt + the pending token's slot now, grow per spec round. A
        # starved draft never blocks admission — the slot just serves at
        # plain-decode cadence (acceptance 0 against an all-scratch draft
        # timeline).
        table = self._build_draft_table(idx, prompt_len)
        if table is None:
            self._draft_tables[idx] = None
            self._draft_table_np[idx] = serve_pages.SCRATCH_PAGE
            self.draft_starved_total += 1
        else:
            self._draft_tables[idx] = table
            self._draft_table_np[idx] = table.padded(self.max_pages)
        self._draft_decode_np[idx] = serve_pages.SCRATCH_PAGE
        return got

    def _build_draft_table(
            self, idx: int, prompt_len: int
    ) -> Optional[serve_pages.PageTable]:
        """The draft-side page reservation for a freshly admitted slot.

        With sharing off: one best-effort ``prompt_len + 1`` allocation.
        With sharing on, the tree's leased nodes carry draft pages too —
        the draft table maps the SAME shared prefix and allocates only
        the suffix, with the draft-side COW mirroring the target's
        frontier copy. The draft shadow prefill starts at the target's
        ``_prefill_start``, so when any leased block lacks a draft page
        (its inserter was draft-starved) the draft timeline cannot be
        made whole — the slot degrades to plain cadence (starved), never
        to garbage-KV proposals being silently trusted (verification
        would catch them anyway; this just keeps acceptance honest)."""
        lease = self._leases[idx]
        if lease is None:
            return self.draft_pool.alloc(prompt_len + 1)
        n_full = len(lease.nodes)
        start = int(self._prefill_start[idx])
        tail_len = start - n_full * self.page_len
        draft_shared = [nd.draft_page for nd in lease.nodes]
        tail = lease.tail_node
        sharable = all(p is not None for p in draft_shared) and (
            tail_len == 0 or (tail is not None
                              and tail.draft_page is not None))
        if not sharable:
            return None
        table = self._draft_alloc_with_evict(
            prompt_len + 1 - n_full * self.page_len)
        if table is None:
            return None
        if tail_len:
            self._cow_draft_page(tail.draft_page, table.pages[0])
        table.pages[:0] = draft_shared
        return table

    def _draft_alloc_with_evict(
            self, n_tokens: int) -> Optional[serve_pages.PageTable]:
        """Draft-pool allocation with tree eviction retry: cold cached
        prefixes hold draft pages too, so draft pressure reclaims LRU
        leaves (freeing BOTH pools' pages) before starving the draft."""
        table = self.draft_pool.alloc(n_tokens)
        need = serve_pages.pages_for_tokens(n_tokens, self.page_len)
        while table is None and self._prefix_cache is not None:
            if self._prefix_cache.evict(need) == 0:
                return None
            table = self.draft_pool.alloc(n_tokens)
        return table

    def _cow_draft_page(self, src_page: int, dst_page: int) -> None:
        """The draft cache's copy-on-write frontier copy — same program
        shape as the target's (engine._make_page_copy_fn), over the
        draft pool's arrays."""
        if self._draft_copy_fn is None:
            self._draft_copy_fn = self._make_page_copy_fn(
                self.draft_pool.n_pages, self._draft_cache_sh)
        with obs_spans.span("serve.cow_copy_draft", src=int(src_page),
                            dst=int(dst_page)):
            self._draft_cache = self._draft_copy_fn(
                self._draft_cache, jnp.int32(src_page), jnp.int32(dst_page))

    def _insert_prefix(self, idx: int, prompt: np.ndarray) -> None:
        """Adopt target AND draft pages as one node per novel block —
        the draft side only when this slot's draft table actually holds
        the prompt's KV (a starved draft adopts target-only nodes, which
        later admissions then cannot draft-share)."""
        draft_table = self._draft_tables[idx]
        self._prefix_cache.insert(
            prompt, self._tables[idx].pages, self._leases[idx],
            draft_pages=(draft_table.pages if draft_table is not None
                         else None))

    def _sync_draft_row(self, idx: int) -> None:
        """Refresh both table views after the slot's draft table changed
        (extend/rewind); the decode view follows only while the slot is
        actually decoding."""
        table = self._draft_tables[idx]
        row = (table.padded(self.max_pages) if table is not None
               else serve_pages.SCRATCH_PAGE)
        self._draft_table_np[idx] = row
        if self._phase[idx] == _DECODE:
            self._draft_decode_np[idx] = row

    def release(self, slot: Slot) -> None:
        idx = slot.index
        table = self._draft_tables[idx]
        lease = self._leases[idx]
        if table is not None:
            if lease is not None:
                # Tree-owned draft pages only drop their (shared) node
                # refcount — super().release() decrements it once for
                # both pools; exclusive draft pages recycle now.
                shared = {nd.draft_page for nd in lease.nodes
                          if nd.draft_page is not None}
                exclusive = [p for p in table.pages if p not in shared]
                if exclusive:
                    self.draft_pool.reclaim(exclusive)
                table.pages = []
            else:
                self.draft_pool.release(table)
        self._draft_tables[idx] = None
        self._draft_table_np[idx] = serve_pages.SCRATCH_PAGE
        self._draft_decode_np[idx] = serve_pages.SCRATCH_PAGE
        super().release(slot)

    # ----------------------------------------------------------------- prefill
    def prefill_step(self, slot: Slot) -> Optional[int]:
        """Advance BOTH prefills one chunk: the draft shadows the target's
        chunking exactly (same start, same window), writing the prompt's
        KV through its own table; its next-token output is discarded —
        the first generated token is the target's, as in plain serving."""
        idx = slot.index
        if (self._phase[idx] == _PREFILL
                and self._draft_tables[idx] is not None):
            if self._draft_prefill_fn is None:
                self._compile_spec()
            prompt = self._prompts[idx]
            start = int(self._prefill_pos[idx])
            c = self.prefill_chunk
            chunk = np.zeros((1, c), np.int32)
            valid = prompt[start:start + c]
            chunk[0, : len(valid)] = valid
            self.draft_invocations += 1
            _, self._draft_cache = self._draft_prefill_fn(
                self.draft_params, jnp.asarray(chunk), np.int32(start),
                np.int32(len(prompt)), self._draft_cache,
                jnp.asarray(self._draft_table_np[idx]))
        first = super().prefill_step(slot)
        if first is not None:
            # The slot just entered decode: its draft table joins the
            # decode view (until now the spec rounds rode its row against
            # scratch, protecting the half-prefilled draft prompt KV).
            self._draft_decode_np[idx] = self._draft_table_np[idx]
        return first

    # -------------------------------------------------------------- spec round
    def step_many(self) -> Dict[Slot, List[int]]:
        """One speculative round over the full slot batch.

        draft k+1 invocations -> ONE target verify -> on-device greedy
        accept -> host emits 1..k+1 tokens per decoding slot and rewinds
        the draft's page reservation to the accepted timeline. Idle and
        prefilling rows ride both programs against scratch, as in plain
        decode.
        """
        out: Dict[Slot, List[int]] = {}
        # Same chaos seam as the plain decode step: engine/replica death
        # schedules target spec replicas identically.
        chaos_hooks.fire(chaos_hooks.SEAM_SERVE_STEP,
                         active=self.active_slots, host=self.chaos_host)
        decoding = np.flatnonzero(self._phase == _DECODE)
        if not len(decoding):
            return out
        if self._verify_fn is None:
            self._compile_spec()
        k = self.spec_k
        # Best-effort draft growth: cover positions pos..pos+k (the k+1
        # feeds below). Failure degrades that slot's proposals to garbage
        # (scratch reads) — acceptance drops, correctness doesn't.
        for i in decoding:
            idx = int(i)
            table = self._draft_tables[idx]
            if table is None:
                continue
            # Clamp at the static ceiling: a draft window hanging off the
            # end of the timeline must not grow the table past max_pages
            # (padded() would refuse the row) — the overhanging feeds
            # land in pad/scratch instead, exactly like the target's
            # verify writes near the ceiling.
            need = min(int(self._lengths[idx]) + k + 1, self.max_len)
            if table.capacity < need:
                if self.draft_pool.extend(table, need):
                    self._sync_draft_row(idx)
                else:
                    self.draft_starved_total += 1
        positions = self._lengths.copy()
        pos_dev = jnp.asarray(positions)
        draft_tables = jnp.asarray(self._draft_decode_np)
        cur = jnp.asarray(self._last_token)
        samp = tuple(map(jnp.asarray, self._mirror(("samp", None))))
        proposals = []
        for j in range(k + 1):
            # k+1 invocations of the ONE draft decode program: feed j
            # writes its token's KV at pos+j and proposes the next; the
            # last feed only completes the draft cache for the
            # all-accepted case (its proposal is discarded). The draft
            # samples with the target's per-slot keys at the same
            # counters — the coupling that makes stochastic acceptance
            # track draft quality.
            self.draft_invocations += 1
            cur, self._draft_cache = self._draft_decode_fn(
                self.draft_params, cur, pos_dev + j, self._draft_cache,
                draft_tables, samp)
            if j < k:
                proposals.append(cur)
        # Chaos seam: a draft_divergence window garbles the PROPOSALS the
        # verifier sees (deterministic offset — no RNG in the hot loop).
        # The system's contract under it: acceptance ~0, output still
        # bit-identical greedy, cadence bounded at ~1 token/round.
        if chaos_hooks.fire(chaos_hooks.SEAM_SERVE_DRAFT,
                            host=self.chaos_host) == "garbage":
            proposals = [p + np.int32(j + 1)
                         for j, p in enumerate(proposals)]
        tokens_mat = jnp.stack([jnp.asarray(self._last_token)] + proposals,
                               axis=1)                          # [B, K+1]
        rids = [self._request_ids[int(i)] for i in decoding[:16]
                if self._request_ids[int(i)]]
        self.verify_invocations += 1
        with obs_spans.span("serve.spec_verify", active=int(len(decoding)),
                            k=k, request_ids=rids):
            acc, out_tok, self._cache = self._verify_fn(
                self.params, tokens_mat, pos_dev, self._cache,
                jnp.asarray(self._decode_table_np), samp)
            acc.copy_to_host_async()
            out_tok.copy_to_host_async()
            with obs_spans.span("wait.tokens", program="spec_verify"):
                jax.block_until_ready(out_tok)
            acc, out_tok = (np.asarray(v)
                            for v in jax.device_get((acc, out_tok)))
        self.spec_rounds += 1
        for i in decoding:
            idx = int(i)
            m = int(acc[idx])
            emit = [int(t) for t in out_tok[idx, : m + 1]]
            # Accepted prefix + bonus token advance the slot; the k - m
            # rejected positions' target KV is garbage that the next
            # round's write-then-mask order can never read (the same
            # future-slot contract chunked prefill relies on).
            self._set_row(idx, lengths=int(positions[idx]) + m + 1,
                          tokens=emit[-1])
            out[Slot(idx)] = emit
            self.proposed_total += k
            self.accepted_total += m
            self.spec_tokens_emitted += len(emit)
            bucket = serve_sampling.temperature_bucket(
                float(self._samp["temperature"][idx]))
            self.bucket_proposed[bucket] = (
                self.bucket_proposed.get(bucket, 0) + k)
            self.bucket_accepted[bucket] = (
                self.bucket_accepted.get(bucket, 0) + m)
            # Rollback: rewind the draft reservation to the accepted
            # timeline (+1 pending slot). A rejection at a page boundary
            # frees pages back to the pool immediately — speculation
            # never holds pages it no longer covers.
            table = self._draft_tables[idx]
            if table is not None:
                if self.draft_pool.rewind(
                        table, int(self._lengths[idx]) + 1):
                    self._sync_draft_row(idx)
        self._decode_step_count += 1
        if self._decode_step_count % 64 == 1:
            obs_recorder.record_step(
                surface="serve", event="decode",
                decode_steps=self._decode_step_count,
                active_slots=len(out),
                spec_rounds=self.spec_rounds,
                acceptance_rate=round(self.acceptance_rate, 4),
                pool_utilization=round(self.page_utilization, 4))
        return out

    # -------------------------------------------------------------- accounting
    @property
    def acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens, cumulative (0..1)."""
        return self.accepted_total / max(self.proposed_total, 1)

    @property
    def target_invocations(self) -> int:
        """Target-model program invocations spent on decode: plain decode
        steps + verify rounds — the numerator of the per-token acceptance
        bar (prefill is excluded on both sides; it is identical work)."""
        return self.decode_invocations + self.verify_invocations

    def spec_stats(self) -> Dict[str, Any]:
        """Cumulative speculative-decode counters — the batcher polls this
        per tick for the ``serve_spec_*`` gauges and the SLO tracker's
        ``acceptance_rate``; ``bench``/selftests read it directly."""
        return {
            "k": self.spec_k,
            "rounds": self.spec_rounds,
            "proposed": self.proposed_total,
            "accepted": self.accepted_total,
            "emitted": self.spec_tokens_emitted,
            "acceptance_rate": self.acceptance_rate,
            "tokens_per_round": (self.spec_tokens_emitted
                                 / max(self.spec_rounds, 1)),
            "verify_invocations": self.verify_invocations,
            "draft_invocations": self.draft_invocations,
            "target_decode_invocations": self.decode_invocations,
            "draft_starved": self.draft_starved_total,
            "draft_pool_free_pages": self.draft_pool.free_pages,
            "draft_pool_used_pages": self.draft_pool.used_pages,
            # Acceptance split by temperature bucket (serve/sampling.py):
            # stochastic rounds accept differently than greedy ones, and
            # the SLO report attributes the split.
            "by_temperature": {
                b: {"proposed": self.bucket_proposed.get(b, 0),
                    "accepted": self.bucket_accepted.get(b, 0),
                    "acceptance_rate": (
                        self.bucket_accepted.get(b, 0)
                        / max(self.bucket_proposed.get(b, 0), 1))}
                for b in sorted(self.bucket_proposed)},
        }


# ------------------------------------------------------------------ selftest
def _selftest_cfgs():
    import jax.numpy as jnp_

    from autodist_tpu.models.transformer import TransformerConfig

    # vocab 128 keeps every mock token in-vocab (the same bit-identity
    # hygiene the router selftest keeps); fp32 so CPU argmaxes are exact.
    target = TransformerConfig(
        vocab_size=128, num_layers=2, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=64, causal=True, dtype=jnp_.float32)
    draft = TransformerConfig(
        vocab_size=128, num_layers=1, d_model=32, num_heads=2, d_ff=64,
        max_seq_len=64, causal=True, dtype=jnp_.float32)
    return target, draft


class _SelftestRig:
    """One target checkpoint + plan, two draft options, spec engines per
    k on demand — the compile-once substrate of the selftest (and of
    ``tests/test_serve_spec.py``)."""

    def __init__(self, n_pages: int = 49, draft_n_pages: int = 25):
        from autodist_tpu.models.transformer import decode_model, init_params

        self.target_cfg, self.draft_cfg = _selftest_cfgs()
        self._decode_model = decode_model
        self.params = init_params(jax.random.PRNGKey(0), self.target_cfg)
        self.n_pages, self.draft_n_pages = n_pages, draft_n_pages
        self.plain = InferenceEngine.build(
            self.params, decode_model=decode_model(self.target_cfg),
            n_slots=8, page_len=8, n_pages=n_pages, prefill_chunk=8)
        self.draft_params = init_params(jax.random.PRNGKey(7), self.draft_cfg)
        self._draft_plans = {
            # same_draft=True: the target IS the draft — the acceptance-
            # friendly workload (acceptance ~1) of the >=2x invocation
            # bar; False: a 1-layer different-seed draft with real
            # rejections on most rounds.
            True: self.plain.plan,
            False: build_draft_plan(self.draft_params, self.plain.plan.mesh),
        }

    def spec_engine(self, spec_k: int, same_draft: bool) -> SpecDecodeEngine:
        dm = self._decode_model
        draft_params = self.params if same_draft else self.draft_params
        ddm = dm(self.target_cfg if same_draft else self.draft_cfg)
        return SpecDecodeEngine(
            self.params, self.plain.plan, draft_params,
            self._draft_plans[same_draft],
            decode_model=dm(self.target_cfg), draft_decode_model=ddm,
            spec_k=spec_k, draft_n_pages=self.draft_n_pages,
            n_slots=8, page_len=8, n_pages=self.n_pages, prefill_chunk=8)


def _pools_balanced(engine: SpecDecodeEngine) -> bool:
    return (engine.pool.used_pages == 0
            and engine.pool.free_pages == engine.pool.usable_pages
            and engine.draft_pool.used_pages == 0
            and engine.draft_pool.free_pages == engine.draft_pool.usable_pages)


def selftest_spec(max_new: int = 12, seed: int = 0) -> int:
    """The ``--selftest-spec`` acceptance proof; returns an exit code.

    Bars (ISSUE 15):

    - **lossless greedy**: for seeded prompts across page/chunk
      boundaries and k in {1, 2, 4, 8}, the spec-decode stream is
      bit-identical to plain greedy — with BOTH an acceptance-friendly
      draft (the target itself) and a genuinely different 1-layer draft
      (real rejections on every round), and through the continuous
      batcher with mid-batch joins;
    - **>= 2x fewer target-model program invocations per emitted token**
      at the acceptance-friendly workload (k=4: ~0.2 invocations/token
      vs plain greedy's 1.0);
    - **balanced page accounting**: target AND draft pools return to
      zero used pages after the whole run, including >= 1000
      accept/reject rounds against the rejecting draft — a rejection
      never leaks pages.
    """
    from autodist_tpu.serve.batcher import ContinuousBatcher, RequestState

    rng = np.random.default_rng(seed)
    t0 = time.monotonic()

    # Prompt set: short, page-crossing (8 = page_len), chunk-boundary
    # (16), multi-chunk (21), and one whose prompt+max_new crosses the
    # last page of its reservation.
    prompts = [
        np.array([5, 17, 3, 88, 2], np.int32),
        rng.integers(1, 127, size=8).astype(np.int32),
        rng.integers(1, 127, size=16).astype(np.int32),
        rng.integers(1, 127, size=21).astype(np.int32),
        rng.integers(1, 127, size=11).astype(np.int32),
    ]

    # ---- lossless-greedy sweep over k and draft quality.
    parity = {}
    invocations_per_token = None
    accept_friendly_rate = None
    divergent_rate = None
    spec_pools_ok = True
    rig = _SelftestRig()
    expected = [rig.plain.generate(p, max_new) for p in prompts]
    plain_invocations_per_token = (
        rig.plain.decode_invocations
        / max(sum(len(e) for e in expected), 1))  # == (max_new-1)/max_new
    for k in (1, 2, 4, 8):
        spec = rig.spec_engine(k, same_draft=True)
        got = [spec.generate(p, max_new) for p in prompts]
        parity[f"same_draft_k{k}"] = bool(got == expected)
        spec_pools_ok = spec_pools_ok and _pools_balanced(spec)
        if k == 4:
            toks = sum(len(g) for g in got)
            invocations_per_token = spec.target_invocations / max(toks, 1)
            accept_friendly_rate = spec.acceptance_rate
    for k in (2, 4):
        spec = rig.spec_engine(k, same_draft=False)
        got = [spec.generate(p, max_new) for p in prompts]
        parity[f"divergent_draft_k{k}"] = bool(got == expected)
        spec_pools_ok = spec_pools_ok and _pools_balanced(spec)
        if k == 4:
            divergent_rate = spec.acceptance_rate

    # ---- batcher integration: concurrent mixed load through the spec
    # engine (mid-batch joins, chunked prefill interleaving, multi-token
    # retirement), streams bit-identical to plain greedy.
    spec = rig.spec_engine(4, same_draft=True)
    batcher = ContinuousBatcher(spec, max_queue=64).start()
    reqs = [batcher.submit(p, max_new) for p in prompts * 4]
    states = [r.wait(120.0).state for r in reqs]
    batcher.stop(drain=False)
    batch_done = all(s is RequestState.DONE for s in states)
    batch_parity = all(
        r.tokens == expected[i % len(prompts)] for i, r in enumerate(reqs))
    programs = spec.compiled_programs
    spec_pools_ok = spec_pools_ok and _pools_balanced(spec)

    # ---- 1000+ accept/reject cycles against the rejecting draft (one
    # cycle = one slot's accept/reject decision in one verify round),
    # concurrent through the batcher: page accounting must balance to
    # zero leaked pages in BOTH pools afterwards.
    rejecter = rig.spec_engine(4, same_draft=False)
    soak_batcher = ContinuousBatcher(rejecter, max_queue=256).start()
    soak_ok = True
    while rejecter.proposed_total // rejecter.spec_k < 1000:
        wave = [soak_batcher.submit(prompts[i % len(prompts)], max_new)
                for i in range(48)]
        soak_ok = soak_ok and all(
            r.wait(120.0).state is RequestState.DONE for r in wave)
        soak_ok = soak_ok and all(
            r.tokens == expected[i % len(prompts)]
            for i, r in enumerate(wave))
        if not soak_ok:
            break
    soak_batcher.stop(drain=False)
    soak_cycles = rejecter.proposed_total // rejecter.spec_k
    soak_balanced = soak_ok and _pools_balanced(rejecter)

    ok = (
        all(parity.values())
        and batch_done and batch_parity
        and invocations_per_token is not None
        and invocations_per_token <= 0.5 * plain_invocations_per_token
        and programs == 5
        and spec_pools_ok and soak_balanced
    )
    line = {
        "selftest": "autodist_tpu.serve.spec",
        "ok": bool(ok),
        "parity": parity,
        "batch_done": bool(batch_done),
        "batch_parity": bool(batch_parity),
        "plain_target_invocations_per_token": round(
            plain_invocations_per_token, 4),
        "spec_target_invocations_per_token": round(
            invocations_per_token, 4),
        "invocation_reduction_x": round(
            plain_invocations_per_token / max(invocations_per_token, 1e-9),
            2),
        "acceptance_rate_friendly": round(accept_friendly_rate or 0.0, 4),
        "acceptance_rate_divergent": round(divergent_rate or 0.0, 4),
        "programs_compiled": programs,
        "soak_cycles": soak_cycles,
        "soak_pages_balanced": bool(soak_balanced),
        "pools_balanced": bool(spec_pools_ok),
        "duration_s": round(time.monotonic() - t0, 1),
        "device": jax.devices()[0].platform,
    }
    print(json.dumps(line))
    return 0 if ok else 1
