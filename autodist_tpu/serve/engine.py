"""Sharded inference engine: one-shot apply + paged KV-cache decode.

The engine is the inference counterpart of
:class:`~autodist_tpu.kernel.DistributedTrainStep`: it consumes the SAME
lowering artifacts — a :class:`~autodist_tpu.kernel.ShardingPlan` produced by
``StrategyCompiler`` + ``GraphTransformer`` from any strategy builder — so a
strategy searched for training reuses directly for serving (the Automap
argument, arxiv 2112.02958: the search substrate is workload-agnostic).
Params land in their plan shardings (optionally restored straight from a
``checkpoint/saver.py`` checkpoint via the partial, parallel sharded-read
path), batches shard over the mesh data axis, and GSPMD inserts the
collectives for model-sharded parameters exactly as in training.

Decode state is a **paged KV-cache** (the vLLM rendering of GSPMD-style
static annotations, arXiv 2105.04663): ONE fixed pool of device pages
(the zoo transformer's ``[n_pages, page_len, heads * head_dim]`` a layer, or
whatever leaves the model's ``init_paged_cache`` gives, the page dim where
its :class:`~autodist_tpu.serve.pages.CacheLayout` says) sized from
``ResourceSpec`` HBM headroom and donated through the compiled steps, with
per-request page tables (host int32 lists, ``serve/pages.py`` — the one
allocator home) padded to a static width. The engine compiles exactly TWO
serving programs regardless of the request-length mix: one decode step over
every slot row, and one fixed-size prefill chunk — long prompts prefill
chunk by chunk, interleaved with decode ticks by the batcher, so a 4k-token
prompt never stalls in-flight decodes. Admission reserves, all-or-nothing,
the pages the whole ``prompt + max_new`` timeline needs under the model's
layout (one per ``page_len`` positions; or, over a window ring, the ring's
pages and one summary page per ``page_len**2`` positions); retirement
recycles them in the same tick.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.chaos import hooks as chaos_hooks
from autodist_tpu.kernel import GraphTransformer, ShardingPlan, build_mesh, data_axis
from autodist_tpu.model_item import ModelItem
from autodist_tpu.obs import recorder as obs_recorder
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.serve import pages as serve_pages
from autodist_tpu.serve import prefix as serve_prefix
from autodist_tpu.serve import sampling as serve_sampling
from autodist_tpu.utils import logging

#: Slot phases (host bookkeeping; single scheduler-thread writer).
_FREE, _PREFILL, _DECODE = 0, 1, 2
#: The per-slot sampling arrays, in the order the programs take them.
_SAMP_KEYS = ("temperature", "top_k", "top_p", "key_hi", "key_lo")


class EngineDeadError(RuntimeError):
    """The inference engine can no longer decode (device lost, fatal
    runtime error, or an injected chaos fault). The batcher catches this
    specifically and sheds all load with typed REJECTED results instead
    of hanging clients (docs/chaos.md)."""


@dataclass
class DecodeModel:
    """Model adapter for autoregressive decode — pure functions, one config.

    The paged surface (all three required):

    - ``init_paged_cache(n_pages, page_len) -> cache`` pytree whose
      leaves carry the page dim at ``cache_layout.page_axis`` (dim 0, a
      leaf a layer, where the model states no layout; the engine shards
      it over the mesh data axis). A model whose cache holds int8 pages
      (``*_scale`` leaves beside them) also takes ``quantized=False`` and
      gives the cache it would hold unquantised: what the engine prices
      the capacity win against;
    - ``prefill_chunk(params, tokens [1,C], start, length, cache,
      page_table [P]) -> (next_token [1], cache)`` — writes prompt
      positions ``[start, start+C)`` through the page table; the returned
      token is the argmax at ``length - 1`` (used on the final chunk);
    - ``decode_paged(params, tokens [B], positions [B], cache,
      page_tables [B,P]) -> (next_token [B], cache)`` with
      ``B == n_slots``.

    ``eos_id``: generation stops when emitted (None = length-only);
    ``max_len``: the model's positional ceiling.

    ``cache_layout`` (:class:`~autodist_tpu.serve.pages.CacheLayout`): what
    the model states about a cache that is not one page per ``page_len``
    positions: its page length, a window ring with chunk summaries behind
    it, the prefill chunk it asks for and the constraint any chunk must
    keep, the page dim of its leaves. None is the plain paged timeline.
    The engine sizes tables and reservations from it; it refuses prefix
    sharing, int8 pages and speculative verification over a ring. A model
    whose page has no head axis (one row a position for all heads, key
    and value the same bytes) states ``latent=True`` and a page length of
    its own: its one leaf a layer is priced, sharded and copied by the
    page dim like any other, and int8 pages and speculative verification
    over it are refused.

    ``step_facts``: names of int32 scalars that only the device knows and
    that both programs append to the token vector they return
    (``prefill_chunk`` then returns ``[1 + F]``, ``decode_paged`` ``[B +
    F]``): a model that holds a share of its experts reports the pairs
    that fell on them and how many of them were hit. They reach the host
    with the fetch that brings the tokens (no second round trip): the
    engine stamps them on the ``serve.decode_step`` / ``serve.prefill_chunk``
    span of the program that produced them and adds them up
    (``InferenceEngine.fact_totals``). ``steps_fact`` names the count of
    decode steps they were added up over (``moe_steps``), published beside
    them. A row that is not decoding carries position 0 and an all-scratch
    table in ``decode_paged``: a model whose facts count rows has that to
    tell them by.

    ``serving_params``: a pure function from the caller's parameter tree
    to the tree the paged programs read (``prefill_chunk``,
    ``decode_paged``, ``verify_paged``), which the engine applies once and
    places in place of the caller's (a one-shot ``apply_fn`` reads the
    same tree). What a model may put there leaves its arithmetic alone: a
    leaf whose every use in those programs is behind a cast to its compute
    dtype, cast once (the programs then read half the bytes and convert
    nothing), or a leaf held in the form its use reads. None places the
    caller's leaves as given.

    ``slot_state``: ``slot_state(n_slots) -> tree of zeros``, what a model
    carries for each slot beside its pages (a recurrent layer's state and
    the tail of its convolution), a leaf per layer with the slot dim first.
    The engine places it once and both programs take it and give it back
    donated: ``prefill_chunk(..., samp=, *, state, slot)`` and
    ``decode_paged(..., samp=, *, state)`` then return ``(tokens, cache,
    state)``. A chunk writes its row's slot and starts from zeros where it
    starts the prompt (a slot's state is never cleared on the host); a
    decode step updates the rows it decodes and leaves every other row's
    state as it was (position 0 tells them). The engine prices the state
    before the pool takes its share of the headroom, and refuses prefix
    sharing, int8 pages and speculative verification over it: sharing a
    prefix would need the state at the prefix's end, and a rejected draft
    would need the state rolled back (ROADMAP.md M4).

    ``autodist_tpu.models.transformer.decode_model(cfg)`` builds one for
    the zoo transformer; any model matching the contract serves the same
    way.
    """

    init_paged_cache: Callable[..., Any]
    prefill_chunk: Callable[..., Tuple[Any, Any]]
    decode_paged: Callable[..., Tuple[Any, Any]]
    # Speculative-decode verification surface (serve/spec.py): one batched
    # multi-position forward ``(params, tokens [B, K+1], positions [B],
    # cache, page_tables [B, P]) -> (accept [B], out_tokens [B, K+1],
    # cache)`` with the greedy accept/reject computed ON DEVICE. Optional:
    # only the SpecDecodeEngine requires it.
    verify_paged: Optional[Callable[..., Tuple[Any, Any, Any]]] = None
    eos_id: Optional[int] = None
    max_len: Optional[int] = None
    cache_layout: Optional[serve_pages.CacheLayout] = None
    step_facts: Tuple[str, ...] = ()
    steps_fact: Optional[str] = None
    serving_params: Optional[Callable[[Any], Any]] = None
    slot_state: Optional[Callable[[int], Any]] = None


def tree_bytes(tree: Any) -> int:
    """Bytes of a pytree's leaves (arrays or shapes)."""
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def place_params(params: Any, plan: ShardingPlan,
                 decode_model: Optional[DecodeModel] = None) -> Any:
    """The parameters the engine's programs read, on the device in the
    plan's shardings: the model's ``serving_params`` of ``params`` where
    it states one, else ``params`` as given. Storage view (pad-and-mask
    plans store padded; the programs unpad under the trace) and
    ``device_view``: serving ignores host-offload markers, params stay
    HBM-resident (offload is a training-memory bargain inference has no
    reason to pay per step). The model's function runs as one program."""
    if decode_model is not None and decode_model.serving_params is not None:
        params = jax.jit(decode_model.serving_params)(params)
    stored = plan.pad_params(params)
    return jax.device_put(stored, plan.params_shardings(
        jax.eval_shape(lambda: stored), device_view=True))


@dataclass(frozen=True)
class Slot:
    """One occupied decode row (paged engine) — index into the static
    decode batch."""

    index: int


@dataclass(frozen=True)
class AdmissionDenied:
    """Typed admission outcome: WHY a request was not placed, and whether
    waiting can ever help. ``retryable=True`` (pool exhausted, no free
    row, chaos defer) means retirement will free resources — the batcher
    keeps the request queued; ``retryable=False`` (over the engine's
    static ceiling) means the request can NEVER be placed — the batcher
    finishes it typed REJECTED instead of head-blocking the FIFO."""

    reason: str
    retryable: bool


class InferenceEngine:
    """Serve a (possibly sharded) model: ``infer`` for one-shot batches,
    ``admit``/``prefill_step``/``step``/``release`` for paged
    continuous-batching decode.

    The surface is deliberately scheduler-free: the
    :class:`~autodist_tpu.serve.batcher.ContinuousBatcher` owns queueing,
    deadlines, prefill/decode interleaving and retirement policy; the
    engine owns device state. All decode-state methods must be called from
    one scheduler thread (they mutate host-side slot tables without
    locking — single-writer by contract; the page pool itself is locked so
    accounting reads from other threads stay coherent).

    Exactly two programs compile (``compiled_programs`` counts them): the
    decode step over all ``n_slots`` rows and the fixed-``prefill_chunk``
    prefill — admission, chunking, retirement and any request-length mix
    never recompile anything, whatever layout the model's cache has (a
    window closing over a ring is the same two programs: they read which
    entries a query sees off ``positions``).

    A row mid-prefill has its next chunk dispatched right behind each
    decode step (``prefill_lookahead``), before the host waits for that
    step's tokens: the device then goes from the decode step into the chunk
    while the host emits, keeps its books and prepares the next tick, and a
    tick that carries a chunk is as long as its two programs run. The
    programs reach the device in the order they always did (decode step,
    chunk, decode step, ...); only a prompt's final chunk, whose token the
    tick waits for, is left to :meth:`prefill_step`.
    """

    # Subclasses whose prefill_step does more than the target's chunk turn
    # this off (the speculative engine's draft shadows every chunk).
    prefill_lookahead = True

    def __init__(
        self,
        params: Any,
        plan: ShardingPlan,
        apply_fn: Optional[Callable] = None,
        decode_model: Optional[DecodeModel] = None,
        n_slots: int = 8,
        page_len: Optional[int] = None,
        n_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        max_len: Optional[int] = None,
        resource_spec: Any = None,
        serve_hbm_frac: float = 0.5,
        prefix_cache: Union[bool, "serve_prefix.PrefixCache", None] = None,
    ):
        if apply_fn is None and decode_model is None:
            raise ValueError(
                "InferenceEngine needs apply_fn (one-shot), decode_model "
                "(autoregressive), or both")
        self.plan = plan
        self.mesh = plan.mesh
        self._data_axis = data_axis(self.mesh)
        self._data_degree = dict(
            zip(self.mesh.axis_names, self.mesh.devices.shape))[self._data_axis]
        # Storage view + plan shardings: the same parameter contract the
        # train step uses, in the tree the model's programs read.
        self.param_bytes_given = tree_bytes(params)
        self.params = place_params(params, plan, decode_model)
        self.param_bytes = tree_bytes(self.params)
        logging.info("serving parameters placed: %d bytes (%d given)",
                     self.param_bytes, self.param_bytes_given)
        self._apply_fn = apply_fn
        self._apply_jit = (
            jax.jit(lambda p, b: apply_fn(plan.unpad_params(p), b))
            if apply_fn is not None else None
        )
        self.decode_model = decode_model
        if decode_model is None:
            return
        # Decode rows shard over the data axis via the batch dim of the
        # per-step tensors; keep the row count divisible so gathers stay
        # even (round up rather than reject).
        if n_slots % self._data_degree:
            n_slots += self._data_degree - n_slots % self._data_degree
        self.n_slots = n_slots
        # The model's statement of its cache; none is the plain paged
        # timeline at the page length asked for (16 where none is).
        layout = decode_model.cache_layout
        if layout is None:
            layout = serve_pages.CacheLayout(
                page_len=int(page_len or serve_pages.DEFAULT_PAGE_LEN))
        elif page_len is not None and int(page_len) != layout.page_len:
            raise ValueError(
                f"this model's cache has pages of {layout.page_len} "
                f"positions; page_len={page_len} was asked for")
        self.layout = layout
        self.page_len = layout.page_len
        self.prefill_chunk = int(
            prefill_chunk or layout.prefill_chunk or self.page_len)
        layout.check_chunk(self.prefill_chunk)
        # Static timeline ceiling: the positional limit rounded DOWN to a
        # multiple of lcm(page_len, chunk) — guarantees every chunk's pad
        # positions stay inside the static page-table width (see
        # forward_paged_prefill_chunk's safety contract).
        ceiling = min(
            x for x in (max_len, decode_model.max_len) if x is not None
        ) if (max_len or decode_model.max_len) else 1024
        quantum = math.lcm(self.page_len, self.prefill_chunk)
        self.max_len = (int(ceiling) // quantum) * quantum
        if self.max_len <= 0:
            raise ValueError(
                f"max_len {ceiling} cannot fit one page_len={page_len} x "
                f"prefill_chunk={self.prefill_chunk} quantum ({quantum})")
        self.max_pages = layout.table_width(self.max_len)

        # Pool sizing: explicit n_pages wins; else ResourceSpec HBM
        # headroom funds it (capped at the point more pages cannot help —
        # every row at the full timeline). Per-page bytes from an abstract
        # eval of the model's own cache shape, so any DecodeModel prices
        # correctly.
        page_shaped = jax.eval_shape(
            lambda: decode_model.init_paged_cache(1, self.page_len))
        page_bytes = tree_bytes(page_shaped)
        self.page_bytes = page_bytes
        # Quantized pool mode (int8 pages + f32 scale planes, PR 20):
        # detected from the model's own cache pytree, so the engine needs
        # no config plumbing — the scale leaves share the page dim and ride
        # the page-axis-keyed sharding/COW/pricing below unchanged. fp-equiv
        # bytes reprice the int8 value leaves at the model's fp cache dtype
        # (its own unquantised cache's leaf dtype) and drop the scale leaves
        # (which would not exist in fp mode): the "what would these pages
        # cost unquantized" figure the capacity-x metrics divide by.
        self.kv_quant = isinstance(page_shaped, dict) and \
            "k_scale" in page_shaped
        if layout.window and (self.kv_quant or prefix_cache):
            raise serve_pages.CacheFeatureRefused(
                ("int8 pages" if self.kv_quant else "prefix sharing")
                + " over a window ring: the ring is overwritten as the "
                "window moves and its summaries are made from unquantised "
                "keys (ROADMAP.md Queue 2)")
        if layout.latent and self.kv_quant:
            raise serve_pages.CacheFeatureRefused(
                "int8 pages over a latent pool: a latent row is shared by "
                "every head and no scale plane is defined for it "
                "(ROADMAP.md Queue 2)")
        # Per-slot state (a recurrent layer's): priced here, placed once
        # beside the pool; what it cannot carry is refused at build.
        self.ssm_state_bytes = 0
        if decode_model.slot_state is not None:
            if prefix_cache:
                raise serve_pages.CacheFeatureRefused(
                    "prefix sharing over per-slot recurrent state: a "
                    "shared prefix would need the state at the prefix's "
                    "end (ROADMAP.md M4)")
            if self.kv_quant:
                raise serve_pages.CacheFeatureRefused(
                    "int8 pages beside per-slot recurrent state: no scale "
                    "plane is defined for them (ROADMAP.md M4)")
            self.ssm_state_bytes = tree_bytes(jax.eval_shape(
                lambda: decode_model.slot_state(self.n_slots)))
        if self.kv_quant:
            fp_itemsize = np.dtype(jax.tree_util.tree_leaves(jax.eval_shape(
                lambda: decode_model.init_paged_cache(
                    1, self.page_len, quantized=False)))[0].dtype).itemsize
            self.page_fp_equiv_bytes = sum(
                int(np.prod(leaf.shape)) * fp_itemsize
                for name, leaves in page_shaped.items()
                if not name.endswith("_scale")
                for leaf in jax.tree_util.tree_leaves(leaves))
        else:
            self.page_fp_equiv_bytes = page_bytes
        max_useful = self.n_slots * self.max_pages
        # Under prefix sharing, pages beyond every-row-at-max-timeline
        # are still useful: they hold COLD cached prefixes that turn
        # future admissions into page-table copies, and live tables
        # double-count shared pages — 1 table is no longer exclusive
        # pages. pool_size_from_spec owns the cap arithmetic.
        sharing_factor = 2.0 if prefix_cache else 1.0
        if n_pages is None:
            if resource_spec is not None:
                n_pages = serve_pages.pool_size_from_spec(
                    resource_spec, page_bytes, params_bytes=self.param_bytes,
                    state_bytes=self.ssm_state_bytes / self._data_degree,
                    serve_frac=serve_hbm_frac,
                    shard_degree=self._data_degree,
                    max_useful_pages=max_useful,
                    min_useful_pages=self.max_pages,
                    sharing_factor=sharing_factor)
            else:
                n_pages = int(max_useful * sharing_factor) + 1
        n_pages = max(int(n_pages), self.max_pages + 1)
        if n_pages % self._data_degree:
            n_pages += self._data_degree - n_pages % self._data_degree
        self.pool = serve_pages.build_pool(
            n_pages, self.page_len, quantized=self.kv_quant,
            bytes_per_page=float(page_bytes),
            fp_equiv_bytes_per_page=float(self.page_fp_equiv_bytes))
        self._cache_sh = self._cache_shardings(
            decode_model.init_paged_cache, n_pages)
        self._cache = jax.device_put(
            decode_model.init_paged_cache(n_pages, self.page_len),
            self._cache_sh)
        self._state = self._state_sh = None
        if decode_model.slot_state is not None:
            self._state_sh = self._slot_shardings(decode_model.slot_state)
            self._state = jax.jit(lambda: decode_model.slot_state(n_slots),
                                  out_shardings=self._state_sh)()
            logging.info("per-slot state placed: %d bytes beside %d bytes "
                         "of parameters", self.ssm_state_bytes,
                         self.param_bytes)
        # Copy-on-write prefix sharing (serve/prefix.py): pass True to
        # build the refcounted radix cache over this engine's pool, or an
        # already-built PrefixCache (the spec engine hands one spanning
        # both its pools). None/False = sharing off (every admission
        # prefills its whole prompt — the selftest's control arm).
        if isinstance(prefix_cache, serve_prefix.PrefixCache):
            self._prefix_cache: Optional[serve_prefix.PrefixCache] = \
                prefix_cache
        elif prefix_cache:
            self._prefix_cache = serve_prefix.build_prefix_cache(
                self.pool, self.page_len)
        else:
            self._prefix_cache = None
        self._copy_fn = None     # the COW page copy, compiled lazily

        # Host-side slot tables (single scheduler-thread writer).
        self._phase = np.full(n_slots, _FREE, np.int8)
        self._tables: List[Optional[serve_pages.PageTable]] = [None] * n_slots
        # Per-slot full table (prefill reads its row); decode sees a row
        # only once the slot ENTERS decode — a prefilling slot's pages
        # must never take decode-step scatter writes.
        self._table_np = np.full(
            (n_slots, self.max_pages), serve_pages.SCRATCH_PAGE, np.int32)
        self._decode_table_np = np.full(
            (n_slots, self.max_pages), serve_pages.SCRATCH_PAGE, np.int32)
        self._lengths = np.zeros(n_slots, np.int32)
        self._last_token = np.zeros(n_slots, np.int32)
        self._prompts: List[Optional[np.ndarray]] = [None] * n_slots
        # Stable request identity per slot: the serve spans and flight
        # records tag device work with it, so one chrome trace shows a
        # request's prefill chunks and decode steps by id (PR 14).
        self._request_ids: List[str] = [""] * n_slots
        self._prefill_pos = np.zeros(n_slots, np.int32)
        # the chunk before _prefill_pos went out behind the last decode step
        self._chunk_ahead = np.zeros(n_slots, bool)
        self._prefill_start = np.zeros(n_slots, np.int32)
        self._prefill_t0 = np.zeros(n_slots, np.float64)
        # Prefix-sharing bookkeeping: the slot's Lease on tree pages and
        # whether its admission matched any cached prefix (the cached/
        # uncached TTFT split keys off this flag).
        self._leases: List[Optional[serve_prefix.Lease]] = [None] * n_slots
        self._cached = np.zeros(n_slots, bool)
        # Per-slot sampling params (serve/sampling.py — the ONE sampling
        # home): greedy defaults (temperature 0) make an all-greedy batch
        # bit-identical to the pre-sampling engine. These ride the
        # compiled programs as traced per-slot ARRAYS, so per-request
        # params never recompile anything and the program pins hold.
        self._samp = serve_sampling.slot_arrays(n_slots)
        # The host mirrors the programs read, by name: the decode step's
        # tokens, lengths, tables and sampling arrays, and (a row of them)
        # a chunk's table and sampling rows. They stay the truth the
        # batcher, the spec engine, the prefix tree and the tests read.
        self._mirrors = {
            "tokens": (self._last_token,), "lengths": (self._lengths,),
            "tables": (self._decode_table_np,), "table": (self._table_np,),
            "samp": tuple(self._samp[k] for k in _SAMP_KEYS)}
        # Their device copies between calls, keyed ``(name, None)`` for the
        # decode batch's and ``(name, row)`` for a row's chunk inputs. An
        # absent key is stale: the next call that reads it puts it again,
        # whole, from its mirror (``_inputs``). ``_set_row`` is the one
        # writer of the mirrors and drops what a changed value makes
        # stale; the decode step hands back the next tokens and lengths
        # itself, so a tick with no admission, prefill completion or
        # release puts nothing.
        self._dev: Dict[Tuple[str, Optional[int]], Any] = {}
        self._in_sh = NamedSharding(self.mesh, P())
        # Arrays put on the device for the programs' inputs (a chunk's
        # tokens among them). Cumulative; the batcher publishes it
        # (serve_input_puts_total); a call's own rides its
        # ``serve.decode_dispatch`` / ``serve.prefill_chunk`` span as
        # ``puts``.
        self.input_puts = 0
        self._prefill_fn = None
        self._decode_fn = None
        self._decode_step_count = 0
        # Target-model decode-program invocations: the denominator of the
        # speculative-decode acceptance bar (>=2x fewer target invocations
        # per emitted token than plain greedy — serve/spec.py counts its
        # verify program through the same ledger).
        self.decode_invocations = 0
        # Over a window ring: boundaries that rows' positions crossed (the
        # window behind closes and its summaries become visible), those of
        # them crossed by the decode step, and summaries made. Cumulative;
        # the batcher publishes them (serve_window_rolls_total, ...). The
        # host does nothing at a boundary: these only count.
        self.window_rolls = 0
        self.window_rolls_decode = 0
        self.summary_chunks = 0
        # Over per-slot state: rows whose state the decode steps dispatched
        # updated (the decoding rows; the rest are skipped). Cumulative;
        # the batcher publishes it (serve_ssm_rows_total).
        self.ssm_rows = 0
        # Over the sampler (``sample_tokens``): calls of the two programs
        # whose rows were all greedy, which end in the argmax alone, and
        # those with a row that samples, which run the draw. The device
        # tests any ``temperature > 0`` in the arrays the call takes; the
        # host reads the same from its mirror, through a count of the rows
        # that sample kept at admission and release. Cumulative; a call's
        # own rides its ``serve.decode_dispatch`` / ``serve.prefill_chunk``
        # span as ``sampler`` (``"argmax"`` or ``"draw"``).
        self.sampler_argmax_calls = 0
        self.sampler_draw_calls = 0
        self.stochastic_slots = 0
        # What only the device knows of a step (``DecodeModel.step_facts``):
        # summed over the decode steps fetched (``fact_steps`` of them), for
        # the batcher to publish; a chunk's facts stay on its span. A chunk
        # whose token nobody waits for leaves its vector on the device
        # until the next fetch brings it along.
        self.step_facts = tuple(decode_model.step_facts)
        self.steps_fact = decode_model.steps_fact
        self.fact_totals = dict.fromkeys(self.step_facts, 0)
        self.fact_steps = 0
        self._facts_pending: List[Tuple[Dict[str, Any], Any]] = []
        # Replica identity carried into the chaos seams so a schedule can
        # target ONE replica of a fleet (replica_death injects
        # EngineDeadError only where host matches — docs/chaos.md).
        # serve/replica.py sets it; 0 for standalone engines.
        self.chaos_host = 0

    # ------------------------------------------------------------ construction
    @classmethod
    def build(
        cls,
        params: Any,
        apply_fn: Optional[Callable] = None,
        decode_model: Optional[DecodeModel] = None,
        *,
        strategy_builder=None,
        resource_spec=None,
        mesh=None,
        checkpoint: Optional[str] = None,
        **engine_kwargs,
    ) -> "InferenceEngine":
        """Standalone construction: capture → strategy → lower → engine.

        The one-call path for scripts that don't hold an
        :class:`~autodist_tpu.api.AutoDist` (which offers the same through
        ``build_inference`` with the chief/worker strategy handoff).
        ``checkpoint`` restores params from a ``Saver`` checkpoint directly
        into the plan's shardings — each process reads only the file regions
        its devices need, so loading a sharded model never materializes the
        full logical arrays on one host.
        """
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        if resource_spec is None and mesh is None:
            resource_spec = ResourceSpec.from_local_devices()
        if mesh is None:
            mesh = build_mesh(resource_spec)
        # Inference default is AllReduce (replicated params, data-sharded
        # batch): with no gradient wire, PS/ZeRO residency choices only add
        # gathers to the forward. Model-partitioned builders (TensorParallel,
        # PartitionedAR) carry over as-is — their pspecs shard the serving
        # params the same way they sharded training.
        builder = strategy_builder or AllReduce()
        model_item = ModelItem.from_params(params)
        strategy = builder.build(model_item, resource_spec) if resource_spec \
            else builder.build(model_item, ResourceSpec.from_local_devices())
        compiled = StrategyCompiler(model_item).compile(strategy)
        plan = GraphTransformer(compiled, model_item, mesh).transform()
        if checkpoint is not None:
            params = cls.restore_params(checkpoint, params, plan)
        return cls(params, plan, apply_fn=apply_fn, decode_model=decode_model,
                   resource_spec=resource_spec, **engine_kwargs)

    @staticmethod
    def restore_params(checkpoint: str, params_template: Any,
                       plan: ShardingPlan) -> Any:
        """Checkpoint → params in plan shardings (partial, parallel read).

        ``checkpoint`` is a checkpoint dir (``.../ckpt-N``) or a Saver
        directory (the newest ``ckpt-*`` inside is taken). The template
        supplies the pytree structure + logical shapes; a training
        checkpoint's extra entries (optimizer slots, step) are ignored —
        saving ``state.params`` or the whole logical state both serve.
        """
        import os

        from autodist_tpu.checkpoint.saver import Saver

        if os.path.exists(os.path.join(checkpoint, "metadata.json")):
            saver, path = Saver(os.path.dirname(checkpoint)), checkpoint
        else:
            saver = Saver(checkpoint)
            path = saver.latest_checkpoint()
            if path is None:
                raise FileNotFoundError(f"no ckpt-* under {checkpoint!r}")
        shaped = jax.eval_shape(lambda: params_template)
        # Serving keeps params HBM-resident regardless of training-time
        # offload markers (device_view): offload trades HBM for per-step
        # streaming, a training-memory bargain inference has no reason to pay.
        shardings = plan.params_shardings(shaped, device_view=True)
        # A checkpoint written from a full train state (step.save) prefixes
        # every parameter with "params/"; restore just that subtree so the
        # optimizer/step entries are never read.
        from autodist_tpu.model_item import _path_to_name

        leaves, _ = jax.tree_util.tree_flatten_with_path(shaped)
        probe = _path_to_name(leaves[0][0]) if leaves else ""
        entries = Saver.read_metadata(path)["entries"]
        if probe and probe not in entries and f"params/{probe}" in entries:
            return saver.restore_subtree(path, "params", shaped, shardings)
        return saver.restore(path, target=shaped, shardings=shardings)

    # --------------------------------------------------------------- one-shot
    def infer(self, batch: Any) -> Any:
        """One-shot forward (classification, scoring): batch shards over the
        data axis, output stays a device pytree."""
        if self._apply_jit is None:
            raise ValueError("engine built without apply_fn; one-shot "
                             "inference unavailable")
        batch = jax.device_put(
            batch, self.plan.batch_shardings(batch, strict=False))
        return self._apply_jit(self.params, batch)

    # ------------------------------------------------------------ decode pool
    def _cache_shardings(self, init_cache, n_pages: int):
        """Page dim (where the model's layout says: dim 0 of a leaf a
        layer, dim 1 of a pool stacked over layers) over the data axis;
        scalars and vectors replicate. Evaluated on abstract shapes
        — no device cache is built to derive its own sharding."""
        from autodist_tpu.kernel.mesh import data_sharding

        shaped = jax.eval_shape(lambda: init_cache(n_pages, self.page_len))
        axis = self.layout.page_axis

        def leaf_sh(leaf):
            if len(leaf.shape) >= 2 and leaf.shape[axis] == n_pages:
                return data_sharding(self.mesh, len(leaf.shape), dim=axis)
            return NamedSharding(self.mesh, P())

        return jax.tree_util.tree_map(leaf_sh, shaped)

    def _slot_shardings(self, init_state):
        """Per-slot state: the slot dim (dim 0) over the data axis, as the
        decode rows are."""
        from autodist_tpu.kernel.mesh import data_sharding

        shaped = jax.eval_shape(lambda: init_state(self.n_slots))
        return jax.tree_util.tree_map(
            lambda leaf: data_sharding(self.mesh, len(leaf.shape), dim=0), shaped)

    def _compile(self) -> None:
        dm = self.decode_model
        # Donate the cache: both programs rewrite the page pool in place on
        # device — steady-state serving allocates nothing. The cache's
        # OUTPUT sharding is pinned to the canonical pool sharding: left to
        # GSPMD's choice it can drift between programs, and a
        # differently-sharded cache argument would silently compile a third
        # serving program (the exactly-2 acceptance pin). The small inputs
        # are put replicated (``_in_sh``) and the decode step's next tokens
        # and lengths come back the same way, so a put copy and a handed-back
        # one are the same argument to the compiled program.
        token_sh = self._in_sh
        n_slots = self.n_slots

        # Named functions: the device trace's module line reads
        # jit_serve_prefill_chunk / jit_serve_decode_step, which is how the
        # benchmark's readers tell the two programs apart.
        # A model that carries per-slot state hands it to both programs
        # (and the chunk its row's slot), donated beside the pool; ``state``
        # is empty for one that carries none. A chunk's one int32 operand
        # is its tokens followed by ``start``, the prompt's length and the
        # row (``_dispatch_chunk``).
        def serve_prefill_chunk(p, chunk, cache, table, samp, *state):
            c = chunk.shape[0] - 3
            slot = dict(state=state[0], slot=chunk[c + 2]) if state else {}
            return dm.prefill_chunk(
                self.plan.unpad_params(p), chunk[None, :c], chunk[c],
                chunk[c + 1], cache, table, samp=samp, **slot)

        # Beside what the model returns, the next step's inputs: each
        # decoding row's token and its length + 1. A row that is not
        # decoding carries length 0 and keeps both as they were.
        def serve_decode_step(p, tokens, positions, cache, tables, samp,
                              *state):
            out, *rest = dm.decode_paged(
                self.plan.unpad_params(p), tokens, positions, cache, tables,
                samp=samp, **dict(zip(("state",), state)))
            decoding = positions > 0
            return (out, *rest,
                    jnp.where(decoding, out[:n_slots], tokens),
                    jnp.where(decoding, positions + 1, positions))

        stateful = self._state is not None
        out_sh = (token_sh, self._cache_sh) + ((self._state_sh,) if stateful else ())
        self._prefill_fn = jax.jit(
            serve_prefill_chunk, donate_argnums=(2, 5)[:1 + stateful],
            out_shardings=out_sh)
        self._decode_fn = jax.jit(
            serve_decode_step, donate_argnums=(3, 6)[:1 + stateful],
            out_shardings=out_sh + (token_sh, token_sh))

    @property
    def compiled_programs(self) -> int:
        """How many serving programs have actually compiled — the
        acceptance pin is exactly 2 (one decode + one chunked prefill)
        regardless of the request-length mix. Counts real XLA cache
        entries; raising (not guessing) on a jax that drops the
        introspection keeps the pin honest — a fallback of "1 per
        wrapped fn" would pass forever while a sharding drift silently
        compiled a third program."""
        total = 0
        for fn in (self._prefill_fn, self._decode_fn):
            if fn is None:
                continue
            size = getattr(fn, "_cache_size", None)
            if size is None:
                raise RuntimeError(
                    "jax.jit lost _cache_size(); compiled_programs cannot "
                    "count real compilations — update the pin (the "
                    "exactly-2-programs acceptance bar must count actual "
                    "XLA cache entries, never assume)")
            total += int(size())
        return total

    # -------------------------------------------------------------- accounting
    @property
    def free_slots(self) -> int:
        return int((self._phase == _FREE).sum())

    @property
    def active_slots(self) -> int:
        return int((self._phase != _FREE).sum())

    @property
    def active_tokens(self) -> int:
        """Timeline tokens reserved across active requests (allocated page
        capacity — the admission budget's currency)."""
        return self.pool.allocated_tokens

    @property
    def written_tokens(self) -> int:
        """Rows actually resident in reserved pages (prompt progress for
        prefilling slots, full timeline length for decoding ones; over a
        window ring what the ring holds of them plus their summaries)."""
        total = 0
        for idx in np.flatnonzero(self._phase != _FREE):
            idx = int(idx)
            if self._phase[idx] == _PREFILL:
                prompt = self._prompts[idx]
                n = min(int(self._prefill_pos[idx]),
                        len(prompt) if prompt is not None else 0)
            else:
                n = int(self._lengths[idx])
            total += self.layout.resident_rows(n)
        return total

    @property
    def ring_pages_in_use(self) -> int:
        """Pages that live tables hold in their ring segment (0 on a
        plain timeline, whose pages are all exact)."""
        if not self.layout.window:
            return 0
        return sum(t.n_exact for t in self._tables if t is not None)

    @property
    def summary_pages_in_use(self) -> int:
        """Pages that live tables hold for chunk summaries."""
        return sum(len(t.pages) - t.n_exact
                   for t in self._tables if t is not None)

    @property
    def page_utilization(self) -> float:
        return self.pool.utilization

    @property
    def page_fragmentation(self) -> float:
        return self.pool.fragmentation(self.written_tokens)

    @property
    def page_pool_bytes(self) -> int:
        """Device bytes of the static page pool (whole pool; divide by the
        data degree for per-chip when sharded) — the figure the analyzer's
        SLM passes account (``hbm_budget(serve_pool_bytes=...)``). The
        pool is a fixed physical tenant, so shared (refcounted) pages are
        inherently counted once; :attr:`shared_fraction` tells the SLM
        report how much logical timeline that physical footprint is
        actually carrying."""
        return int(self.page_bytes) * self.pool.n_pages

    @property
    def page_pool_fp_equiv_bytes(self) -> int:
        """What the pool's KV capacity would cost in fp pages — equal to
        :attr:`page_pool_bytes` unless the cache is quantized, in which
        case the ratio is the quantization capacity win
        (:attr:`quant_capacity_x`)."""
        return int(self.page_fp_equiv_bytes) * self.pool.n_pages

    @property
    def quant_capacity_x(self) -> float:
        """Effective-capacity multiplier from int8 KV pages (1.0 fp):
        fp-equivalent bytes per physical pool byte."""
        if not self.kv_quant or self.page_bytes <= 0:
            return 1.0
        return float(self.page_fp_equiv_bytes) / float(self.page_bytes)

    @property
    def prefix_cache(self) -> Optional["serve_prefix.PrefixCache"]:
        return self._prefix_cache

    def slot_cached(self, slot: Slot) -> bool:
        """Whether this slot's admission matched a cached prefix (>= 1
        token mapped instead of prefilled) — the cached/uncached TTFT
        split keys off this."""
        return bool(self._cached[slot.index])

    def _logical_physical_pages(self) -> Tuple[int, int]:
        """(logical, physical) page counts across live tables: logical
        counts every table entry, physical counts distinct pages — they
        differ exactly by sharing."""
        logical, phys = 0, set()
        for t in self._tables:
            if t is None:
                continue
            logical += len(t.pages)
            phys.update(t.pages)
        return logical, len(phys)

    @property
    def sharing_ratio(self) -> float:
        """``logical_bytes / physical_bytes`` across live page tables —
        1.0 with sharing off (or idle), above 1.0 when admissions map
        onto the same physical pages (the
        ``serve_page_pool_sharing_ratio`` gauge)."""
        logical, phys = self._logical_physical_pages()
        return logical / phys if phys else 1.0

    @property
    def shared_fraction(self) -> float:
        """Fraction of the live logical timeline served by deduplicated
        pages, 0..1 — the analyzer's shared-pool accounting figure
        (``hbm_budget(serve_shared_fraction=...)``)."""
        logical, phys = self._logical_physical_pages()
        return 1.0 - phys / logical if logical else 0.0

    def prefix_stats(self) -> Dict[str, float]:
        """The prefix tree's counters (zeros when sharing is off) — the
        ``serve_prefix_*`` gauges and the selftest bars read these."""
        if self._prefix_cache is None:
            return {"hit_rate": 0.0, "hits": 0, "lookups": 0,
                    "cached_pages": 0, "shared_pages": 0, "evictions": 0,
                    "inserts": 0, "cow_copies": 0, "live_refcount": 0}
        return self._prefix_cache.stats()

    # --------------------------------------------------------------- admission
    def check_admissible(self, prompt_len: int,
                         max_new_tokens: int) -> Optional[AdmissionDenied]:
        """The static (never-serveable) admission checks, shared by
        :meth:`admit` and the batcher's ``submit`` edge — ONE home for the
        ceiling arithmetic and its prose, so the typed-at-the-edge
        contract and the engine-side check cannot drift apart. Returns a
        non-retryable :class:`AdmissionDenied` or None (admissible as far
        as static shape goes — capacity is :meth:`admit`'s call)."""
        total = int(prompt_len) + int(max_new_tokens)
        if prompt_len < 1:
            return AdmissionDenied("empty prompt", retryable=False)
        if total > self.max_len:
            return AdmissionDenied(
                f"request needs a {total}-token timeline; engine ceiling is "
                f"{self.max_len} (prompt {prompt_len} + max_new_tokens "
                f"{max_new_tokens})", retryable=False)
        return None

    def _set_row(self, idx: int, **values) -> None:
        """Write row ``idx`` of the host mirrors the programs read, by
        name (``tokens``, ``lengths``, ``tables``: the row of the decode
        batch's; ``table``: the row's chunk table; ``samp``: its five
        sampling values in ``_SAMP_KEYS`` order), and drop the device
        copies that a changed value makes stale: the batch's and the
        row's own."""
        for name, value in values.items():
            changed = False
            for mirror, v in zip(self._mirrors[name],
                                 value if name == "samp" else (value,)):
                old = mirror[idx].copy()
                mirror[idx] = v
                changed |= not np.array_equal(old, mirror[idx])
            if changed:
                self._dev.pop((name, None), None)
                self._dev.pop((name, idx), None)

    def _mirror(self, key: Tuple[str, Optional[int]]):
        """What the device copy ``key`` is put from: the decode batch's
        arrays, or one row of them as a chunk takes it (the table's row
        ``[P]``, the sampling rows ``[1]``); a tuple for ``samp``. Copies:
        on a host platform a put may alias the array it is given, and the
        mirrors change under the copies."""
        name, idx = key
        pick = (slice(None) if idx is None else idx if name == "table"
                else slice(idx, idx + 1))
        rows = tuple(np.array(a[pick]) for a in self._mirrors[name])
        return rows if name == "samp" else rows[0]

    def _inputs(self, sp, *keys):
        """The device copies of ``keys``, those that are stale put again
        from their mirrors in one transfer; adds the arrays put to the
        span's ``puts`` and to :attr:`input_puts`."""
        stale = [k for k in keys if k not in self._dev]
        if stale:
            hosts = [self._mirror(k) for k in stale]
            self._dev.update(zip(stale, jax.device_put(hosts, self._in_sh)))
            n = len(jax.tree_util.tree_leaves(hosts))
            sp["puts"] += n
            self.input_puts += n
        return [self._dev[k] for k in keys]

    def _count_sampler(self, sp, draws) -> None:
        """Count a call by the sampler's branch it takes (``draws``: some
        row it serves samples) and stamp the branch on its span."""
        if draws:
            self.sampler_draw_calls += 1
        else:
            self.sampler_argmax_calls += 1
        sp["sampler"] = "draw" if draws else "argmax"

    def admit(self, prompt: np.ndarray, max_new_tokens: int,
              request_id: str = "",
              sampling: Optional["serve_sampling.SamplingParams"] = None,
              ) -> Union[Slot, AdmissionDenied]:
        """Reserve a decode row + pages for ``prompt`` — host bookkeeping
        only, no device work (prefill runs chunk-by-chunk via
        :meth:`prefill_step`). Returns a :class:`Slot` or a typed
        :class:`AdmissionDenied` (never raises for load/shape reasons):
        over the static ceiling is non-retryable — the request can never
        run; pool/row exhaustion is retryable — retirement recycles pages.
        ``request_id`` (the batcher's stable id) tags this slot's spans
        and flight records for request-scoped tracing — and, with
        ``sampling``, keys the counter-based RNG: the stream is a pure
        function of ``(request_id, seed, position)``, so re-admitting the
        same identity (failover resume, journal replay, prefix-cache hit
        or miss) reproduces it bit-identically.
        """
        if self.decode_model is None:
            raise ValueError("engine built without decode_model")
        prompt = np.asarray(prompt, np.int32).ravel()
        total = len(prompt) + int(max_new_tokens)
        unservable = self.check_admissible(len(prompt), max_new_tokens)
        if unservable is not None:
            return unservable
        # Chaos seam: "defer" emulates an admission failure (behaves as no
        # free capacity — the batcher keeps the request queued and
        # backpressure does the shedding); the hook may also raise
        # EngineDeadError.
        if chaos_hooks.fire(chaos_hooks.SEAM_SERVE_ADMIT,
                            prompt_len=len(prompt),
                            max_new_tokens=max_new_tokens) == "defer":
            return AdmissionDenied("admission deferred (chaos)",
                                   retryable=True)
        free = np.flatnonzero(self._phase == _FREE)
        if not len(free):
            return AdmissionDenied(
                f"no free decode row ({self.n_slots} active)",
                retryable=True)
        lease: Optional[serve_prefix.Lease] = None
        start_pos = 0
        if self._prefix_cache is None:
            table = self.pool.alloc(total, self.layout)
        else:
            # Prefix sharing: matched full blocks ride the SAME physical
            # pages (refcount++ under the lease); only the unmatched
            # suffix reserves fresh pages — under pressure, cold cached
            # prefixes evict (LRU leaves) before the admission defers.
            m = self._prefix_cache.match(prompt)
            lease = self._prefix_cache.acquire(m)
            suffix_tokens = total - m.n_full * self.page_len
            table = self._alloc_with_evict(suffix_tokens)
            if table is None:
                self._prefix_cache.cancel(lease)
            else:
                start_pos = m.n_full * self.page_len
                if m.tail_len:
                    # COW frontier: copy the partially-matched page into
                    # this request's FIRST exclusive page, then resume
                    # prefill mid-page — a shared page is never written.
                    # The source node stays pinned on the lease until
                    # release: the spec engine's draft-side COW reads it
                    # after this call, and eviction must not race it.
                    self._cow_page(m.tail_node.page, table.pages[0])
                    start_pos += m.tail_len
                else:
                    self._prefix_cache.unpin_tail(lease)
                table.pages[:0] = [nd.page for nd in lease.nodes]
        if table is None:
            return AdmissionDenied(
                f"page pool exhausted ({self.pool.free_pages} of "
                f"{self.pool.usable_pages} pages free; need "
                f"{self.layout.pages_for(total)})",
                retryable=True)
        idx = int(free[0])
        self._phase[idx] = _PREFILL
        self._tables[idx] = table
        sp = sampling or serve_sampling.SamplingParams()
        request_id = str(request_id or "")
        hi, lo = serve_sampling.request_key(request_id, sp.seed)
        self._set_row(idx, table=table.padded(self.max_pages),
                      tables=serve_pages.SCRATCH_PAGE, lengths=0, tokens=0,
                      samp=(sp.temperature, sp.top_k, sp.top_p, hi, lo))
        self.stochastic_slots += int(self._samp["temperature"][idx] > 0)
        self._prompts[idx] = prompt
        self._request_ids[idx] = request_id
        self._prefill_pos[idx] = start_pos
        self._prefill_start[idx] = start_pos
        self._leases[idx] = lease
        self._cached[idx] = start_pos > 0
        self._prefill_t0[idx] = time.perf_counter()
        # Flight-record the admit (non-critical: batched fsync — serve load
        # must not turn into an fsync storm). Rate is bounded by request
        # admission, not token emission.
        obs_recorder.record_step(
            surface="serve", event="admit", prompt_len=len(prompt),
            request_id=self._request_ids[idx], pages=len(table.pages),
            cached_tokens=start_pos,
            pool_used=self.pool.used_pages, pool_free=self.pool.free_pages)
        return Slot(idx)

    def _alloc_with_evict(
            self, n_tokens: int) -> Optional[serve_pages.PageTable]:
        """Pool allocation with eviction retry: when the pool cannot cover
        the suffix, reclaim cold cached prefixes (LRU refcount-0 leaves)
        and try again — pressure degrades FUTURE admissions to recompute,
        never a live request's pages. Returns None only once the tree has
        nothing left to give (or a chaos exhaustion window is open)."""
        table = self.pool.alloc(n_tokens)
        need = serve_pages.pages_for_tokens(n_tokens, self.page_len)
        while table is None and self._prefix_cache is not None:
            if self._prefix_cache.evict(need) == 0:
                return None
            table = self.pool.alloc(n_tokens)
        return table

    def _make_page_copy_fn(self, n_pages: int, cache_sh):
        """Compile the COW page copy for one pool: every cache leaf's
        ``src`` page duplicated into ``dst`` along the layout's page axis,
        donated in place with the pool's canonical sharding. Page ids are
        traced scalars, so ONE program serves every copy — a data-movement
        program over the pool, not a serving program (the exactly-2 /
        exactly-5 pins count the per-token decode/prefill/verify
        programs)."""
        axis = self.layout.page_axis

        def copy_page(leaf, src, dst):
            if leaf.ndim < 2 or leaf.shape[axis] != n_pages:
                return leaf
            page = jax.lax.dynamic_index_in_dim(leaf, src, axis)
            return jax.lax.dynamic_update_index_in_dim(leaf, page, dst, axis)

        def serve_cow_copy(cache, src, dst):
            return jax.tree_util.tree_map(
                lambda leaf: copy_page(leaf, src, dst), cache)

        return jax.jit(serve_cow_copy, donate_argnums=(0,),
                       out_shardings=cache_sh)

    def _cow_page(self, src_page: int, dst_page: int) -> None:
        """Device copy of one KV page — the copy-on-write at the
        divergence frontier (never a shared write)."""
        if self._copy_fn is None:
            self._copy_fn = self._make_page_copy_fn(
                self.pool.n_pages, self._cache_sh)
        with obs_spans.span("serve.cow_copy", src=int(src_page),
                            dst=int(dst_page)):
            self._cache = self._copy_fn(
                self._cache, jnp.int32(src_page), jnp.int32(dst_page))
        if self._prefix_cache is not None:
            self._prefix_cache.cow_copies += 1

    def prefill_pending(self) -> List[Slot]:
        """Slots mid-prefill, in row order — the batcher advances each by
        one chunk per tick (chunked prefill interleaves with decode)."""
        return [Slot(int(i)) for i in np.flatnonzero(self._phase == _PREFILL)]

    def _dispatch_chunk(self, idx: int):
        """Dispatch the next chunk of row ``idx``'s prompt and advance its
        position; returns ``(first token, still on the device; final; the
        span's attributes)``.

        The ``serve.prefill_chunk`` span covers the host's preparation and
        the asynchronous DISPATCH of the chunk, not its run on the device
        (that is the module ``jit_serve_prefill_chunk`` in a device trace)."""
        prompt = self._prompts[idx]
        start = int(self._prefill_pos[idx])
        c = self.prefill_chunk
        if self._prefill_fn is None:
            self._compile()
        final = start + c >= len(prompt)
        with obs_spans.span("serve.prefill_chunk", start=start,
                            prompt_len=len(prompt), final=final,
                            request_id=self._request_ids[idx], puts=1) as sp:
            # the one put of a chunk whose row's inputs are on the device
            valid = prompt[start:start + c]
            chunk = np.zeros(c + 3, np.int32)
            chunk[: len(valid)] = valid
            chunk[c:] = start, len(prompt), idx
            self.input_puts += 1
            self._count_sampler(sp, self._samp["temperature"][idx] > 0)
            table, samp = self._inputs(sp, ("table", idx), ("samp", idx))
            state = () if self._state is None else (self._state,)
            first, self._cache, *state = self._prefill_fn(
                self.params, jax.device_put(chunk, self._in_sh), self._cache,
                table, samp, *state)
            self._state = state[0] if state else None
        if self.layout.window:
            rolls, chunks = self.layout.rolls(
                start, min(start + c, len(prompt)))
            self.window_rolls += rolls
            self.summary_chunks += chunks
        self._prefill_pos[idx] = start + c
        if self.step_facts and not final:
            self._facts_pending.append((sp, first))
        return first, final, sp

    def _dispatch_chunks_ahead(self) -> None:
        """Behind a decode step that has just been dispatched: the next
        chunk of every row mid-prefill, unless it is the prompt's last
        (its token is fetched by the tick that owns it)."""
        for idx in np.flatnonzero(self._phase == _PREFILL):
            idx = int(idx)
            if self._chunk_ahead[idx] or (
                    int(self._prefill_pos[idx]) + self.prefill_chunk
                    >= len(self._prompts[idx])):
                continue
            self._dispatch_chunk(idx)
            self._chunk_ahead[idx] = True

    def _fetch(self, tokens, attrs, pending=None, decode=False) -> np.ndarray:
        """The one host fetch of a tick: a program's token vector and,
        with it, the vectors of the chunks dispatched before it whose
        tokens nobody waited for (``pending``: ``(span attributes,
        vector)``; all that are left where none is given), which have run
        by now. Returns the tokens. Where the model states ``step_facts``
        each vector's tail is stamped on the span that produced it
        (``attrs`` for ``tokens``' own) and a decode step's is added to
        ``fact_totals``.

        The copies to the host start first, so that each runs on the
        device as soon as its program has. The wait for the program to
        finish is then a span of its own, ``wait.tokens`` (``program``):
        its end, on the device trace's clock, is the instant the host learns
        the program has run, and what the enclosing ``serve.token_fetch``
        holds beyond it is what is left of the copy."""
        vecs = [tokens]
        if self.step_facts:
            if pending is None:
                pending, self._facts_pending = self._facts_pending, []
            vecs += [vec for _, vec in pending]
        for vec in vecs:
            vec.copy_to_host_async()
        with obs_spans.span("wait.tokens", program="decode_step" if decode
                            else "prefill_chunk"):
            jax.block_until_ready(tokens)
        got = jax.device_get(vecs)
        if not self.step_facts:
            return np.asarray(got[0])
        n = len(self.step_facts)
        for where, vec in zip([attrs] + [sp for sp, _ in pending], got):
            where.update(zip(self.step_facts, (int(v) for v in vec[-n:])))
        if decode:
            for name in self.step_facts:
                self.fact_totals[name] += attrs[name]
            self.fact_steps += 1
        return np.asarray(got[0])[:-n]

    def prefill_step(self, slot: Slot) -> Optional[int]:
        """Run ONE prefill chunk for ``slot``. Returns the first generated
        token when the prompt is fully prefilled (the slot then joins the
        decode batch next :meth:`step`), else None. A chunk that already
        went out behind the last decode step counts as this call's; only
        a final chunk waits, under ``serve.token_fetch``."""
        idx = slot.index
        if self._phase[idx] != _PREFILL:
            raise ValueError(f"slot {idx} is not prefilling")
        if self._chunk_ahead[idx]:
            self._chunk_ahead[idx] = False
            return None
        prompt = self._prompts[idx]
        c = self.prefill_chunk
        first, final, chunk_sp = self._dispatch_chunk(idx)
        if not final:
            return None
        with obs_spans.span("serve.token_fetch", program="prefill_chunk"):
            first = int(self._fetch(first, chunk_sp)[0])
        self._phase[idx] = _DECODE
        self._set_row(idx, lengths=len(prompt), tokens=first,
                      tables=self._table_np[idx])
        if self._leases[idx] is not None:
            # Adopt this prompt's novel full blocks into the prefix tree:
            # the NEXT admission sharing them becomes a page-table copy.
            self._insert_prefix(idx, prompt)
        prefilled = len(prompt) - int(self._prefill_start[idx])
        obs_recorder.record_step(
            surface="serve", event="prefilled", prompt_len=len(prompt),
            chunks=-(-prefilled // c), cached=bool(self._cached[idx]),
            prefill_s=round(time.perf_counter() - self._prefill_t0[idx], 6))
        return first

    def _insert_prefix(self, idx: int, prompt: np.ndarray) -> None:
        """Hook for the prefix-tree adoption at prefill completion (the
        spec engine overrides it to adopt target + draft pages as ONE
        node per block)."""
        self._prefix_cache.insert(
            prompt, self._tables[idx].pages, self._leases[idx])

    def step(self) -> Dict[Slot, int]:
        """One decode step over the full slot batch (ONE compiled program).

        Feeds each decoding row its last emitted token at its current
        position, returns ``{slot: next_token}`` for decoding rows only
        (idle and prefilling rows ride along against the scratch page —
        finite garbage, ignored). Host-side lengths advance here — the
        emitted token's k/v will be written at the advanced position next
        step.
        """
        out: Dict[Slot, int] = {}
        # Chaos seam: may raise EngineDeadError (mid-decode engine death);
        # host identifies this engine's replica so fleet schedules can
        # kill exactly one of N.
        chaos_hooks.fire(chaos_hooks.SEAM_SERVE_STEP,
                         active=self.active_slots, host=self.chaos_host)
        decoding = np.flatnonzero(self._phase == _DECODE)
        if not len(decoding):
            return out
        if self._decode_fn is None:
            self._compile()
        # The decode step serves every decoding row at once: tag the span
        # with the request ids riding it (bounded — a trace viewer needs
        # identity, not an unbounded arg blob).
        rids = [self._request_ids[int(i)] for i in decoding[:16]
                if self._request_ids[int(i)]]
        self.decode_invocations += 1
        with obs_spans.span("serve.decode_step", active=int(len(decoding)),
                            request_ids=rids) as step_sp:
            # The host's part (the puts of stale inputs and the call) apart
            # from the wait for the device: a device left idle under the
            # first is the host's to cure, under the second it is not idle.
            with obs_spans.span("serve.decode_dispatch", puts=0) as sp:
                last, lengths, tables, samp = self._inputs(
                    sp, ("tokens", None), ("lengths", None),
                    ("tables", None), ("samp", None))
                self._count_sampler(sp, self.stochastic_slots > 0)
                state = () if self._state is None else (self._state,)
                if state:
                    # the rows whose state the step updates: the decoding
                    sp["ssm_rows"] = int(len(decoding))
                    self.ssm_rows += sp["ssm_rows"]
                tokens, self._cache, *state, last, lengths = self._decode_fn(
                    self.params, last, lengths, self._cache, tables, samp,
                    *state)
                self._state = state[0] if state else None
            # the chunks that went out before this step have run by the
            # time its tokens arrive; those about to go out behind it have not
            ran, self._facts_pending = self._facts_pending, []
            if self.prefill_lookahead:
                self._dispatch_chunks_ahead()
            with obs_spans.span("serve.token_fetch", program="decode_step"):
                tokens = self._fetch(tokens, step_sp, ran, decode=True)
        for idx in decoding:
            idx = int(idx)
            if self.layout.window:
                rolls, chunks = self.layout.rolls(
                    int(self._lengths[idx]), int(self._lengths[idx]) + 1)
                self.window_rolls += rolls
                self.window_rolls_decode += rolls
                self.summary_chunks += chunks
            self._lengths[idx] += 1
            self._last_token[idx] = tokens[idx]
            out[Slot(idx)] = int(tokens[idx])
        # the step's own next inputs, equal to the mirrors just advanced
        self._dev[("tokens", None)] = last
        self._dev[("lengths", None)] = lengths
        # Sampled flight record (1 per 64 decode rounds): enough black-box
        # trail to show "serving was alive and at depth N" in a postmortem
        # without a per-token write amplifying the hot loop.
        self._decode_step_count += 1
        if self._decode_step_count % 64 == 1:
            obs_recorder.record_step(
                surface="serve", event="decode",
                decode_steps=self._decode_step_count, active_slots=len(out),
                pool_utilization=round(self.page_utilization, 4))
        return out

    def step_many(self) -> Dict[Slot, List[int]]:
        """One decode round, multi-token surface: ``{slot: [token, ...]}``.

        The batcher consumes THIS method so one scheduler loop serves
        both engines: plain decode emits exactly one token per decoding
        slot per round; the speculative engine (serve/spec.py) overrides
        it to emit 0..k+1 greedy-identical tokens per slot per round.
        """
        return {slot: [tok] for slot, tok in self.step().items()}

    def slot_len(self, slot: Slot) -> int:
        return int(self._lengths[slot.index])

    def release(self, slot: Slot) -> None:
        """Retire a row: its pages recycle into the pool immediately (the
        next admission may reuse them; stale KV rows are dead weight
        overwritten before any mask can admit them)."""
        idx = slot.index
        table = self._tables[idx]
        lease = self._leases[idx]
        if table is not None:
            if lease is not None:
                # Shared (tree-owned) pages only drop a refcount — they
                # stay cached for the next admission; exclusive pages
                # recycle immediately, exactly like the unshared path.
                shared = set(lease.pages)
                exclusive = [p for p in table.pages if p not in shared]
                self._prefix_cache.release(lease)
                if exclusive:
                    self.pool.reclaim(exclusive)
                table.pages = []
            else:
                self.pool.release(table)
        self._leases[idx] = None
        self._cached[idx] = False
        self._prefill_start[idx] = 0
        self._tables[idx] = None
        self._phase[idx] = _FREE
        self.stochastic_slots -= int(self._samp["temperature"][idx] > 0)
        self._set_row(idx, table=serve_pages.SCRATCH_PAGE,
                      tables=serve_pages.SCRATCH_PAGE, lengths=0, tokens=0,
                      samp=(0.0, 0, 1.0, 0, 0))
        self._prompts[idx] = None
        self._request_ids[idx] = ""
        self._prefill_pos[idx] = 0
        self._chunk_ahead[idx] = False

    @property
    def prefilling_slots(self) -> int:
        return int((self._phase == _PREFILL).sum())

    @property
    def decoding_slots(self) -> int:
        return int((self._phase == _DECODE).sum())

    # ------------------------------------------------------------- generation
    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 request_id: str = "",
                 sampling: Optional["serve_sampling.SamplingParams"] = None,
                 ) -> List[int]:
        """Single-request decode — the sequential baseline (and the
        correctness oracle's cached side; greedy unless ``sampling`` is
        given, in which case ``request_id`` keys the counter-based
        stream). Production traffic should go through the batcher; this
        admits one request and steps it alone.
        """
        admitted = self.admit(prompt, max_new_tokens,
                              request_id=request_id, sampling=sampling)
        if isinstance(admitted, AdmissionDenied):
            raise RuntimeError(
                f"single-request generate() not admitted: {admitted.reason}")
        slot = admitted
        try:
            first = None
            while first is None:
                first = self.prefill_step(slot)
            tokens = [first]
            eos = self.decode_model.eos_id
            # step_many so the speculative engine's multi-token rounds
            # drive single-request generate too (each round emits >= 1
            # token for a decoding slot — the loop always progresses);
            # tokens past max_new/EOS are computed-but-discarded, exactly
            # as the batcher truncates them at retirement.
            while len(tokens) < max_new_tokens and (
                    eos is None or tokens[-1] != eos):
                for tok in self.step_many()[slot]:
                    tokens.append(tok)
                    if len(tokens) >= max_new_tokens or tok == eos:
                        break
        finally:
            self.release(slot)
        return tokens
