"""User API (L5): the ``AutoDist`` entry point.

Mirrors the reference's user lifecycle (``/root/reference/autodist/
autodist.py``): construct ``AutoDist(resource_spec_file, strategy_builder)``,
then turn a single-device model into a distributed one. In the TF reference
that meant graph capture inside ``scope()`` + a wrapped session; here the
single-device artifact is a pure ``loss_fn`` + params pytree, and the result
is a compiled :class:`DistributedTrainStep` that runs sharded over the mesh.

Minimal usage (the ≤3-line diff contract, reference README.md:39-54)::

    import autodist_tpu as ad

    autodist = ad.AutoDist(resource_spec_file="spec.yml",
                           strategy_builder=ad.strategy.AllReduce())
    step = autodist.build(loss_fn, params, example_batch)   # <- the diff
    state = step.init(params)
    for batch in data:
        state, metrics = step(state, batch)

Lifecycle parity:
- one AutoDist per process (``autodist.py:46-57``);
- default builder is ``PSLoadBalancing`` (``autodist.py:70``);
- chief builds + serializes the strategy, workers deserialize by
  ``AUTODIST_STRATEGY_ID`` (``autodist.py:100-109``);
- ``build`` = capture → strategy → compile → transform
  (``autodist.py:139-150``).
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any, Callable, Optional, Sequence, TYPE_CHECKING, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import const
from autodist_tpu.const import ENV
from autodist_tpu.kernel import DistributedTrainStep, GraphTransformer, ShardingPlan, build_mesh
from autodist_tpu.model_item import ModelItem, OptimizerSpec
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import PSLoadBalancing, Strategy, StrategyBuilder, StrategyCompiler
from autodist_tpu.utils import is_broadcast_leaf, logging

if TYPE_CHECKING:  # circular at runtime: async_ps imports nothing from api
    from autodist_tpu.ft import FTConfig, FTRuntime
    from autodist_tpu.obs import ObsConfig, ObsRuntime
    from autodist_tpu.runtime.async_ps import AsyncPSTrainer

_default_autodist: Optional["AutoDist"] = None


# Windows per tune() trial, dispatched back-to-back with one trailing sync
# (see tune's timing loop): the device never idles on a host round trip
# between windows, and the sweep stays short.
_TUNE_TRIAL_WINDOWS = 4

# Non-factory jax.checkpoint_policies usable directly as a remat policy
# (factories like save_only_these_names need arguments and are out of scope
# for the string shorthand).
_REMAT_POLICIES = (
    "everything_saveable",
    "nothing_saveable",
    "dots_saveable",
    "checkpoint_dots",
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",
)


def _cast_compute(loss_fn: Callable, compute_dtype: str) -> Callable:
    """Mixed-precision wrapper: params enter the loss in ``compute_dtype``
    while the train state stays fp32 (master weights). Autodiff through
    ``astype`` upcasts gradients back to the parameter dtype, so the
    optimizer update runs full precision — the standard TPU policy (MXU
    eats bf16, accumulation and weight updates stay fp32). Non-floating
    leaves (embedding id tables etc.) pass through untouched.
    """
    dtype = jnp.dtype(compute_dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        raise ValueError(f"compute_dtype must be floating, got {compute_dtype!r}")

    def cast(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(dtype)
        return leaf

    def wrapped(params, batch):
        return loss_fn(jax.tree.map(cast, params), batch)

    return wrapped


def _remat_policy(remat: Union[bool, str]):
    if remat is True:
        return None
    if remat in _REMAT_POLICIES:
        return getattr(jax.checkpoint_policies, remat)
    raise ValueError(
        f"unknown remat policy {remat!r}; use True or one of {_REMAT_POLICIES}")


def _resolve_optimizer(optimizer):
    """(OptimizerSpec, optax transform) from the user's optimizer argument.

    One resolution path for :meth:`AutoDist.build` and
    :meth:`AutoDist.build_pipeline`: an :class:`OptimizerSpec` is
    materialized; ``None`` gets the default spec; a raw optax transform is
    wrapped as the opaque ``"custom"`` spec (planners then assume the
    conservative worst-case slot count).
    """
    if isinstance(optimizer, OptimizerSpec):
        return optimizer, optimizer.make()
    if optimizer is None:
        spec = OptimizerSpec("sgd", {"learning_rate": 0.01})
        return spec, spec.make()
    return OptimizerSpec("custom"), optimizer


def get_default_autodist() -> Optional["AutoDist"]:
    return _default_autodist


class AutoDist:
    """Distributed-training entry point bound to one cluster description."""

    def __init__(
        self,
        resource_spec_file: Optional[str] = None,
        strategy_builder: Union[StrategyBuilder, str, None] = None,
        resource_spec: Optional[ResourceSpec] = None,
        mesh_axes: Sequence[str] = ("data", "model"),
        fault_tolerance: "Optional[FTConfig]" = None,
        observability: "Optional[ObsConfig]" = None,
    ):
        global _default_autodist
        if _default_autodist is not None:
            # Parity: one AutoDist per process (autodist.py:46-57; the
            # reference test asserts the second construction raises).
            raise RuntimeError(
                "Only one AutoDist instance is supported per process; "
                "call AutoDist.reset_default() first if you really need another."
            )
        # Join the multi-controller runtime if this process was launched by
        # the coordinator/launcher (the reference's _setup stage,
        # autodist.py:120-128). Must happen before any ResourceSpec device
        # query initializes the XLA backend; idempotent, no-op single-process.
        from autodist_tpu.runtime.launcher import initialize_from_env

        initialize_from_env()

        if resource_spec is not None:
            self.resource_spec = resource_spec
        elif resource_spec_file:
            self.resource_spec = ResourceSpec(resource_spec_file)
        elif ENV.AUTODIST_RESOURCE_SPEC.val:
            self.resource_spec = ResourceSpec(ENV.AUTODIST_RESOURCE_SPEC.val)
        else:
            self.resource_spec = ResourceSpec.from_local_devices()
        # Default strategy builder (autodist.py:70). A string names a
        # builder class ("AllReduce", "Auto", ...) or the search-based
        # auto-planner ("plan" — docs/planner.md).
        if isinstance(strategy_builder, str):
            from autodist_tpu.strategy import from_name

            strategy_builder = from_name(strategy_builder)
        self.strategy_builder = strategy_builder or PSLoadBalancing()
        self.mesh_axes = tuple(mesh_axes)
        self._mesh = None
        self._built: Optional[DistributedTrainStep] = None
        self._strategy: Optional[Strategy] = None
        self._model_item: Optional[ModelItem] = None
        # Filled by tune(): {"table": {name: {measured_s, predicted_s}},
        # "calibration": Calibration, "calibration_path": str}.
        self.last_tune_results: Optional[dict] = None
        # Fault tolerance (docs/fault_tolerance.md): a started HealthMonitor
        # + SnapshotManager bundle, or None when the knob is off (zero
        # overhead on the default path).
        self.ft: "Optional[FTRuntime]" = None
        if fault_tolerance is not None:
            from autodist_tpu.ft import FTRuntime

            self.ft = FTRuntime(fault_tolerance)
        # Observability (docs/observability.md): spans + exporters +
        # cross-host aggregation, or None when the knob is off (zero
        # overhead on the default path — mirrors the ft pattern).
        self.obs: "Optional[ObsRuntime]" = None
        if observability is not None:
            from autodist_tpu.obs import ObsRuntime

            self.obs = ObsRuntime(observability)
            if self.ft is not None:
                # Straggler scores escalate through the ft HealthMonitor.
                self.obs.attach_monitor(self.ft.monitor)
        _default_autodist = self

    @classmethod
    def reset_default(cls) -> None:
        """Testing hook — the reference isolates per-process state by forking
        (tests/integration/test_all.py:20-75); we allow explicit reset."""
        global _default_autodist
        _default_autodist = None

    # ------------------------------------------------------------------ mesh
    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = build_mesh(self.resource_spec, axes=self.mesh_axes)
        return self._mesh

    @property
    def is_chief(self) -> bool:
        return const.is_chief_process()

    # ----------------------------------------------------------------- build
    def _build_or_load_strategy(self, model_item: ModelItem) -> Strategy:
        """Chief builds + serializes; workers receive it
        (autodist.py:100-109, strategy/base.py:89-99).

        Two handoff paths:
        - connected multi-controller runtime (all hosts started together,
          the TPU launch model): the chief broadcasts the strategy bytes
          over the distributed runtime — no shared filesystem or
          launch-time env needed;
        - coordinator-launched workers (reference SSH-relaunch model):
          load by ``AUTODIST_STRATEGY_ID`` from the shipped file.
        """
        shipped_id = ENV.AUTODIST_STRATEGY_ID.val
        if jax.process_count() > 1 and not (not self.is_chief and shipped_id):
            # Connected fleet without a coordinator-shipped strategy file:
            # broadcast. A worker that *was* shipped an id (Coordinator
            # relaunch, possibly with a hand-tuned strategy) must honor the
            # file — rebuilding from the local builder could silently train
            # a different strategy.
            return self._sync_strategy_multihost(model_item)
        if self.is_chief:
            strategy = self.strategy_builder.build(model_item, self.resource_spec)
            strategy.serialize()
            # Child/worker processes launched from here inherit the id.
            os.environ[ENV.AUTODIST_STRATEGY_ID.name] = strategy.id
        else:
            strategy_id = ENV.AUTODIST_STRATEGY_ID.val
            if not strategy_id:
                raise RuntimeError(
                    "AUTODIST_WORKER is set but AUTODIST_STRATEGY_ID is empty — "
                    "workers must be launched with the chief's strategy id "
                    "(the coordinator does this automatically)"
                )
            logging.info("worker loading strategy %s", strategy_id)
            strategy = self._wait_for_strategy(strategy_id)
        return strategy

    def _sync_strategy_multihost(self, model_item: ModelItem) -> Strategy:
        """Chief builds; everyone else receives the bytes via the runtime.

        Replaces the reference's SFTP strategy shipping
        (coordinator.py:84-88) with a payload broadcast riding the already-
        connected jax.distributed cluster: length first (fixed shape), then
        the zero-padded JSON bytes.
        """
        import json as _json

        from jax.experimental import multihost_utils

        if jax.process_index() == 0:
            try:
                strategy = self.strategy_builder.build(model_item, self.resource_spec)
            except Exception:
                # Only the chief builds — a build failure here is NOT
                # SPMD-deterministic, and the workers are already waiting in
                # the length broadcast below. Ship a -1 sentinel so every
                # process raises in lockstep instead of the workers pairing
                # this broadcast with some later one (protocol desync).
                multihost_utils.broadcast_one_to_all(np.int32(-1))
                raise
            strategy.serialize()  # audit trail on the chief host
            # Children forked from the chief later (coordinator relaunch
            # pattern) inherit the id, same as the single-process path.
            os.environ[ENV.AUTODIST_STRATEGY_ID.name] = strategy.id
            payload = _json.dumps(strategy.to_json()).encode()
        else:
            payload = b""
        n = int(multihost_utils.broadcast_one_to_all(np.int32(len(payload))))
        if n < 0:
            raise RuntimeError(
                "strategy build failed on the chief — see the chief's "
                "traceback for the cause")
        buf = np.zeros(n, np.uint8)
        if payload:
            buf[: len(payload)] = np.frombuffer(payload, np.uint8)
        buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
        strategy = Strategy.from_json(_json.loads(buf.tobytes().decode()))
        if jax.process_index() != 0:
            # The serialized path references the chief's filesystem; blank
            # it on receivers so nothing tries to read a nonexistent file.
            strategy.path = ""
        logging.info(
            "strategy %s synced across %d processes", strategy.id, jax.process_count()
        )
        return strategy

    @staticmethod
    def _wait_for_strategy(strategy_id: str, timeout_s: float = 60.0) -> Strategy:
        """Load the chief's serialized strategy, waiting for it to appear.

        Covers concurrent multi-process starts on a shared filesystem; on
        disjoint filesystems the runtime coordinator broadcasts the strategy
        instead (runtime/coordinator.py)."""
        from autodist_tpu.utils import retry as _retry

        path = os.path.join(const.DEFAULT_STRATEGY_DIR, strategy_id)
        if not _retry.wait_until(lambda: os.path.exists(path), timeout_s,
                                 interval_s=0.2):
            raise FileNotFoundError(
                f"strategy {strategy_id!r} not found at {path} after "
                f"{timeout_s:.0f}s — was the chief's strategy shipped to "
                f"this host? (AUTODIST_STRATEGY_ID contract)"
            )
        return Strategy.deserialize(strategy_id)

    def build(
        self,
        loss_fn: Callable,
        params: Any,
        example_batch: Any = None,
        optimizer: Union[OptimizerSpec, optax.GradientTransformation, None] = None,
        has_aux: bool = False,
        sparse_names: Sequence[str] = (),
        expert_names: Sequence[str] = (),
        donate_state: bool = True,
        host_offload: Union[bool, str] = False,
        grad_accum_steps: int = 1,
        remat: Union[bool, str] = False,
        compute_dtype: Union[str, None] = None,
        record_norms: bool = False,
    ) -> "Union[DistributedTrainStep, AsyncPSTrainer]":
        """Capture → strategy → compile → lower (autodist.py:139-150).

        Returns a :class:`DistributedTrainStep` (SPMD path), or — when the
        strategy carries ``sync=False`` PS nodes — a host-driven
        :class:`autodist_tpu.runtime.async_ps.AsyncPSTrainer`, whose
        ``run(state, next_batch_callable, n_pushes)`` signature differs
        from the SPMD step's ``run(state, batch, n_steps)`` (asynchronous
        pulls need a batch *source*, not one batch). See docs/async_ps.md.

        ``optimizer`` may be an :class:`OptimizerSpec` (serializable, lets
        builders see the optimizer) or a raw optax transform.
        ``host_offload=True`` parks PS-synchronized parameters + optimizer
        slots in pinned host memory, streaming through HBM per step (the
        reference's params-on-CPU placement, ps_strategy.py:38-55);
        ``host_offload="from_strategy"`` follows the strategy's own
        placement instead — only variables whose ``reduction_destination``
        (node- or shard-level) names a host CPU device are offloaded.
        ``grad_accum_steps=k`` microbatches each step k-ways (activation
        memory ÷ k, same update for batch-mean losses).
        ``remat`` rematerializes the forward pass during backward
        (``jax.checkpoint``): ``True`` saves nothing (max memory savings,
        ~+1/3 FLOPs), or pass a ``jax.checkpoint_policies`` name (e.g.
        ``"dots_saveable"``) to keep MXU outputs and recompute the rest —
        the HBM-vs-FLOPs trade the TPU guide recommends.
        ``record_norms=True`` adds global gradient/update L2 norms to the
        step metrics (two cheap reductions) — the flight recorder persists
        them and the obs sentry's SNT002 non-finite-norm check watches
        them (docs/observability.md).
        ``compute_dtype="bfloat16"`` is the mixed-precision master-weight
        policy: floating-point parameters are cast to the compute dtype on
        entry to the loss (XLA fuses the casts into the consuming
        matmuls, so the MXU sees bf16 operands and param HBM reads
        halve), while the stored parameters, gradients, and optimizer
        update stay full fp32 — autodiff through the cast upcasts the
        gradient automatically. Zoo models already cast activations
        internally; this knob brings user-supplied fp32 models onto the
        same MXU contract without touching their code.
        """
        opt_spec, tx = _resolve_optimizer(optimizer)

        model_item = ModelItem.from_params(
            params,
            # "custom" (raw optax) flows through so planners know the slot
            # count is unknown and must assume the conservative worst case.
            optimizer_spec=opt_spec,
            loss_fn=loss_fn,
            example_batch=example_batch,
            sparse_names=sparse_names,
            expert_names=expert_names,
        )
        strategy = self._build_or_load_strategy(model_item)
        compiled = StrategyCompiler(model_item).compile(strategy)
        if compute_dtype is not None:
            # Wrap AFTER ModelItem capture (like remat below): sparse
            # detection must run on the bare loss_fn. Only floating leaves
            # cast — integer tables/embedding ids pass through. BEFORE the
            # async route, so mixed precision composes with sync=False
            # (workers compute in bf16, the server's master weights stay
            # fp32) and an invalid dtype fails fast on every path.
            loss_fn = _cast_compute(loss_fn, compute_dtype)
        async_trainer = self._maybe_build_async(
            compiled, model_item, loss_fn, tx, has_aux=has_aux,
            host_offload=host_offload, grad_accum_steps=grad_accum_steps,
            remat=remat)
        if async_trainer is not None:
            return async_trainer
        plan = GraphTransformer(
            compiled, model_item, self.mesh, host_offload=host_offload
        ).transform()
        logging.debug("sharding plan:\n%s", plan.describe())
        if remat:
            # Wrap AFTER ModelItem capture: _trace_analysis cannot see through
            # a remat2 equation, so sparse-update detection must run on the
            # bare loss_fn.
            loss_fn = jax.checkpoint(loss_fn, policy=_remat_policy(remat))
        step = DistributedTrainStep(
            plan, loss_fn, tx, has_aux=has_aux, donate_state=donate_state,
            grad_accum_steps=grad_accum_steps, record_norms=record_norms,
        )
        self._built, self._strategy, self._model_item = step, compiled, model_item
        return step

    # -------------------------------------------------------------- async
    def _maybe_build_async(self, compiled, model_item, loss_fn, tx, *,
                           has_aux, host_offload, grad_accum_steps, remat):
        """Route ``sync=False`` strategies to the host-driven async PS.

        The reference's asynchronous training mode (synchronizers.proto:28,
        ps_synchronizer.py:553-630) has no SPMD rendering — lockstep jitted
        programs cannot express "a worker that doesn't wait" — so the
        asynchrony lives in the host dispatch schedule instead
        (runtime/async_ps.py, docs/async_ps.md). Returns None for fully
        synchronous strategies.
        """
        from autodist_tpu.strategy.ir import PSSynchronizer
        from autodist_tpu.strategy.ir import iter_synchronizers as _syncs

        async_nodes = [
            n for n in compiled.node_config
            if any(isinstance(s, PSSynchronizer) and not s.sync
                   for s in _syncs(n))
        ]
        if not async_nodes:
            return None
        if len(async_nodes) != len(compiled.node_config):
            raise NotImplementedError(
                "strategies mixing sync and async synchronizers have no "
                "rendering: under the host-driven async loop every "
                "variable's update applies per push. Make the strategy "
                "uniformly sync or uniformly async (sync=False)."
            )
        unsupported = []
        if host_offload:
            unsupported.append("host_offload")
        if grad_accum_steps != 1:
            unsupported.append("grad_accum_steps")
        if remat:
            unsupported.append("remat")
        if unsupported:
            raise NotImplementedError(
                f"async PS (sync=False) does not compose with "
                f"{', '.join(unsupported)}; these knobs belong to the SPMD "
                f"lowering path."
            )
        from autodist_tpu.runtime.async_ps import AsyncPSTrainer

        staleness = max(
            (s.staleness for n in async_nodes for s in _syncs(n)
             if isinstance(s, PSSynchronizer)),
            default=0,
        )
        n_workers = max(1, len(compiled.graph_config.replicas))
        trainer = AsyncPSTrainer(
            loss_fn, tx, n_workers=n_workers, staleness=staleness,
            has_aux=has_aux,
        )
        self._built, self._strategy, self._model_item = (
            trainer, compiled, model_item)
        logging.info(
            "sync=False strategy: routed to host-driven AsyncPSTrainer "
            "(%d workers, staleness=%d)", n_workers, staleness)
        return trainer

    # ------------------------------------------------------------ inference
    def build_inference(
        self,
        params: Any,
        apply_fn: Optional[Callable] = None,
        decode_model=None,
        checkpoint: Optional[str] = None,
        n_slots: int = 8,
        max_len: Optional[int] = None,
        page_len: Optional[int] = None,
        n_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        draft_params: Any = None,
        draft_decode_model=None,
        draft_checkpoint: Optional[str] = None,
        spec_k: int = 4,
        draft_n_pages: Optional[int] = None,
        prefix_cache: bool = False,
    ):
        """Compile a sharded *inference* engine over this AutoDist's mesh —
        the serving counterpart of :meth:`build` (same capture → strategy →
        compile → lower pipeline, a forward/decode step instead of a train
        step; docs/serving.md).

        ``apply_fn(params, batch)`` enables one-shot inference
        (:meth:`~autodist_tpu.serve.InferenceEngine.infer`); ``decode_model``
        (e.g. ``autodist_tpu.models.transformer.decode_model(cfg)``) enables
        autoregressive paged KV-cache decode behind the continuous batcher:
        ``n_slots`` decode rows over a fixed pool of ``page_len``-token KV
        pages (``n_pages`` overrides the pool size; the default funds it
        from this AutoDist's ResourceSpec HBM headroom), with prompts
        prefilled in ``prefill_chunk``-token chunks (default: one page)
        interleaved with decode. ``checkpoint`` restores parameters from a
        ``checkpoint/saver.py`` checkpoint directly into the plan's
        shardings (partial parallel reads — no host ever holds the full
        logical arrays). The strategy comes from this AutoDist's builder
        with the usual chief-builds/workers-receive handoff, so a fleet
        serves one consistent plan.

        ``draft_params`` + ``draft_decode_model`` turn the engine into a
        :class:`~autodist_tpu.serve.spec.SpecDecodeEngine` — speculative
        decode with a small draft model (same mesh, its own ShardingPlan
        compiled through the same builder, its own paged KV pool of
        ``draft_n_pages``; ``draft_checkpoint`` restores it through the
        same Saver path), proposing ``spec_k`` tokens per slot per round
        with lossless greedy verification (docs/serving.md § speculative
        decode).

        ``prefix_cache=True`` enables copy-on-write prefix sharing over
        the page pool (``serve/prefix.py``): admissions whose prompts
        share cached block prefixes map onto the same physical pages and
        prefill only their suffix (docs/serving.md § prefix sharing); a
        spec-decode engine shares one tree across its target and draft
        pools.
        """
        from autodist_tpu.serve.engine import InferenceEngine

        model_item = ModelItem.from_params(params)
        strategy = self._build_or_load_strategy(model_item)
        compiled = StrategyCompiler(model_item).compile(strategy)
        plan = GraphTransformer(compiled, model_item, self.mesh).transform()
        logging.debug("inference sharding plan:\n%s", plan.describe())
        if checkpoint is not None:
            params = InferenceEngine.restore_params(checkpoint, params, plan)
        engine_kwargs = dict(
            n_slots=n_slots, page_len=page_len, n_pages=n_pages,
            prefill_chunk=prefill_chunk, max_len=max_len,
            resource_spec=self.resource_spec,
            prefix_cache=prefix_cache,
        )
        if draft_params is not None:
            from autodist_tpu.serve.spec import (
                SpecDecodeEngine, build_draft_plan)

            # The draft rides the same builder over the same mesh but
            # skips the strategy-id handoff: its build is deterministic
            # per (builder, model, spec), so every process of a fleet
            # derives the identical draft plan locally while the TARGET
            # plan still travels the normal chief->worker channel.
            draft_plan = build_draft_plan(
                draft_params, self.mesh, resource_spec=self.resource_spec,
                strategy_builder=self.strategy_builder)
            if draft_checkpoint is not None:
                draft_params = InferenceEngine.restore_params(
                    draft_checkpoint, draft_params, draft_plan)
            engine = SpecDecodeEngine(
                params, plan, draft_params, draft_plan,
                apply_fn=apply_fn, decode_model=decode_model,
                draft_decode_model=draft_decode_model, spec_k=spec_k,
                draft_n_pages=draft_n_pages, **engine_kwargs)
        else:
            engine = InferenceEngine(
                params, plan, apply_fn=apply_fn, decode_model=decode_model,
                **engine_kwargs)
        self._strategy, self._model_item = compiled, model_item
        return engine

    # ------------------------------------------------------------- pipeline
    def build_pipeline(
        self,
        stage_fn: Callable,
        loss_head: Callable,
        n_microbatches: int,
        optimizer: Union[OptimizerSpec, optax.GradientTransformation, None] = None,
        donate_state: bool = True,
    ):
        """Pipeline-parallel train step over this AutoDist's mesh.

        The pipelined counterpart of :meth:`build` for stage-stack models
        (``stage_fn(stage_params, h) -> h`` shape-preserving, params given
        stacked ``[S, ...]`` to ``init``): returns a
        :class:`~autodist_tpu.parallel.PipelineTrainStep` with the same
        ``init / __call__ / run / evaluate`` contract, running the
        interleaved-1F1B schedule over the mesh ``pipe`` axis while the
        batch shards over ``data`` (beyond-reference capability;
        SURVEY.md §2.2 lists pipeline parallelism as absent upstream).
        """
        from autodist_tpu.parallel import PipelineTrainStep

        _, tx = _resolve_optimizer(optimizer)
        return PipelineTrainStep(
            stage_fn, loss_head, tx, n_microbatches,
            mesh=self.mesh, donate_state=donate_state,
        )

    # -------------------------------------------------------------- elastic
    def elastic_rebuild(
        self,
        loss_fn: Callable,
        params: Any,
        example_batch: Any = None,
        devices: Optional[Sequence] = None,
        optimizer: Union[OptimizerSpec, optax.GradientTransformation, None] = None,
        **recompile_kwargs,
    ):
        """Elastic restart onto the SURVIVING devices: re-derive the
        resource spec from whatever is still alive, recompile the
        Strategy→ShardingPlan on the resized mesh, and restore the newest
        integrity-verified snapshot into the new shardings
        (``ft/elastic.py``; requires ``fault_tolerance=FTConfig(...)``).

        Returns ``(step, state)``. This AutoDist's ``resource_spec`` /
        ``mesh`` are repointed at the surviving cluster so subsequent
        ``build``/``build_inference`` calls compile for the same resized
        mesh the restored state lives on.
        """
        if self.ft is None:
            raise RuntimeError(
                "elastic_rebuild needs fault tolerance enabled: construct "
                "AutoDist(fault_tolerance=FTConfig(...))")
        from autodist_tpu.ft.elastic import surviving_resource_spec

        devices = list(devices) if devices is not None else jax.devices()
        recompile_kwargs.setdefault("mesh_axes", self.mesh_axes)
        step, state = self.ft.elastic.resume(
            loss_fn, params, example_batch,
            devices=devices,
            strategy_builder=self.strategy_builder,
            optimizer=optimizer,
            spec_template=self.resource_spec,
            **recompile_kwargs,
        )
        self.resource_spec = surviving_resource_spec(
            devices, template=self.resource_spec)
        self._mesh = step.plan.mesh
        self._built = step
        return step, state

    # ----------------------------------------------------------------- tune
    def tune(
        self,
        loss_fn: Callable,
        params: Any,
        example_batch: Any,
        candidates: Optional[Sequence] = None,
        window: int = 8,
        **build_kwargs,
    ) -> DistributedTrainStep:
        """Measured strategy selection: build each candidate strategy, time
        ``_TUNE_TRIAL_WINDOWS`` (4) back-to-back device-side windows of
        ``window`` real training steps each (plus one warmup window), keep
        the fastest.

        The analytical :class:`~autodist_tpu.strategy.cost_model.CostModel`
        behind :class:`~autodist_tpu.strategy.Auto` *predicts*; ``tune``
        *measures* — the empirical complement the reference project pointed
        at (its performance page shows the best strategy differs per model,
        ``docs/usage/performance.md:14``, but ships no way to find it).
        Compiles every candidate, so expect ~N× the normal build latency;
        infeasible or non-compiling candidates are skipped with a warning.

        ``candidates``: ``[(name, StrategyBuilder), ...]``; defaults to the
        Auto dense slate (+ Parallax, which degenerates to AllReduce on
        dense-only models). On a multi-process fleet every process times
        every candidate in lockstep (the candidates' collectives keep the
        fleet synchronized), the CHIEF's measurements decide, and the
        winner's index is broadcast over the runtime — so the election is
        both *measured* and fleet-consistent, the same broadcast contract
        the strategy handoff uses (``_sync_strategy_multihost``).
        """
        import time

        from autodist_tpu.strategy.cost_model import CostModel, candidate_slate

        if candidates is None:
            candidates = candidate_slate()
        multi = jax.process_count() > 1
        if multi:
            # The feed contract depends only on (batch, process count) —
            # fail it once, loudly, before paying any candidate builds.
            self._check_fleet_batch(example_batch)

        results = []  # (name, dt) per candidate; inf when it failed here
        predicted = {}  # name -> analytical StrategyCost of the strategy timed
        best = None   # single-process: (name, dt, builder, step, strategy, item)
        for name, builder in candidates:
            self.strategy_builder = builder
            try:
                step = self.build(loss_fn, params, example_batch, **build_kwargs)
                if multi:
                    # Already device-resident global arrays (assembled via
                    # plan.global_batch_from_local).
                    bench_batch = self._fleet_bench_batch(step.plan, example_batch)
                else:
                    # Pin ONCE in HBM, synced before the warmup run
                    # (mirroring bench.py's measure()): re-uploading a host
                    # batch per window would serialize the transfer into
                    # the timed region and skew calibration absolutes.
                    bench_batch = jax.device_put(
                        example_batch, step.plan.batch_shardings(example_batch))
                jax.block_until_ready(bench_batch)
                state = step.init(params)
                state, _ = step.run(state, bench_batch, window)  # compile+warm
                jax.block_until_ready(state.params)
                # Back-to-back windows with one trailing sync: run() returns
                # immediately and the programs pipeline on the device, so
                # the host round trip is paid once, not per window — per
                # window it would bias every candidate's absolute ms/step
                # equally (fair ranking, skewed calibration).
                t0 = time.perf_counter()
                for _ in range(_TUNE_TRIAL_WINDOWS):
                    state, _ = step.run(state, bench_batch, window)
                jax.block_until_ready(state.params)
                dt = (time.perf_counter() - t0) / (_TUNE_TRIAL_WINDOWS * window)
            except Exception as e:  # noqa: BLE001 - candidate-level isolation
                # Fleet alignment: chief-only build failures ship a sentinel
                # through the strategy broadcast so every process raises (and
                # lands here) for the same candidate; compile/run failures
                # are SPMD-deterministic (same program everywhere). Either
                # way the results lists stay index-aligned, and the
                # election below only considers candidates that succeeded
                # on every process.
                logging.warning("tune: candidate %s failed (%s); skipped", name, e)
                results.append((name, float("inf")))
                continue
            finally:
                # Free this candidate's device train state before the next
                # one's init(): holding both transiently doubles HBM and
                # would make near-capacity models fail every candidate after
                # the first (electing the first, not the fastest).
                state = None  # noqa: F841
            logging.info("tune: %-16s %.3f ms/step", name, dt * 1e3)
            results.append((name, dt))
            try:
                # Cost the exact strategy just timed (self._strategy is the
                # one build() compiled — on a fleet, the chief-broadcast one).
                predicted[name] = CostModel(
                    self._model_item, self.resource_spec
                ).strategy_cost(self._strategy)
            except Exception:  # noqa: BLE001 - calibration is best-effort
                pass
            if multi:
                # The winner is rebuilt after the election; holding every
                # candidate's compiled programs would waste HBM meanwhile.
                step = None  # noqa: F841
            elif best is None or dt < best[1]:
                # Keep only the running best — a losing step's compiled
                # device programs are dead weight for the rest of the sweep.
                best = (name, dt, builder, step, self._strategy, self._model_item)

        self._record_calibration(results, predicted)

        if multi:
            from jax.experimental import multihost_utils

            dts = np.array([dt for _, dt in results], np.float64)
            # Fleet-wide election in one collective: allgather every
            # process's timing vector (identical result everywhere), keep
            # only candidates that succeeded on EVERY process, then pick
            # the chief's fastest among those. Deterministic on all
            # processes with no follow-up broadcast, and a candidate that
            # failed anywhere can never be elected — so the winner rebuild
            # below cannot diverge. (A failure *inside* a candidate's
            # collectives still hangs like any SPMD program would; this
            # protects the host-side stages around them.)
            all_dts = np.asarray(
                multihost_utils.process_allgather(dts)
            ).reshape(jax.process_count(), len(results))
            fleet_valid = np.isfinite(all_dts).all(axis=0)
            if not fleet_valid.any():
                raise RuntimeError(
                    "tune(): every candidate strategy failed to build/run "
                    "on at least one process")
            chief_dts = np.where(fleet_valid, all_dts[0], np.inf)
            idx = int(np.argmin(chief_dts))
            best_name = results[idx][0]
            logging.info(
                "tune (fleet) selected %s — chief-measured; local %.3f ms/step",
                best_name, results[idx][1] * 1e3,
            )
            self._record_tune_obs(results, best_name)
            self.strategy_builder = dict(candidates)[best_name]
            return self.build(loss_fn, params, example_batch, **build_kwargs)

        if best is None:
            raise RuntimeError("tune(): every candidate strategy failed to build/run")
        best_name, best_dt, best_builder, best_step, best_strategy, best_item = best
        logging.info("tune selected %s (%.3f ms/step)", best_name, best_dt * 1e3)
        self._record_tune_obs(results, best_name)
        # Leave every selection-visible surface pointing at the WINNER, not
        # the last candidate tried: the builder (future build() calls) and
        # the strategy id env (coordinator-relaunched workers load by it).
        self.strategy_builder = best_builder
        os.environ[ENV.AUTODIST_STRATEGY_ID.name] = best_strategy.id
        self._built, self._strategy, self._model_item = (
            best_step, best_strategy, best_item,
        )
        return best_step

    def _record_tune_obs(self, results, selected: str) -> None:
        """Auditable strategy selection: every candidate's name and measured
        seconds (inf = failed) plus the winner land in the process metrics
        registry and the obs span timeline, and ride
        ``last_tune_results["measured"]/["selected"]`` — so *why this
        strategy* is answerable after the fact from any export surface,
        not just the tune call's log lines. Best-effort: never fails a tune.
        """
        import time as _time

        try:
            from autodist_tpu import metrics as M
            from autodist_tpu.obs import spans as _spans

            reg = M.registry
            reg.counter("tune_runs_total").inc()
            reg.gauge("tune_candidates").set(len(results))
            now = _time.time()
            for name, dt in results:
                failed = not (dt < float("inf"))
                if not failed:
                    reg.gauge(f"tune_measured_ms_{name}").set(dt * 1e3)
                _spans.add_span(
                    "tune.candidate", now, 0.0 if failed else dt,
                    candidate=name, failed=failed,
                    selected=(name == selected))
            sel_dt = dict(results).get(selected)
            if sel_dt is not None and sel_dt < float("inf"):
                reg.gauge("tune_selected_ms").set(sel_dt * 1e3)
            self.last_tune_results = {
                **(self.last_tune_results or {}),
                "measured": {n: dt for n, dt in results},
                "selected": selected,
            }
        except Exception:  # noqa: BLE001 - diagnostics must not break tune
            logging.warning("tune: obs audit recording failed", exc_info=True)

    def _record_calibration(self, results, predicted) -> None:
        """Close the predict→measure loop (VERDICT r1 next #10): pair each
        candidate's measured step time with the analytical cost of the
        strategy actually timed (computed in the sweep loop), fit a
        :class:`~autodist_tpu.strategy.cost_model.Calibration`
        (measured ≈ base + scale × predicted), and persist it so
        ``explain`` can show calibrated absolute step times next to the
        analytical column. On a fleet, only the chief writes (atomic
        replace inside ``Calibration.save``), so the persisted fit is the
        chief's timings — the ones that decide elections. Best-effort:
        never fails a tune."""
        try:
            from autodist_tpu.strategy.cost_model import Calibration

            meas, pred, table = [], [], {}
            for name, dt in results:
                cost = predicted.get(name)
                if cost is None or not (dt < float("inf")):
                    continue
                meas.append(dt)
                pred.append(cost.total_s)
                table[name] = {"measured_s": dt, "predicted_s": cost.total_s}
            if not meas:
                return
            device = ""
            try:
                device = str(jax.devices()[0].device_kind)
            except Exception:  # noqa: BLE001
                pass
            calib = Calibration.fit(pred, meas, device=device)
            path = calib.save() if jax.process_index() == 0 else None
            plan_calib = None
            if jax.process_index() == 0:
                # The same sweep feeds the planner's per-topology
                # per-component calibration (docs/planner.md): every
                # measured candidate becomes a CalibrationRecord, so a
                # later `strategy_builder="plan"` run prices THIS topology
                # instead of nominal constants.
                try:
                    from autodist_tpu.plan.calibrate import (
                        CalibrationRecord, calibrate_from_records)

                    plan_calib = calibrate_from_records(
                        [CalibrationRecord.from_cost(
                            predicted[n], dt, name=n)
                         for n, dt in results
                         if n in predicted and dt < float("inf")],
                        self.resource_spec, device_kind=device)
                except Exception:  # noqa: BLE001 - planner feed is optional
                    logging.warning(
                        "tune: plan calibration recording failed",
                        exc_info=True)
            self.last_tune_results = {
                "table": table,
                "calibration": calib,
                "calibration_path": path,
                "plan_calibration": plan_calib,
            }
            logging.info(
                "tune calibration: measured ≈ %.3fms + %.2f × predicted "
                "(%d candidates, %s)%s",
                calib.base_s * 1e3, calib.scale, calib.n_points, device,
                f" -> {path}" if path else "",
            )
        except Exception as e:  # noqa: BLE001 - diagnostics must not break tune
            logging.warning("tune: calibration recording failed (%s)", e)

    @staticmethod
    def _check_fleet_batch(example_batch) -> None:
        """Pre-sweep validation of the fleet feed contract (see
        :meth:`_fleet_bench_batch`), so a bad batch fails once with the
        real cause instead of failing every candidate after a full build."""
        pc = jax.process_count()
        for leaf in jax.tree.leaves(example_batch):
            shape = tuple(np.shape(leaf))
            # Broadcast leaves (is_broadcast_leaf — masks, per-feature
            # constants) replicate and are exempt from the per-process
            # divisibility contract.
            if not is_broadcast_leaf(shape) and shape[0] % pc != 0:
                raise ValueError(
                    f"tune() on a {pc}-process fleet needs every batched "
                    f"leaf's leading dim divisible by {pc}; got {shape}"
                )

    @staticmethod
    def _fleet_bench_batch(plan: ShardingPlan, example_batch):
        """Global example batch → fleet-fed global arrays for timing.

        On a multi-process fleet a raw host batch cannot be fed to a
        sharded jit (numpy + non-addressable shardings is rejected); the
        feed contract is per-process local slices assembled via
        ``plan.global_batch_from_local``. Every process holds the same
        global example, so each takes its row slice.
        (:meth:`_check_fleet_batch` owns the divisibility validation.)
        """
        pi, pc = jax.process_index(), jax.process_count()
        AutoDist._check_fleet_batch(example_batch)

        # The broadcast mask comes from the GLOBAL example shapes — after
        # slicing, a genuinely batched leaf with global batch == pc also has
        # local leading dim 1 and could not be told apart.
        broadcast = jax.tree.map(
            lambda x: is_broadcast_leaf(np.shape(x)), example_batch)

        def to_local(x, is_bcast):
            arr = np.asarray(x)
            # Broadcast leaves stay whole on every process; slicing them
            # would hand k=0 rows to each host.
            if not is_bcast:
                k = arr.shape[0] // pc
                return arr[pi * k:(pi + 1) * k]
            return arr

        return plan.global_batch_from_local(
            jax.tree.map(to_local, example_batch, broadcast), broadcast)

    # ------------------------------------------------------------- accessors
    @property
    def strategy(self) -> Optional[Strategy]:
        return self._strategy

    @property
    def plan(self) -> Optional[ShardingPlan]:
        # AsyncPSTrainer has no sharding plan (host-driven engine): None,
        # same as "not built yet", so function()'s guidance path still fires.
        return getattr(self._built, "plan", None)

    @property
    def model_item(self) -> Optional[ModelItem]:
        return self._model_item

    # ------------------------------------------------------------- tf2-style
    def function(self, fn: Callable) -> Callable:
        """``autodist.function`` analog (autodist.py:269-289): wrap an
        arbitrary step function so its array arguments are sharded along the
        mesh data axis on first call, then executed jitted.

        Unlike the TF2 path (which replayed ndarrays through placeholders),
        JAX functions are already traceable — this only adds sharding
        constraints + compile caching.
        """
        jitted = jax.jit(fn)

        def wrapper(*args):
            plan = self.plan
            if plan is None:
                raise RuntimeError("call AutoDist.build(...) before .function(...)")
            args = jax.device_put(args, plan.batch_shardings(args, strict=False))
            return jitted(*args)

        return wrapper

    @contextmanager
    def scope(self):
        """Model-definition scope (autodist.py:309-322). JAX needs no graph
        capture; the scope exists for lifecycle parity and future hooks."""
        yield self
