"""Strategy lowering (L2): Strategy IR → sharding plan → compiled train step.

This is the TPU-native replacement for the reference's entire kernel layer —
``GraphTransformer`` + partitioner + replicator + synchronizers
(``/root/reference/autodist/kernel/graph_transformer.py:55-92``,
``partitioner.py``, ``replicator.py``, ``synchronization/*.py``). Where the
reference rewrote a TF graph op-by-op (replicating it per device, splicing
accumulators, queues and collective ops), this layer emits
``jax.sharding.NamedSharding`` annotations per variable and lets XLA GSPMD
insert the collectives:

- ``AllReduceSynchronizer`` → parameter replicated over the mesh; with the
  batch sharded over the "data" axis, autodiff of the mean loss makes XLA
  emit the gradient all-reduce over ICI (the ``lax.psum`` path) — replacing
  the reference's explicit ``collective_ops.all_reduce`` splicing
  (``all_reduce_synchronizer.py:100-126``).
- ``PSSynchronizer`` (unpartitioned, dense) → parameter replicated, but
  optimizer slots *sharded*: weight-update sharding (the ZeRO-style scheme of
  arXiv 2004.13336), so the "server-side" update computation and optimizer
  memory are distributed exactly where the reference placed them on PS
  devices. ``reduction_destination`` degrees of freedom collapse onto mesh
  coordinates.
- ``partitioner: "1,k,1"`` → the parameter itself is sharded on the active
  axis (``NamedSharding``); XLA all-gathers on use and reduce-scatters the
  gradient — a *true* tensor-parallel upgrade of the reference's
  variable-only partitioning (``docs/design/kernels.md:11-17``).
- sparse-update variables (embeddings) → row-sharded on axis 0 under both
  PS and AllReduce, keeping the PS sparse-path capability
  (``ps_synchronizer.py:473-532``) and the sparse-AllReduce wire contract
  (``all_reduce_synchronizer.py:129-169``: sync cost scales with touched
  rows) with gather/scatter collectives instead of
  SparseConditionalAccumulators / collective all-gathers.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, replace as _dc_replace
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.chaos import hooks as chaos_hooks
from autodist_tpu.kernel import bucketing
from autodist_tpu.kernel.mesh import data_axis
from autodist_tpu.obs import recorder as flight
from autodist_tpu.obs import spans as obs_spans
from autodist_tpu.model_item import ModelItem, VarItem, _path_to_name
from autodist_tpu.strategy.ir import (
    AllReduceSynchronizer,
    NodeConfig,
    PSSynchronizer,
    Strategy,
)
from autodist_tpu.utils import is_broadcast_leaf, logging


class SyncKind(Enum):
    ALL_REDUCE = "all_reduce"
    PS = "ps"


@dataclass
class VarPlan:
    """Resolved per-variable lowering decision."""

    var: VarItem
    kind: SyncKind
    pspec: P                       # parameter sharding
    update_pspec: P                # optimizer-slot / weight-update sharding
    compressor: str = "NoneCompressor"
    group: int = 0
    staleness: int = 0
    reduction_destination: str = ""
    local_replication: bool = False
    num_shards: int = 1
    # Store the parameter (and its optimizer slots) in pinned host memory,
    # streaming through HBM inside the step — the TPU rendering of the
    # reference parking PS variables on host CPUs (ps_strategy.py:38-55).
    offload: bool = False
    # Per-shard PS destination table (reference strategy.proto:46-50, as
    # emitted by the PartitionedPS load balancer): shard i of the variable
    # reduces at shard_destinations[i]. Under SPMD the *identity* of each
    # destination collapses onto mesh coordinates (shard i lives at position
    # i of the shard axis — uniform by construction), but the table is part
    # of the plan: explain prints it, the cost model prices it, and
    # ``host_offload="from_strategy"`` reads the destinations' device type
    # to pick the memory kind.
    shard_destinations: Tuple[str, ...] = ()
    # Pad-and-mask sharding (SURVEY §7.4 item 5): when a requested shard
    # axis divides no axis evenly (e.g. GPT-2's prime vocab 50257), the
    # parameter is STORED zero-padded to this shape so XLA's equal-shard
    # requirement holds; the loss sees the sliced logical view, so padded
    # entries get zero gradients and elementwise optimizers keep them at
    # zero. None = storage is the logical shape.
    storage_shape: Optional[Tuple[int, ...]] = None
    # ZeRO-1 weight-update sharding for an AllReduce var (arXiv 2004.13336,
    # strategy.ir.AllReduceSynchronizer.shard_update): param replicated,
    # optimizer slots + update sharded per ``update_pspec`` over the data
    # axis, gradient sync rendered reduce-scatter → sharded update →
    # all-gather. True only when the rendering is ACTIVE (update_pspec is
    # genuinely sharded) — the step keys its manual grad sync off this.
    shard_update: bool = False
    # Declared quiet degradations: why a requested capability (today:
    # shard_update) did NOT render for this var, in the shared
    # ``kernel.degrade.zero1_degradation_reasons`` vocabulary. The static
    # analyzer (autodist_tpu.analysis) treats exactly these as declared;
    # a plan whose flags disagree with the predicate is a finding.
    degradations: Tuple[str, ...] = ()


@struct.dataclass
class TrainState:
    """Minimal functional train state (the reference's mutable-graph state —
    variables + optimizer slots — as an explicit pytree). ``.replace`` comes
    from the struct.dataclass decorator. ``comp_state`` carries gradient-
    compressor persistence (EF residuals per data shard, PowerSGD bases);
    empty dict when no compressor is active."""

    step: jax.Array
    params: Any
    opt_state: Any
    comp_state: Any = struct.field(default_factory=dict)
    # Bounded-staleness gradient buffers ({var: [K, ...]}): the SPMD
    # rendering of the reference's staleness queues (ps_synchronizer.py:
    # 384-455) — gradients apply with a fixed K-step delay instead of a
    # nondeterministic ≤K-step one. Empty when no var has staleness.
    stale_state: Any = struct.field(default_factory=dict)


def _spec_with_axis(rank: int, dim: int, mesh_axis: str) -> P:
    entries: List[Optional[str]] = [None] * rank
    entries[dim] = mesh_axis
    return P(*entries)


def _is_cpu_device(dest: str) -> bool:
    """True when a DeviceSpec string (``host:TYPE:index``) names a host CPU.

    Delegates the parse to :class:`resource_spec.DeviceSpec` so there is one
    implementation of the device-string grammar; unparseable destinations
    read as non-CPU (stay in HBM) rather than raising — a strategy artifact
    with a malformed destination should still lower.
    """
    from autodist_tpu.resource_spec import DeviceSpec, DeviceType

    try:
        return DeviceSpec.from_string(dest).device_type is DeviceType.CPU
    except (ValueError, KeyError):
        return False


def _memory_kinds_supported(mesh: Mesh) -> bool:
    """True when the runtime can stream pinned-host leaves inside jit.

    Requires (a) a pinned_host memory space, and (b) a compile path that
    accepts in-jit memory-space transfers: the TPU toolchain, or any
    single-device mesh (the SPMD partitioner — which rejects
    ``annotate_device_placement`` custom calls — only runs multi-device).
    """
    dev = mesh.devices.flat[0]
    if "pinned_host" not in {m.kind for m in dev.addressable_memories()}:
        why = "no pinned_host memory space"
    elif dev.platform != "tpu":
        # The CPU runtime has no annotate_device_placement kernel and the
        # non-TPU SPMD partitioner rejects the custom call.
        why = "in-jit host streaming needs the TPU toolchain"
    else:
        return True
    logging.warning("host offload requested but unsupported (%s); disabled",
                    why)
    return False


class GraphTransformer:
    """Lower a compiled Strategy over a mesh into a :class:`ShardingPlan`.

    Keeps the reference's pass-manager name (graph_transformer.py:45-92); the
    passes here are sharding-assignment rules instead of graph rewrites.
    """

    #: host_offload modes: False (never), True (every PS variable), or
    #: "from_strategy" (PS variables whose reduction destination — node- or
    #: shard-level — names a host CPU device, the reference's literal
    #: placement; ps_strategy.py:38-55).
    OFFLOAD_MODES = (False, True, "from_strategy")

    def __init__(
        self,
        strategy: Strategy,
        model_item: ModelItem,
        mesh: Mesh,
        host_offload: "bool | str" = False,
    ):
        if host_offload not in self.OFFLOAD_MODES:
            raise ValueError(
                f"host_offload={host_offload!r}: expected one of "
                f"{self.OFFLOAD_MODES}"
            )
        self.strategy = strategy
        self.model_item = model_item
        self.mesh = mesh
        if host_offload and not _memory_kinds_supported(mesh):
            host_offload = False
        self.host_offload = host_offload

    def transform(self) -> "ShardingPlan":
        from autodist_tpu.obs import spans as _spans

        t_wall, t0 = time.time(), time.perf_counter()
        plans: Dict[str, VarPlan] = {}
        for node in self.strategy.node_config:
            var = self.model_item.var(node.var_name)
            plans[var.name] = self._lower_node(node, var)
        # Non-trainable vars: replicated.
        for var in self.model_item.variables:
            if var.name not in plans:
                plans[var.name] = VarPlan(
                    var=var, kind=SyncKind.ALL_REDUCE, pspec=P(), update_pspec=P()
                )
        # Retroactive span (obs timeline): how long lowering took and how
        # many vars carry the zero1 reduce-scatter/all-gather rendering.
        _spans.add_span(
            "lowering.transform", t_wall, time.perf_counter() - t0,
            n_nodes=len(self.strategy.node_config),
            shard_update_vars=sum(1 for p in plans.values() if p.shard_update),
        )
        return ShardingPlan(
            mesh=self.mesh, var_plans=plans,
            bucket_bytes=int(getattr(
                self.strategy.graph_config, "bucket_bytes", 0) or 0),
        )

    # ------------------------------------------------------------------ rules
    def _shard_axis_name(self) -> str:
        """Mesh axis carrying variable partitioning: the "model" axis when it
        is non-trivial, else the data axis (ZeRO-style sharding)."""
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        model_ax = const.MESH_AXIS_MODEL
        if shape.get(model_ax, 1) > 1:
            return model_ax
        return data_axis(self.mesh)

    @staticmethod
    def _fold_part_config(node: NodeConfig) -> dict:
        """Fold per-shard sync configs (strategy.proto:46-50) into the plan.

        The reference rendered each shard of a partitioned variable as an
        independent variable with its own synchronizer, so shards could
        legitimately differ (partitioned_ps_strategy.py:104-121 gives each a
        different reduction destination). Under SPMD one variable lowers to
        ONE NamedSharding and one gradient wire, so the per-shard degrees of
        freedom fold: settings that must be uniform across a single wire
        (synchronizer kind, sync/staleness, compressor, local_replication)
        are validated uniform — heterogeneous values have no SPMD rendering
        and raise — and the uniform value *overrides* the node-level one
        (shard configs are the more specific contract). Exception: ``sync``
        is validated, never overridden — async PS is rejected loudly whether
        it appears at node or shard level (a shard-level ``sync=True`` does
        not resurrect an async node config).
        Per-shard destinations survive as the plan's ``shard_destinations``
        table. Per-shard ``group`` ids are advisory (see
        AllReduceSynchronizer.group) and are not required to agree.
        """
        parts = node.part_config
        folded: dict = {}
        if not parts:
            return folded
        if len(parts) != node.num_shards:
            # StrategyCompiler checks this too, but GraphTransformer also
            # lowers hand-built / deserialized strategies directly — a
            # mismatched table must not silently skew shard_destinations.
            raise ValueError(
                f"{node.var_name!r}: {len(parts)} part configs but "
                f"partitioner {node.partitioner!r} implies {node.num_shards}"
            )
        kinds = {type(p.synchronizer) for p in parts} | {type(node.synchronizer)}
        if len(kinds) > 1:
            raise ValueError(
                f"{node.var_name!r}: per-shard synchronizers mix "
                f"{sorted(k.__name__ for k in kinds)} — shards of one "
                f"variable share a single gradient wire under SPMD, so "
                f"heterogeneous synchronizer kinds have no rendering"
            )

        def uniform(field_name: str):
            vals = {getattr(p.synchronizer, field_name) for p in parts}
            if len(vals) > 1:
                raise ValueError(
                    f"{node.var_name!r}: per-shard {field_name} differs "
                    f"across shards ({sorted(map(str, vals))}) — one "
                    f"variable has one gradient wire under SPMD, so "
                    f"per-shard {field_name} must be uniform"
                )
            return vals.pop()

        if isinstance(node.synchronizer, PSSynchronizer):
            if not uniform("sync"):
                from autodist_tpu.strategy.base import check_sync_supported

                check_sync_supported(False)
            folded["staleness"] = uniform("staleness")
            folded["proxy"] = uniform("local_replication")
            folded["shard_destinations"] = tuple(
                p.synchronizer.reduction_destination for p in parts
            )
        else:
            # The schema has no "unset" sentinel for compressor, so a shard
            # table left at the default is indistinguishable from one that
            # explicitly chose NoneCompressor; treat default-valued parts as
            # deferring to the node-level choice (overriding would silently
            # strip an explicitly configured node-level compressor). A
            # non-default uniform part compressor wins as usual.
            part_comp = uniform("compressor")
            if part_comp != "NoneCompressor":
                folded["compressor"] = part_comp
            # Same default-ambiguity contract for shard_update (default
            # False): a uniform True overrides; uniform False defers to the
            # node level. One variable = one gradient wire, so a mixed
            # table raises in uniform().
            if uniform("shard_update"):
                folded["shard_update"] = True
        return folded

    def _lower_node(self, node: NodeConfig, var: VarItem) -> VarPlan:
        sync = node.synchronizer
        shard_ax = self._shard_axis_name()
        rank = len(var.shape)
        folded = self._fold_part_config(node)

        if isinstance(sync, AllReduceSynchronizer):
            kind = SyncKind.ALL_REDUCE
            compressor, group = folded.get("compressor", sync.compressor), sync.group
            staleness, dest, proxy = 0, "", False
            shard_update = folded.get("shard_update", sync.shard_update)
        else:
            assert isinstance(sync, PSSynchronizer)
            if not sync.sync:
                # Builders already reject async PS (base.check_sync_supported);
                # this guards hand-built / deserialized strategies so the knob
                # is never silently ignored.
                from autodist_tpu.strategy.base import check_sync_supported

                check_sync_supported(False)
            kind = SyncKind.PS
            compressor, group = "NoneCompressor", 0
            staleness = folded.get("staleness", sync.staleness)
            dest = sync.reduction_destination
            proxy = folded.get("proxy", sync.local_replication)
            shard_update = False

        mesh_shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n_shard = mesh_shape[shard_ax]

        def divisible(axis: int) -> bool:
            # jax NamedSharding requires exact divisibility; non-divisible
            # axes (incl. UnevenPartitionedPS's deliberate non-divisor shard
            # counts) fall back to replication until pad-and-mask sharding
            # lands (SURVEY.md §7.4 item 5).
            ok = var.shape[axis] % n_shard == 0 and var.shape[axis] >= n_shard
            if not ok:
                logging.debug(
                    "var %s axis %d (size %d) not divisible by mesh axis %s=%d; "
                    "replicating instead",
                    var.name, axis, var.shape[axis], shard_ax, n_shard,
                )
            return ok

        def padded_storage(axis: int) -> Tuple[int, ...]:
            shape = list(var.shape)
            shape[axis] = -(-shape[axis] // n_shard) * n_shard  # ceil multiple
            return tuple(shape)

        storage_shape: Optional[Tuple[int, ...]] = None
        expert_ax = const.MESH_AXIS_EXPERT
        n_expert = mesh_shape.get(expert_ax, 1)
        part_axis = node.active_partition_axis
        if (
            var.expert and rank > 0 and n_expert > 1
            and var.shape[0] % n_expert == 0
        ):
            # Expert parallelism: the leading (expert) dim shards over the
            # expert axis; the expert einsums then keep tokens local after
            # the all_to_all dispatch GSPMD inserts.
            pspec = _spec_with_axis(rank, 0, expert_ax)
            update_pspec = pspec
        elif part_axis is not None and rank > 0 and divisible(part_axis):
            # Explicit partitioning: shard the parameter itself.
            pspec = _spec_with_axis(rank, part_axis, shard_ax)
            update_pspec = pspec
        elif part_axis is not None and rank > 0 and self._fallback_axis(var, n_shard) is not None:
            # Requested axis not divisible (UnevenPartitionedPS deliberately
            # picks non-divisor counts, uneven_partition_ps_strategy.py:
            # 128-137). XLA shardings must divide evenly, so the *intent*
            # (shard this variable) is honored on the largest divisible
            # axis instead of falling all the way back to replication.
            fb = self._fallback_axis(var, n_shard)
            logging.debug(
                "var %s: partition axis %d (size %d) not divisible by %d; "
                "sharding axis %d instead",
                var.name, part_axis, var.shape[part_axis], n_shard, fb,
            )
            pspec = _spec_with_axis(rank, fb, shard_ax)
            update_pspec = pspec
        elif part_axis is not None and rank > 0 and var.shape[part_axis] > n_shard:
            # No axis divides at all (e.g. a prime-sized dim): pad-and-mask
            # on the requested axis — store the parameter zero-padded to the
            # next multiple of the mesh axis, shard that, slice the logical
            # view for compute (SURVEY §7.4 item 5). Axes smaller than the
            # mesh degree keep replicating: padding them yields degenerate
            # sub-element shards for pure overhead.
            storage_shape = padded_storage(part_axis)
            logging.debug(
                "var %s: no divisible axis for %d shards; padding axis %d "
                "%d→%d and sharding it",
                var.name, n_shard, part_axis, var.shape[part_axis],
                storage_shape[part_axis],
            )
            pspec = _spec_with_axis(rank, part_axis, shard_ax)
            update_pspec = pspec
        elif var.sparse_update and rank > 0 and divisible(0):
            # Sparse path (PS *and* AllReduce): row-sharded embedding
            # (axis 0). Under PS this is the reference's sharded sparse
            # table (ps_synchronizer.py:473-532); under AllReduce it is the
            # TPU rendering of the reference's sparse all-gather sync
            # (all_reduce_synchronizer.py:129-169) — GSPMD turns the lookup
            # and its scatter-add gradient into tokens-sized collectives,
            # so sync wire scales with touched rows, never with the table
            # (a dense psum of the full table gradient is what a replicated
            # sparse var would cost).
            pspec = _spec_with_axis(rank, 0, shard_ax)
            update_pspec = pspec
        elif var.sparse_update and rank > 0 and var.shape[0] > n_shard:
            # Sparse tables need axis-0 (row) sharding for the gather/scatter
            # path regardless of divisibility — pad the rows (the GPT-2
            # prime-vocab case: 50257 rows divide nothing).
            storage_shape = padded_storage(0)
            pspec = _spec_with_axis(rank, 0, shard_ax)
            update_pspec = pspec
        elif kind is SyncKind.PS and rank > 0:
            # Dense PS: the proxy-variable knob (reference
            # proxy_variable.py:96-114) picks the parameter's residency.
            # With a proxy the reference cached a worker-local replica →
            # replicated param + sharded weight update (ZeRO-1,
            # arXiv 2004.13336). Without one, workers read the variable
            # from the PS on every use → fully sharded param with
            # all-gather on use (ZeRO-3), the SPMD rendering of that
            # remote-read-per-step placement.
            update_pspec = self._weight_update_spec(var)
            pspec = P() if proxy else update_pspec
        elif kind is SyncKind.ALL_REDUCE and shard_update and rank > 0:
            # ZeRO-1 for an AllReduce var (shard_update capability): the
            # parameter stays replicated — its uses are untouched — but the
            # optimizer slots and the update computation shard over the
            # data axis. The step's manual grad sync renders the gradient
            # reduction as reduce-scatter and the fresh values all-gather
            # back (arXiv 2004.13336; docs/zero.md).
            pspec = P()
            update_pspec = self._weight_update_spec(var)
        else:
            pspec = P()
            update_pspec = P()

        # shard_update activation: the ONE shared degradation predicate
        # (kernel/degrade.py) decides whether the request renders — the same
        # predicate the cost model prices by and the static analyzer
        # (autodist_tpu.analysis) treats as the declared-degradation list.
        # The structural rendering above must agree with it; divergence is a
        # lowering bug and fails loudly rather than desyncing the three.
        su_active = False
        degradations: Tuple[str, ...] = ()
        if kind is SyncKind.ALL_REDUCE and shard_update:
            from autodist_tpu.kernel.degrade import zero1_degradation_reasons

            degradations = zero1_degradation_reasons(
                var.shape,
                sparse_update=var.sparse_update,
                expert=var.expert,
                part_axis=part_axis,
                compressor=compressor,
                n_data=mesh_shape.get(data_axis(self.mesh), 1),
                n_model=mesh_shape.get(const.MESH_AXIS_MODEL, 1),
                n_expert=mesh_shape.get(expert_ax, 1),
            )
            su_active = not degradations
            structural = pspec == P() and update_pspec != P()
            if su_active != (structural and "compressed" not in degradations):
                raise RuntimeError(
                    f"var {var.name!r}: zero1 rendering "
                    f"(pspec={pspec}, update={update_pspec}) disagrees with "
                    f"degradation_reasons={degradations!r} — "
                    f"kernel/degrade.py and _lower_node have drifted"
                )
            if structural and "compressed" in degradations:
                # The compressed wire psums the FULL gradient inside its
                # manual region (_manual_sync_grads) — there is no
                # reduce-scatter to render, and a silently ineffective
                # shard_update would desync pricing from the program. The
                # compressor is the explicit opt-in; it wins.
                logging.warning(
                    "var %s: shard_update ignored — compressor %s syncs the "
                    "full gradient (no reduce-scatter rendering); optimizer "
                    "state stays replicated for this var",
                    var.name, compressor,
                )
                update_pspec = P()
            elif degradations:
                logging.debug(
                    "var %s: shard_update has no effect (%s)",
                    var.name, ", ".join(degradations),
                )

        shard_dests = folded.get("shard_destinations", ())
        # Reference parity: PS destinations are host CPUs; offload is opt-in
        # (True = every PS var) because HBM residency is usually faster on
        # TPU, or destination-driven ("from_strategy" = follow the strategy's
        # placement: offload exactly the vars whose reduction destination
        # names a CPU device).
        if kind is SyncKind.PS and self.host_offload:
            if self.host_offload == "from_strategy":
                # Shard destinations are the more specific contract: when
                # the table exists it decides placement (the node-level
                # destination may be stale relative to it, and the cost
                # model prices the shard table too); empty shard entries
                # fall back to the node-level destination.
                dests = [d or dest for d in shard_dests] if shard_dests else [dest]
                offload = any(_is_cpu_device(d) for d in dests if d)
            else:
                offload = True
        else:
            offload = False
        return VarPlan(
            var=var,
            kind=kind,
            pspec=pspec,
            update_pspec=update_pspec,
            compressor=compressor,
            group=group,
            staleness=staleness,
            reduction_destination=dest,
            local_replication=proxy,
            num_shards=node.num_shards,
            offload=offload,
            shard_destinations=shard_dests,
            storage_shape=storage_shape,
            shard_update=su_active,
            degradations=degradations,
        )

    @staticmethod
    def _fallback_axis(var: VarItem, n_shard: int):
        """Largest axis evenly divisible by ``n_shard``, or None."""
        cands = [
            i for i, d in enumerate(var.shape) if d % n_shard == 0 and d >= n_shard
        ]
        return max(cands, key=lambda i: var.shape[i]) if cands else None

    def _weight_update_spec(self, var: VarItem) -> P:
        """Largest axis divisible by the data-axis size, else replicated."""
        ax_name = data_axis(self.mesh)
        n = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[ax_name]
        if n <= 1 or not var.shape:
            return P()
        candidates = [i for i, d in enumerate(var.shape) if d % n == 0 and d >= n]
        if not candidates:
            return P()
        best = max(candidates, key=lambda i: var.shape[i])
        return _spec_with_axis(len(var.shape), best, ax_name)


@dataclass(frozen=True)
class VarWire:
    """One variable's slice of the plan's promised collective wire — what
    the lowering COMMITS the compiled program to carrying for this var (see
    :meth:`ShardingPlan.promised_wire`). Consumed by the static analyzer's
    wire-conformance pass (``autodist_tpu.analysis.passes``)."""

    var: str
    rendering: str                      # zero1|sparse|expert|partitioned|...
    require: Tuple[str, ...] = ()       # op kinds that MUST appear
    allow: Tuple[str, ...] = ()         # kinds allowed at up-to-full payload
    storage_elements: int = 0
    storage_bytes: int = 0
    shard_update: bool = False
    sparse_row_sharded: bool = False
    compressor: str = "NoneCompressor"
    degradations: Tuple[str, ...] = ()
    # Backward-overlap bucket this var's gradient collective is emitted in
    # (kernel/bucketing.py; None = unbucketed post-backward sync), and the
    # bucket's summed payload — the per-bucket allowance the analyzer
    # attributes a combined/fused collective against.
    bucket: Optional[int] = None
    bucket_elements: int = 0


@dataclass
class ShardingPlan:
    """The lowered strategy: mesh + per-variable shardings."""

    mesh: Mesh
    var_plans: Dict[str, VarPlan]
    # Backward-overlap gradient bucketing target (bytes, 0 = disabled):
    # carried from Strategy.graph_config.bucket_bytes by the lowering; the
    # step, the cost model and the analyzer all derive the SAME assignment
    # from it via bucket_assignment().
    bucket_bytes: int = 0

    # --------------------------------------------------------------- lookups
    def plan_for(self, name: str) -> VarPlan:
        return self.var_plans[name]

    def bucket_assignment(self) -> Tuple[Tuple[str, ...], ...]:
        """Deterministic backward-overlap bucket partition of this plan's
        bucket-eligible variables (kernel/bucketing.py): reverse model
        order, greedy fill to ``bucket_bytes``. Empty when bucketing is
        disabled or nothing is eligible. The ONE assignment the step's
        emission, the analyzer's attribution and the cost model's overlap
        pricing share."""
        from autodist_tpu.kernel.bucketing import (
            assign_buckets,
            plan_exclusion_reasons,
        )

        if self.bucket_bytes <= 0:
            return ()
        sized = []
        for name, p in self.var_plans.items():
            if plan_exclusion_reasons(p):
                continue
            elems = 1
            for d in (p.storage_shape or tuple(p.var.shape) or (1,)):
                elems *= int(d)
            sized.append((name, elems * int(np.dtype(p.var.dtype).itemsize)))
        return assign_buckets(sized, self.bucket_bytes)

    @property
    def has_sparse_ps(self) -> bool:
        return any(
            p.kind is SyncKind.PS and p.var.sparse_update for p in self.var_plans.values()
        )

    def _sharding(self, pspec: P, offload: bool = False) -> NamedSharding:
        if offload:
            return NamedSharding(self.mesh, pspec, memory_kind="pinned_host")
        return NamedSharding(self.mesh, pspec)

    @property
    def has_offload(self) -> bool:
        return any(p.offload for p in self.var_plans.values())

    @property
    def has_padding(self) -> bool:
        return any(p.storage_shape is not None for p in self.var_plans.values())

    def _resize_state_tree(self, tree, to_storage: bool) -> Any:
        """Map padded↔logical shapes across any state-like pytree.

        Leaves are matched by var-name path suffix (the same rule
        ``opt_shardings`` uses, so params, optax slots and staleness buffers
        all match); a matched leaf whose *trailing* dims equal the source
        shape is padded/sliced on those dims, leading (buffer) dims pass
        through. Trace-safe (jnp.pad / lax.slice), so the storage→logical
        direction runs inside the jitted step. Identity without padding.
        """
        if not self.has_padding:
            return tree
        names = sorted(self.var_plans, key=len, reverse=True)

        def leaf_fn(path, leaf):
            leaf_name = _path_name(path)
            for n in names:
                if leaf_name != n and not leaf_name.endswith("/" + n):
                    continue
                plan = self.var_plans[n]
                if plan.storage_shape is None:
                    return leaf
                logical, storage = tuple(plan.var.shape), tuple(plan.storage_shape)
                src = logical if to_storage else storage
                dst = storage if to_storage else logical
                shape = tuple(getattr(leaf, "shape", ()))
                r = len(src)
                if len(shape) < r or shape[-r:] != src:
                    return leaf
                lead = shape[:-r]
                if to_storage:
                    pads = [(0, 0)] * len(lead) + [
                        (0, d - s) for d, s in zip(dst, src)
                    ]
                    return jnp.pad(jnp.asarray(leaf), pads)
                return lax.slice(
                    jnp.asarray(leaf),
                    [0] * len(shape),
                    list(lead) + list(dst),
                )
            return leaf

        return jax.tree_util.tree_map_with_path(leaf_fn, tree)

    def pad_params(self, params) -> Any:
        """Logical → storage view: zero-pad every leaf whose plan shards a
        non-divisible axis. No-op (identity tree) without padding."""
        return self._resize_state_tree(params, to_storage=True)

    def unpad_params(self, params) -> Any:
        """Storage → logical view: slice padded leaves back to the shapes the
        user's model defines."""
        return self._resize_state_tree(params, to_storage=False)

    def pad_state(self, state) -> Any:
        """Logical → storage view across a full state tree (params, optimizer
        slots, staleness buffers)."""
        return self._resize_state_tree(state, to_storage=True)

    def unpad_state(self, state) -> Any:
        """Storage → logical view across a full state tree — what checkpoints
        should contain so they restore into any sharding (the reference's
        original-name/shape contract, checkpoint/saver.py:50-57)."""
        return self._resize_state_tree(state, to_storage=False)

    # ------------------------------------------------------------- shardings
    def params_shardings(self, params, device_view: bool = False) -> Any:
        """Pytree of NamedShardings matching ``params`` (matched by path).

        ``device_view=True`` ignores host-offload markers — the sharding the
        parameter has *inside* the step after streaming into HBM.
        """
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for path, leaf in leaves:
            name = _path_name(path)
            plan = self.var_plans.get(name)
            pspec = plan.pspec if plan is not None else P()
            offload = plan.offload if plan is not None and not device_view else False
            out.append(self._sharding(pspec, offload))
        return jax.tree_util.tree_unflatten(treedef, out)

    def opt_shardings(self, opt_state_shapes, device_view: bool = False) -> Any:
        """Shardings for an optimizer-state pytree.

        Slot leaves are matched to variables by path suffix (optax states
        embed the params tree, e.g. ``0/mu/dense/kernel``); matched slots get
        the variable's ``update_pspec`` (weight-update sharding for PS vars,
        the param sharding for partitioned vars) and the variable's
        host-offload placement (slots are 1-2x the param bytes — leaving
        them in HBM would defeat the offload); unmatched leaves (step
        counts, scalars) are replicated on device.
        """
        names = sorted(self.var_plans, key=len, reverse=True)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(opt_state_shapes)
        out = []
        for path, leaf in leaves:
            leaf_name = _path_name(path)
            spec, offload = P(), False
            for n in names:
                if leaf_name == n or leaf_name.endswith("/" + n):
                    plan = self.var_plans[n]
                    # Slots mirror the *storage* shape when the param is
                    # padded (optax init runs on the padded tree).
                    expect = plan.storage_shape or tuple(plan.var.shape)
                    if tuple(getattr(leaf, "shape", ())) == tuple(expect):
                        spec = plan.update_pspec
                        offload = plan.offload and not device_view
                    break
            out.append(self._sharding(spec, offload))
        return jax.tree_util.tree_unflatten(treedef, out)

    def batch_shardings(self, batch, strict: bool = True) -> Any:
        """Batch leaves sharded along the data axis on dim 0 (the remapper's
        feed-splitting contract, remapper.py:81-123). With ``strict=False``,
        non-divisible leading dims replicate instead of raising."""
        ax = data_axis(self.mesh)
        n = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[ax]

        def leaf_sharding(leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            if not is_broadcast_leaf(shape) and shape[0] % n == 0:
                return self._sharding(P(ax))
            # Broadcast leaves (attention masks, per-feature constants —
            # see is_broadcast_leaf) replicate without complaint.
            if not is_broadcast_leaf(shape) and shape[0] % n != 0 and strict:
                raise ValueError(
                    f"global batch dim {shape[0]} not divisible by data-parallel "
                    f"degree {n}"
                )
            return self._sharding(P())

        return jax.tree_util.tree_map(leaf_sharding, batch)

    def global_batch_from_local(self, local_batch, broadcast=None) -> Any:
        """Assemble per-process batch shards into global arrays (multi-host
        feed path — the remapper's feed-splitting contract in reverse,
        reference remapper.py:81-123: each host loads only its slice of the
        global batch, dim 0 concatenates across processes).

        ``broadcast`` optionally disambiguates leaves whose LOCAL leading dim
        is 1: a pytree of bools (same structure as ``local_batch``) marking
        leaves every process holds whole (replicated) rather than as a slice.
        Without it, local leading dim <= 1 is taken as broadcast — the
        framework convention (``is_broadcast_leaf``) — which mis-classifies a
        genuinely batched leaf whose per-process batch is exactly 1; callers
        that know the global shapes (e.g. the fleet-tune feed) should pass
        the mask.

        Single-process: equivalent to ``device_put`` with batch shardings.
        """
        if jax.process_count() == 1:
            return jax.device_put(local_batch, self.batch_shardings(local_batch, strict=False))

        n_proc = jax.process_count()
        if broadcast is None:
            broadcast = jax.tree_util.tree_map(
                lambda x: is_broadcast_leaf(np.shape(x)), local_batch
            )

        def global_shape_of(x, is_bcast) -> Tuple[int, ...]:
            shape = tuple(np.shape(x))
            # Broadcast (and rank-0) leaves are replicated: every process
            # holds the same value, so the global shape is the local shape.
            if not shape or is_bcast:
                return shape
            return (shape[0] * n_proc,) + shape[1:]

        def leaf_to_global(leaf, sharding, is_bcast):
            arr = np.asarray(leaf)
            if arr.ndim == 0:
                # Replicated scalar: every process holds the same value;
                # make_array_from_process_local_data has no dim to concat.
                return jax.make_array_from_callback((), sharding, lambda _: arr)
            return jax.make_array_from_process_local_data(
                sharding, arr, global_shape_of(arr, is_bcast))

        shardings = self.batch_shardings(
            jax.tree_util.tree_map(
                lambda x, b: jax.ShapeDtypeStruct(
                    global_shape_of(x, b),
                    getattr(x, "dtype", None) or np.asarray(x).dtype,
                ),
                local_batch, broadcast,
            ),
            strict=False,
        )
        return jax.tree_util.tree_map(
            leaf_to_global, local_batch, shardings, broadcast)

    def window_shardings(self, stacked_batch, strict: bool = True) -> Any:
        """Shardings for a prefetched data window: every leaf carries a
        leading (scan-step) axis that stays unsharded, and each per-step
        slice shards exactly as :meth:`batch_shardings` would shard it —
        including the strict default: a window is always TRAINING data, so
        a non-divisible slice dim should fail loudly, not silently
        replicate 8x redundant work per device."""
        slice_struct = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(x.shape)[1:], getattr(x, "dtype", None) or np.asarray(x).dtype
            ),
            stacked_batch,
        )
        slice_sh = self.batch_shardings(slice_struct, strict=strict)
        return jax.tree_util.tree_map(
            lambda s: self._sharding(P(None, *s.spec)), slice_sh)

    def window_from_local(self, stacked_local) -> Any:
        """Per-process stacked host window → device-resident global window.

        ``stacked_local`` leaves are ``[num_steps, local_batch, ...]`` (this
        process's slices of ``num_steps`` consecutive batches, stacked on a
        new leading axis). One transfer ships the whole window — the bridge
        between the DataLoader and ``run(stacked=True)``'s device-side scan,
        instead of paying per-step dispatch+transfer latency
        (docs/performance.md measures that pattern at ~11× slower here).

        Window leaves are batched by construction, so no broadcast-leaf
        ambiguity exists: dim 1 (after the step axis) always concatenates
        across processes. The host's part of the transfer is one
        ``input.stage`` span.
        """
        with obs_spans.span("input.stage"):
            return self._window_from_local(stacked_local)

    def _window_from_local(self, stacked_local) -> Any:
        if jax.process_count() == 1:
            return jax.device_put(
                stacked_local, self.window_shardings(stacked_local))

        n_proc = jax.process_count()

        def global_shape_of(x) -> Tuple[int, ...]:
            shape = tuple(np.shape(x))
            return (shape[0], shape[1] * n_proc) + shape[2:]

        shardings = self.window_shardings(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    global_shape_of(x),
                    getattr(x, "dtype", None) or np.asarray(x).dtype,
                ),
                stacked_local,
            )
        )
        return jax.tree_util.tree_map(
            lambda leaf, sh: jax.make_array_from_process_local_data(
                sh, np.asarray(leaf), global_shape_of(leaf)),
            stacked_local, shardings,
        )

    def comp_shardings(self, comp_state) -> Any:
        """Compressor-state shardings: per-worker ("local") leaves carry a
        leading data-axis dim and shard over it; "shared" leaves replicate."""
        ax = data_axis(self.mesh)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(comp_state)
        out = []
        for path, _leaf in leaves:
            name = _path_name(path)
            spec = P(ax) if "/local/" in f"/{name}/" else P()
            out.append(self._sharding(spec))
        return jax.tree_util.tree_unflatten(treedef, out)

    def stale_shardings(self, stale_state) -> Any:
        """Gradient-delay buffers: the var's sharding behind a replicated
        leading (delay-depth) dim. (Staleness is a PS-only capability —
        the AR arm of ``_lower_node`` pins staleness=0 — so zero1
        shard_update vars never appear here.)"""
        out = {}
        for name, leaf in stale_state.items():
            pspec = self.var_plans[name].pspec if name in self.var_plans else P()
            out[name] = self._sharding(P(None, *pspec))
        return out

    def state_shardings(self, state_shapes: TrainState, device_view: bool = False) -> TrainState:
        return TrainState(
            step=self._sharding(P()),
            params=self.params_shardings(state_shapes.params, device_view=device_view),
            opt_state=self.opt_shardings(state_shapes.opt_state, device_view=device_view),
            comp_state=self.comp_shardings(state_shapes.comp_state),
            stale_state=self.stale_shardings(state_shapes.stale_state),
        )

    # -------------------------------------------------------- promised wire
    def promised_wire(self) -> Dict[str, "VarWire"]:
        """The collective wire this plan PROMISES, per variable — the
        contract the static analyzer (``autodist_tpu.analysis``) checks the
        compiled program against. Exported from the lowering (not re-derived
        in the analyzer) so the promise and the rendering can never drift:
        each :class:`VarWire` names the op kinds that must appear
        (``require``), the kinds this var's sync can legitimately emit at up
        to its full payload (``allow``), and the declared degradations.

        Renderings (mirroring ``_lower_node`` precedence):

        - ``zero1`` (shard_update active): reduce-scatter + all-gather are
          REQUIRED; an all-reduce carrying this var's full gradient is the
          regression GSPMD re-fusion produces (docs/zero.md);
        - ``sparse``: row-sharded table — wire must stay tokens-scale, so
          NOTHING is allowed at full-table payload;
        - ``expert`` / ``partitioned``: sharded param; gathers/reduces up to
          the storage size are the planned TP/EP wire (activation-scale
          all-to-all / collective-permute ride the activation allowance);
        - ``zero3`` (data-axis-sharded param): all-gather on use is
          required; this toolchain's GSPMD renders the grad reduce-scatter
          as all-reduce + slice, so full-size all-reduce is allowed;
        - ``ps1`` / ``replicated``: dense all-reduce wire at full payload.
        """
        ax_d = data_axis(self.mesh)
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        # Bucket attribution: which backward-overlap bucket carries each
        # var's gradient collective, and the bucket's summed payload (the
        # allowance a combined per-bucket collective is checked against).
        bucket_of: Dict[str, int] = {}
        for bi, names in enumerate(self.bucket_assignment()):
            for n in names:
                bucket_of[n] = bi

        def axes_of(pspec: P):
            out = set()
            for e in tuple(pspec):
                if e is None:
                    continue
                for name in (e if isinstance(e, tuple) else (e,)):
                    out.add(name)
            return out

        wires: Dict[str, VarWire] = {}
        for name, p in self.var_plans.items():
            elems = 1
            for d in (p.storage_shape or tuple(p.var.shape) or (1,)):
                elems *= int(d)
            axes = {a for a in axes_of(p.pspec) if sizes.get(a, 1) > 1}
            if not p.var.trainable:
                rendering, require, allow = "nontrainable", (), ()
            elif p.shard_update:
                rendering = "zero1"
                require = ("reduce-scatter", "all-gather")
                allow = ("reduce-scatter", "all-gather")
            elif p.var.sparse_update and axes:
                rendering, require, allow = "sparse", (), ()
            elif const.MESH_AXIS_EXPERT in axes:
                rendering, require = "expert", ()
                allow = ("all-reduce", "all-gather", "all-to-all")
            elif ax_d in axes:
                rendering = "zero3"
                require = ("all-gather",) if sizes.get(ax_d, 1) > 1 else ()
                allow = ("all-gather", "reduce-scatter", "all-reduce")
            elif axes:
                rendering, require = "partitioned", ()
                allow = ("all-gather", "reduce-scatter", "all-reduce")
            elif p.kind is SyncKind.PS:
                rendering, require = "ps1", ()
                allow = ("all-reduce", "all-gather")
            else:
                rendering, require = "replicated", ()
                allow = ("all-reduce", "all-gather")
            wires[name] = VarWire(
                var=name,
                rendering=rendering,
                require=require,
                allow=allow,
                storage_elements=elems,
                storage_bytes=elems * int(np.dtype(p.var.dtype).itemsize),
                shard_update=p.shard_update,
                sparse_row_sharded=(p.var.sparse_update and bool(axes)),
                compressor=p.compressor,
                degradations=p.degradations,
                bucket=bucket_of.get(name),
            )
        if bucket_of:
            # Per-bucket summed payload: a combined collective for bucket i
            # may legitimately carry up to this many elements.
            bucket_sums: Dict[int, int] = {}
            for name, bi in bucket_of.items():
                bucket_sums[bi] = (bucket_sums.get(bi, 0)
                                   + wires[name].storage_elements)
            for name, bi in bucket_of.items():
                wires[name] = _dc_replace(
                    wires[name], bucket_elements=bucket_sums[bi])
        return wires

    def describe(self) -> str:
        lines = [f"ShardingPlan(mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))})"]
        for name, p in self.var_plans.items():
            lines.append(
                f"  {name}: {p.kind.value} param={p.pspec} update={p.update_pspec}"
                + (" shard_update=zero1" if p.shard_update else "")
                + (f" dest={p.reduction_destination}" if p.reduction_destination else "")
                + (f" shard_dests={list(p.shard_destinations)}"
                   if p.shard_destinations else "")
                + (" offload=pinned_host" if p.offload else "")
            )
        return "\n".join(lines)


# Param names are matched by string equality against ModelItem's names, so
# both sides must use the one path-to-name implementation.
_path_name = _path_to_name


def _stream(tree, marker_shardings, target_shardings):
    """device_put only the leaves whose marker sharding is host-placed."""
    def leaf(x, marker, target):
        if getattr(marker, "memory_kind", None) == "pinned_host":
            return jax.device_put(x, target)
        return x

    return jax.tree_util.tree_map(leaf, tree, marker_shardings, target_shardings)


class DistributedTrainStep:
    """Compiled distributed train step — the ``WrappedSession`` analog
    (reference runner.py:117-132): users call it like the single-device step;
    sharding, collectives and device placement are invisible.
    """

    def __init__(
        self,
        plan: ShardingPlan,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        has_aux: bool = False,
        donate_state: bool = True,
        grad_accum_steps: int = 1,
        record_norms: bool = False,
    ):
        self.plan = plan
        # Under pad-and-mask sharding the step's param tree is the padded
        # STORAGE view; the user's loss always sees the sliced logical view.
        # Slicing's autodiff transpose zero-pads the gradients, so padded
        # entries never move (elementwise optimizers; factored ones like
        # adafactor mix padding zeros into their row/col statistics — a
        # small, documented perturbation).
        if plan.has_padding:
            self.loss_fn = lambda p, b: loss_fn(plan.unpad_params(p), b)
        else:
            self.loss_fn = loss_fn
        self.tx = optimizer
        self.has_aux = has_aux
        self._donate = donate_state
        # Flight-recorder telemetry (docs/observability.md): global grad /
        # update norms in the step metrics — two extra reductions per step
        # (cheap next to the backward), opt-in because they change the
        # metrics pytree shape callers may have pinned.
        self._record_norms = bool(record_norms)
        if grad_accum_steps < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {grad_accum_steps}")
        self._accum = grad_accum_steps
        self._compiled = None
        self._compiled_runs: Dict[Any, Any] = {}
        self._compiled_eval: Dict[Any, Any] = {}
        # Fresh-program first-call latencies (compile happens synchronously
        # inside that call): the obs StepProfiler's compile count/time feed.
        self.compile_log: List[Dict[str, Any]] = []
        self._state_shardings = None
        self._compressors = self._resolve_compressors(plan)
        # ZeRO-1 (shard_update) vars: gradient sync rendered manually as
        # reduce-scatter inside the shard_map region (the toolchain's GSPMD
        # pass renders a psum + sliced consumer as all-reduce +
        # dynamic-slice, which pays full wire AND forfeits the pinned
        # reduce-scatter evidence), update computed on the 1/N shard,
        # params re-gathered by the output shardings.
        self._shard_update = {
            name: p for name, p in plan.var_plans.items() if p.shard_update
        }
        self._stale = {
            name: p.staleness
            for name, p in plan.var_plans.items()
            if p.staleness > 0
        }
        # Backward-overlap gradient bucketing (kernel/bucketing.py): the
        # plan's deterministic assignment, emitted as per-bucket collectives
        # INSIDE the backward via custom_vjp hooks so XLA's latency-hiding
        # scheduler can overlap the wire with backward compute. Disabled
        # under gradient accumulation: per-microbatch emission would
        # multiply the wire by k and reassociate the mean.
        self._buckets: Tuple[Tuple[str, ...], ...] = ()
        if plan.bucket_bytes > 0:
            if self._accum > 1:
                logging.warning(
                    "bucketed grad sync (bucket_bytes=%d) disabled under "
                    "grad_accum_steps=%d: collectives must fire once per "
                    "step, after accumulation", plan.bucket_bytes,
                    self._accum)
            else:
                self._buckets = plan.bucket_assignment()

    @staticmethod
    def _resolve_compressors(plan: ShardingPlan):
        """var name → Compressor for vars whose strategy asks for one.

        Compression wraps the data-axis gradient psum, so it applies only to
        vars not sharded over the data axis (matching the reference, where
        compressors exist only on the dense AllReduce path,
        compressor.py:146-201); others are skipped with a warning.
        Model/seq/expert-sharded vars compress fine: the compressed sync is
        manual over the data axis only, with other mesh axes left to GSPMD
        (partial-manual shard_map).
        """
        from autodist_tpu.kernel.compressor import (
            get_compressor,
            is_active_compressor,
        )

        ax = data_axis(plan.mesh)
        sizes = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
        mixed_mesh = any(v > 1 for k, v in sizes.items() if k != ax)
        platform = plan.mesh.devices.flat[0].platform
        out = {}
        for name, p in plan.var_plans.items():
            if not is_active_compressor(p.compressor):
                continue
            if any(e == ax or (isinstance(e, tuple) and ax in e) for e in p.pspec):
                logging.warning(
                    "compressor %s on %s ignored: var is sharded over the data "
                    "axis (sparse/ZeRO path has no gradient all-reduce to "
                    "compress). NOTE: with any compressor active this var "
                    "enters the compressed grad region replicated, so its "
                    "sync pays full-size (table-scale) wire — avoid "
                    "compressors on embedding-heavy AllReduce models",
                    p.compressor, name,
                )
                continue
            comp = get_compressor(p.compressor)
            if (
                mixed_mesh
                and platform == "cpu"
                and getattr(comp, "wire_dtype", None) not in (None, jnp.float32)
            ):
                # XLA's CPU pipeline (AllReducePromotion/ChangeOpDataType)
                # check-fails cloning a bf16 all-reduce inside a
                # partial-manual region ("Invalid binary instruction opcode
                # copy"). TPU handles bf16 collectives natively; on the CPU
                # test backend keep the semantics and drop only the wire
                # narrowing.
                logging.warning(
                    "compressor %s on %s: bf16 collective unsupported by the "
                    "CPU backend inside a partial-manual region; wire stays "
                    "f32 here (TPU runs the narrow wire)", p.compressor, name,
                )
                comp.wire_dtype = jnp.float32
            out[name] = comp
        return out

    # ------------------------------------------------------------------ init
    def init(self, params) -> TrainState:
        """Build + shard the initial state (runs the reference's "run
        initializers on session creation", runner.py:86-100).

        Copies param leaves: the returned state's buffers are donated on each
        step, and ``device_put`` may alias the caller's arrays when shardings
        already match — donation must never invalidate user-held arrays.
        """
        params = jax.tree.map(
            lambda x: jnp.array(x, copy=True) if isinstance(x, jax.Array) else jnp.asarray(x),
            params,
        )
        # Pad-and-mask storage view (no-op without padded plans). jnp.pad
        # also makes a copy, satisfying the donation-safety contract above.
        params = self.plan.pad_params(params)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.tx.init(params),
            comp_state=self._init_comp_state(),
            stale_state=self._init_stale_state(params),
        )
        shardings = self.plan.state_shardings(jax.eval_shape(lambda: state))
        self._state_shardings = shardings
        return jax.device_put(state, shardings)

    def logical_params(self, state: TrainState):
        """The user-shaped parameter view of a train state — identical to
        ``state.params`` except under pad-and-mask sharding, where the padded
        storage is sliced back to the model's shapes."""
        return self.plan.unpad_params(state.params)

    def logical_state(self, state: TrainState) -> TrainState:
        """Checkpoint view of a train state: every leaf (params, optimizer
        slots, staleness buffers) in its logical shape. Identity when the
        plan has no padding, so ``saver.save(step.logical_state(state))`` is
        always the right call — the written checkpoint restores into any
        sharding, padded or not (the reference's original-name/shape
        contract, checkpoint/saver.py:50-57). ``init_or_restore`` re-pads on
        the way back in."""
        return self.plan.unpad_state(state)

    def _init_comp_state(self):
        """Compressor persistence: {"<var>": {"local": ..., "shared": ...}}.
        Local (per-worker) entries are stacked with a leading data-axis dim —
        one residual per data shard (each reference worker kept its own
        ``error`` tensor)."""
        if not self._compressors:
            return {}
        n = dict(zip(self.plan.mesh.axis_names, self.plan.mesh.devices.shape))[
            data_axis(self.plan.mesh)
        ]
        comp_state = {}
        for name, comp in self._compressors.items():
            var = self.plan.var_plans[name].var
            local = comp.init_local(var)
            comp_state[name] = {
                "local": jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), local
                ),
                "shared": comp.init_shared(var),
            }
        return comp_state

    # ------------------------------------------------------------------ step
    def _init_stale_state(self, params):
        """Zero-filled [K, ...] delay buffer per stale var."""
        if not self._stale:
            return {}
        buffers = {}
        leaves, _ = jax.tree_util.tree_flatten_with_path(params)
        by_name = {_path_name(p): leaf for p, leaf in leaves}
        for name, k in self._stale.items():
            leaf = by_name[name]
            buffers[name] = jnp.zeros((k,) + tuple(leaf.shape), leaf.dtype)
        return buffers

    def _apply_staleness(self, grads, stale_state):
        """Swap each stale var's fresh gradient for the K-step-old one.

        The fresh grad enters the buffer tail; the head (computed K steps
        ago) is what the optimizer sees — so updates lag exactly
        ``staleness`` steps, the deterministic rendering of the reference's
        ≤K bound (its staleness queues let the chief run ahead by at most K
        tokens). The first K steps apply zero gradient (buffers start
        empty), matching "workers proceed before the server has aggregated".
        """
        leaves, treedef = jax.tree_util.tree_flatten_with_path(grads)
        new_bufs = dict(stale_state)
        out = []
        for path, g in leaves:
            name = _path_name(path)
            if name in new_bufs:
                buf = new_bufs[name]
                delayed = buf[0]
                new_bufs[name] = jnp.concatenate([buf[1:], g[None]], axis=0)
                g = delayed
            out.append(g)
        return jax.tree_util.tree_unflatten(treedef, out), new_bufs

    def _step(self, state: TrainState, batch):
        host_shardings = None
        if self.plan.has_offload:
            # Weight streaming: offloaded leaves live in pinned host memory
            # between steps; stream them into HBM for compute and back out
            # after the update. Only offloaded leaves get device_put —
            # annotating already-on-device leaves (e.g. the step scalar)
            # trips the SPMD partitioner's side-effect sharding check.
            shapes = jax.eval_shape(lambda: state)
            host_shardings = self.plan.state_shardings(shapes)
            device_shardings = self.plan.state_shardings(shapes, device_view=True)
            state = _stream(state, host_shardings, device_shardings)
        if self._compressors or self._shard_update or self._buckets:
            loss, aux, grads, new_comp = self._manual_sync_grads(state, batch)
        elif self._accum > 1:
            loss, aux, grads = self._accumulated_grads(state.params, batch)
            new_comp = state.comp_state
        else:
            if self.has_aux:
                (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
                    state.params, batch
                )
            else:
                loss, grads = jax.value_and_grad(self.loss_fn)(state.params, batch)
                aux = None
            new_comp = state.comp_state
        new_stale = state.stale_state
        if self._stale:
            grads, new_stale = self._apply_staleness(grads, state.stale_state)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        if self._shard_update:
            new_params = self._gather_updated_params(new_params)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt,
            comp_state=new_comp, stale_state=new_stale,
        )
        if host_shardings is not None:
            new_state = _stream(new_state, host_shardings, host_shardings)
        metrics = {"loss": loss}
        if aux is not None:
            metrics["aux"] = aux
        if self._record_norms:
            # Global (all-leaf) L2 norms: the NaN/explosion signal the obs
            # sentry watches (SNT002). optax.global_norm handles ragged
            # pytrees; sharded leaves are fine — the norm is computed under
            # the same shardings as the update itself.
            metrics["grad_norm"] = optax.global_norm(grads)
            metrics["update_norm"] = optax.global_norm(updates)
        return new_state, metrics

    def _gather_updated_params(self, params):
        """Re-gather zero1 (shard_update) parameters to their replicated
        residency after the sharded update — the all-gather leg of
        reduce-scatter → sharded update → all-gather (arXiv 2004.13336).
        The explicit constraint (under a named scope, so profiles attribute
        the collective) pins the gather HERE; without it the output
        shardings would still force one, but anonymously at program exit."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        with jax.named_scope(bucketing.ZERO1_ALL_GATHER_SCOPE):
            for path, leaf in leaves:
                plan = self._shard_update.get(_path_name(path))
                if plan is not None:
                    leaf = lax.with_sharding_constraint(
                        leaf, NamedSharding(self.plan.mesh, plan.pspec))
                out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    # --------------------------------------------- gradient accumulation
    def _accumulated_grads(self, params, batch):
        """Microbatched gradients: split the batch dim into ``_accum``
        slices, scan, and average — activation memory drops ~k× while the
        update equals the full-batch step exactly (for batch-mean losses,
        the zoo's convention). Loss and aux metrics come back averaged over
        microbatches (so sum-style aux reports the per-micro mean, in f32). This is the memory side of what the
        reference's per-variable ``ConditionalAccumulator`` did across
        workers (ps_synchronizer.py:553-630), rendered as a deterministic
        on-device loop.
        """
        k = self._accum
        ax = data_axis(self.plan.mesh)
        n = dict(zip(self.plan.mesh.axis_names, self.plan.mesh.devices.shape))[ax]

        for leaf in jax.tree.leaves(batch):
            shape = getattr(leaf, "shape", ())
            # Broadcast leaves replicate (is_broadcast_leaf — the same
            # tolerance as batch_shardings); batched leaves must split
            # evenly.
            if not is_broadcast_leaf(shape) and shape[0] % k != 0:
                raise ValueError(
                    f"grad_accum_steps={k} requires every batched leaf's "
                    f"leading dim to be divisible by {k}; got shape {shape}")

        def to_micro(x):
            # [B, ...] -> [k, B/k, ...]; keep the micro batch dim sharded on
            # the data axis exactly where the plan would shard the full
            # batch (one all-to-all on the feed, versus resharding the
            # whole activation set every micro-step). Rank-0 and broadcast
            # leaves ride along whole, one copy per micro-step.
            shape = tuple(getattr(x, "shape", ()))
            if is_broadcast_leaf(shape):
                m = jnp.broadcast_to(jnp.asarray(x)[None], (k,) + shape)
                return lax.with_sharding_constraint(
                    m, NamedSharding(self.plan.mesh, P()))
            m = x.reshape((k, x.shape[0] // k) + x.shape[1:])
            if m.shape[1] % n == 0 and m.shape[1] > 0:
                spec = P(None, ax)
            else:
                logging.warning(
                    "grad_accum_steps=%d: micro batch dim %d not divisible "
                    "by data-parallel degree %d — micro batches replicate "
                    "and every device computes the full gradient redundantly",
                    k, m.shape[1], n,
                )
                spec = P()
            return lax.with_sharding_constraint(
                m, NamedSharding(self.plan.mesh, spec))

        micro_batches = jax.tree.map(to_micro, batch)

        def grads_fn(p, mb):
            if self.has_aux:
                (loss, aux), grads = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(p, mb)
                return loss, aux, grads
            loss, grads = jax.value_and_grad(self.loss_fn)(p, mb)
            return loss, None, grads

        return self._scan_accumulate(grads_fn, params, micro_batches, k)

    def _scan_accumulate(self, grads_fn, params, micro_batches, k):
        """Shared microbatch-accumulation core (plain and compressed paths):
        scan ``grads_fn`` over the leading ``k`` dim, averaging loss, aux
        (promoted to ≥f32 — ``a + x/k`` needs a dtype-stable carry) and
        grads."""
        zero_grads = jax.tree.map(jnp.zeros_like, params)
        if self.has_aux:
            micro0 = jax.tree.map(lambda x: x[0], micro_batches)
            aux_shape = jax.eval_shape(lambda: self.loss_fn(params, micro0)[1])
            zero_aux = jax.tree.map(
                lambda s: jnp.zeros(s.shape, jnp.promote_types(s.dtype, jnp.float32)),
                aux_shape)
        else:
            zero_aux = None

        def body(carry, mb):
            loss_acc, grads_acc, aux_acc = carry
            loss, aux, grads = grads_fn(params, mb)
            grads_acc = jax.tree.map(lambda a, g: a + g / k, grads_acc, grads)
            if aux is not None:
                aux_acc = jax.tree.map(lambda a, x: a + x / k, aux_acc, aux)
            return (loss_acc + loss / k, grads_acc, aux_acc), None

        (loss, grads, aux), _ = lax.scan(
            body, (jnp.zeros((), jnp.float32), zero_grads, zero_aux),
            micro_batches,
        )
        return loss, aux, grads

    # ---------------------------------------------- manual gradient sync
    def _manual_sync_grads(self, state: TrainState, batch):
        """Gradient sync with an explicit per-variable wire: compression,
        zero1 reduce-scatter, and/or bucketed backward-overlap emission
        around the data-axis psum.

        Runs the loss/grad computation inside a ``shard_map`` that is manual
        over the data axis only: each instance sees its local batch shard,
        computes local-mean grads, and each var picks its wire —

        - bucketed vars (``plan.bucket_assignment()`` non-empty): the
          collective is emitted INSIDE the backward pass by the bucket's
          ``custom_vjp`` hook (kernel/bucketing.py, ``gradsync.bucket_{i}``
          named scopes) — same per-var op (psum / psum_scatter), moved to
          the bucket's layer-group boundary so XLA's latency-hiding
          scheduler overlaps it with the remaining backward compute; the
          trailing loop only re-slices zero1 shards;
        - compressed vars: the compressor's compress → psum → decompress
          sequence (the collective runs on compressed payloads — the
          reference wrapped ``collective_ops.all_reduce`` the same way);
        - ``shard_update`` (zero1) vars: ``lax.psum_scatter`` over the data
          axis, so each instance exits with its 1/N reduce-scattered
          gradient slice (arXiv 2004.13336) — the optimizer update outside
          the region then runs sharded and the output shardings all-gather
          the fresh params;
        - everything else: a plain ``lax.psum``.

        Model/other mesh axes stay GSPMD-auto (partial-manual mode), so
        tensor-parallel vars keep their shardings; on a pure-DP mesh the
        region runs fully manual over a flat data-only mesh view (identical
        device order), which keeps the long-tested full-manual lowering on
        the bench path.

        Assumes ``loss_fn`` computes a *mean* over the batch (the reference's
        merge=Add final=Div semantics, all_reduce_synchronizer.py:100-126).
        """
        from autodist_tpu.utils.compat import shard_map

        mesh = self.plan.mesh
        ax = data_axis(mesh)
        n = dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
        if n == mesh.devices.size:
            # Pure DP: flat full-manual view, device order unchanged.
            mesh = Mesh(mesh.devices.reshape(-1), (ax,))
        compressors = self._compressors
        # zero1 vars: data-axis index of their scatter dimension, taken from
        # the plan's update spec (always divisible — _weight_update_spec
        # only picks divisible axes).
        su_dims = {
            name: list(p.update_pspec).index(ax)
            for name, p in self._shard_update.items()
        }

        # Every parameter enters the manual region REPLICATED over the data
        # axis (shard_map all-gathers data-sharded leaves at entry): the
        # user's loss indexes and matmuls against full-shaped parameters, so
        # feeding a data-row-sliced leaf (e.g. a row-sharded embedding, or a
        # ZeRO-sharded kernel) would silently compute garbage — jnp.take
        # clamps out-of-range ids instead of failing. Grads exit replicated
        # too (each instance psums the full gradient) EXCEPT zero1 vars,
        # whose reduce-scattered slice exits sharded on its scatter dim;
        # GSPMD reshards everything onto the plan's update shardings at the
        # region boundary.
        param_specs = jax.tree_util.tree_map(lambda _: P(), state.params)
        g_spec_leaves, g_spec_treedef = jax.tree_util.tree_flatten_with_path(
            state.params)
        grad_specs = jax.tree_util.tree_unflatten(
            g_spec_treedef,
            [
                (self._shard_update[_path_name(path)].update_pspec
                 if _path_name(path) in self._shard_update else P())
                for path, _ in g_spec_leaves
            ],
        )

        def spec_for_batch(leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            return P(ax) if len(shape) >= 1 and shape[0] % n == 0 and shape[0] > 0 else P()

        batch_specs = jax.tree_util.tree_map(spec_for_batch, batch)

        c_leaves, c_treedef = jax.tree_util.tree_flatten_with_path(state.comp_state)
        comp_specs = jax.tree_util.tree_unflatten(
            c_treedef,
            [
                P(ax) if "/local/" in f"/{_path_name(path)}/" else P()
                for path, _ in c_leaves
            ],
        )

        loss_fn, has_aux, k = self.loss_fn, self.has_aux, self._accum

        # Backward-overlap buckets: wrap the loss so each bucket's params
        # pass through an identity custom_vjp whose backward rule emits the
        # bucket's collectives mid-backward (kernel/bucketing.py). Names
        # are filtered to leaves actually present in the params tree so a
        # hook's arg list always zips exactly with its cotangents.
        p_leaves, _ = jax.tree_util.tree_flatten_with_path(state.params)
        present = {_path_name(path) for path, _ in p_leaves}
        buckets = tuple(
            b for b in (
                tuple(nm for nm in names if nm in present)
                for names in self._buckets)
            if b)
        bucketed = {nm for names in buckets for nm in names}
        if buckets:
            hooks = [
                bucketing.make_bucket_hook(i, names, su_dims, ax, n)
                for i, names in enumerate(buckets)
            ]
            inner_loss_fn = loss_fn

            def loss_fn(p, b):  # noqa: F811 - deliberate hooked rebind
                leaves, treedef = jax.tree_util.tree_flatten_with_path(p)
                vals = [leaf for _, leaf in leaves]
                idx_of = {
                    _path_name(path): j for j, (path, _) in enumerate(leaves)
                }
                for hook, names in zip(hooks, buckets):
                    idxs = [idx_of[nm] for nm in names]
                    outs = hook(*[vals[j] for j in idxs])
                    for j, o in zip(idxs, outs):
                        vals[j] = o
                return inner_loss_fn(
                    jax.tree_util.tree_unflatten(treedef, vals), b)

        if k > 1:
            # Validate (and later microbatch) ONLY the leaves the region
            # data-shards; replicated leaves (broadcast masks, scalars —
            # the spec_for_batch P() cases) ride through whole.
            for leaf in jax.tree.leaves(batch):
                shape = tuple(getattr(leaf, "shape", ()))
                if (
                    len(shape) >= 1 and shape[0] > 0 and shape[0] % n == 0
                    and (shape[0] // n) % k != 0
                ):
                    raise ValueError(
                        f"grad_accum_steps={k} with a manual gradient sync "
                        f"(compression and/or zero1 shard_update) requires "
                        f"each data shard's batch slice (global {shape[0]} "
                        f"/ {n} shards) to split into {k} microbatches; "
                        f"got shape {shape}")

        def local_grads(params, local_batch):
            if has_aux:
                (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, local_batch
                )
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, local_batch)
                aux = None
            return loss, aux, grads

        # Which leaves arrive data-sliced inside the manual region (the
        # others — broadcast masks, scalars — arrive whole and must not be
        # split along their leading dim).
        sharded_leaf = jax.tree_util.tree_map(
            lambda s: s == P(ax), batch_specs
        )

        def local_fn(params, local_batch, comp_state):
            if k > 1:
                # Microbatch INSIDE the manual region: accumulate local-mean
                # grads over a scan (the shared _scan_accumulate core), then
                # compress + psum once — activation memory ÷ k with a single
                # compressed collective per step.
                def to_micro(x, is_sharded):
                    if is_sharded and getattr(x, "ndim", 0) >= 1:
                        return x.reshape((k, x.shape[0] // k) + x.shape[1:])
                    return jnp.broadcast_to(
                        jnp.asarray(x)[None],
                        (k,) + tuple(getattr(x, "shape", ())))

                micro = jax.tree.map(to_micro, local_batch, sharded_leaf)
                loss, aux, grads = self._scan_accumulate(
                    local_grads, params, micro, k)
            else:
                loss, aux, grads = local_grads(params, local_batch)
            loss = bucketing.psum_mean(loss, ax, n)
            if aux is not None:
                aux = jax.tree.map(
                    lambda x: bucketing.psum_mean(x, ax, n), aux)
            g_leaves, g_treedef = jax.tree_util.tree_flatten_with_path(grads)
            new_comp = dict(comp_state)
            synced = []
            for path, g in g_leaves:
                name = _path_name(path)
                if name in bucketed:
                    if name in su_dims:
                        # Bucketed zero1: the reduce-scatter already fired
                        # inside the backward (gradsync.bucket_i scope);
                        # extract this instance's shard from the hook's
                        # re-embedded full-shape buffer (bit-exact).
                        with jax.named_scope(
                                bucketing.GRADSYNC_SHARD_SLICE_SCOPE):
                            synced.append(bucketing.slice_update_shard(
                                g, ax, n, su_dims[name]))
                    else:
                        # Plain AR bucketed var: already psum'd mid-backward.
                        synced.append(g)
                    continue
                if name in su_dims:
                    # zero1: one reduce-scatter replaces the all-reduce —
                    # this instance keeps only its 1/n gradient slice, which
                    # is exactly what its optimizer-state shard consumes.
                    with jax.named_scope(
                            bucketing.ZERO1_REDUCE_SCATTER_SCOPE):
                        synced.append(bucketing.reduce_scatter_grad(
                            g, ax, n, su_dims[name]))
                    continue
                comp = compressors.get(name)
                if comp is None:
                    synced.append(bucketing.psum_mean(g, ax, n))
                    continue
                # Local state arrives as the (1, ...) slice of the stacked
                # per-shard leaves; unwrap, step, rewrap.
                local = jax.tree.map(lambda x: x[0], comp_state[name]["local"])
                g_hat, new_local, new_shared = comp.step(
                    g, local, comp_state[name]["shared"], axis=ax, nshards=n
                )
                new_comp[name] = {
                    "local": jax.tree.map(lambda x: x[None], new_local),
                    "shared": new_shared,
                }
                synced.append(g_hat)
            grads = jax.tree_util.tree_unflatten(g_treedef, synced)
            return loss, aux, grads, new_comp

        sm = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(param_specs, batch_specs, comp_specs),
            out_specs=(P(), P(), grad_specs, comp_specs),
            axis_names={ax},
            check_vma=False,
        )
        return sm(state.params, batch, state.comp_state)

    def _compile(self, state: TrainState, batch):
        if self._state_shardings is None:
            self._state_shardings = self.plan.state_shardings(jax.eval_shape(lambda: state))
        in_shardings = (self._state_shardings, self.plan.batch_shardings(batch))
        out_shardings = (self._state_shardings, None)
        def train_step(st, b):       # the trace's module: jit_train_step
            return self._step(st, b)

        self._compiled = jax.jit(
            train_step,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=(0,) if self._donate else (),
        )
        if const.ENV.AUTODIST_DUMP_HLO.val:
            # Per-stage compile snapshots (the reference dumped its graph to
            # TensorBoard at each transform stage, graph_transformer.py:62-90).
            from autodist_tpu.utils import tracing

            lowered = self._compiled.lower(state, batch)
            tracing.dump_compiled("train_step", lowered, lowered.compile())
        return self._compiled

    # ------------------------------------------------------------- multi-step
    def run(self, state: TrainState, batch, num_steps: int,
            stacked: bool = False, _force_unroll: bool = False):
        """Execute ``num_steps`` train steps as ONE compiled device program
        (``lax.scan`` over the step body).

        The reference's per-step ``session.run`` was cheap because its hot
        loop lived inside TF's C++ runtime (SURVEY §3.4); the TPU analog is
        keeping the loop on device — one dispatch per *window*, amortizing
        host latency and param transfers that per-step dispatch pays every
        step.

        ``stacked=False`` (default): ``batch`` is a single batch pytree,
        re-used each step (benchmarking / steady-state input).
        ``stacked=True``: every ``batch`` leaf carries a leading
        ``num_steps`` axis — a prefetched data window, one slice per step.
        The flag is explicit because shape inference is ambiguous (a batch
        whose leading dim happens to equal ``num_steps`` is a valid single
        batch). Returns ``(state, metrics)`` with per-step stacked metric
        leaves (``metrics["loss"].shape == (num_steps,)``).
        """
        if stacked:
            for leaf in jax.tree.leaves(batch):
                if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != num_steps:
                    raise ValueError(
                        f"stacked=True requires every batch leaf to have "
                        f"leading dim num_steps={num_steps}; got shape "
                        f"{getattr(leaf, 'shape', ())}")
        key = (int(num_steps), stacked, _force_unroll)
        fresh = key not in self._compiled_runs
        program = f"run[{num_steps}{'/stacked' if stacked else ''}]"
        try:
            fn = self._window_program(state, batch, num_steps, stacked,
                                      _force_unroll)
            batch = self._chaos_batch(batch, num_steps, stacked)
            # The host's part of one window: the (asynchronous) dispatch,
            # and for a fresh program the synchronous compile before it,
            # whose latency is the compile-time signal the obs
            # StepProfiler reports.
            t0 = time.perf_counter()
            with obs_spans.span("train.window_dispatch", program=program,
                                fresh=fresh):
                out = fn(state, batch)
            if fresh:
                entry = {
                    "program": program,
                    "first_call_s": time.perf_counter() - t0,
                }
                self.compile_log.append(entry)
                # Flight-record the compile (no-op without a recorder): a
                # run that dies mid-compile leaves "compiling X" as its
                # last event — exactly what the postmortem doctor needs.
                flight.record_event("compile", critical=False, **entry)
            return self._chaos_metrics(out, num_steps)
        except Exception as e:
            # Black-box the failure before re-raising: an XLA OOM
            # (RESOURCE_EXHAUSTED) or runtime error recorded here is the
            # doctor's primary oom/crash evidence (docs/observability.md).
            flight.record_event(
                "error", program=program,
                error=f"{type(e).__name__}: {e}"[:500])
            raise

    def _window_program(self, state: TrainState, batch, num_steps: int,
                        stacked: bool, _force_unroll: bool):
        """Build-or-fetch the jitted window program for one ``run`` shape
        (shared by :meth:`run` and :meth:`window_cost`)."""
        key = (int(num_steps), stacked, _force_unroll)
        fn = self._compiled_runs.get(key)
        if fn is None:
            if self._state_shardings is None:
                self._state_shardings = self.plan.state_shardings(
                    jax.eval_shape(lambda: state))
            # device_put streaming (host offload) inside a scan body is not
            # supported by the SPMD partitioner; unroll those windows instead
            # — same one-dispatch amortization, longer compile.
            unroll = self.plan.has_offload or _force_unroll

            def unrolled(st, get_batch):
                ms = []
                for i in range(num_steps):
                    st, m = self._step(st, get_batch(i))
                    ms.append(m)
                return st, jax.tree.map(lambda *xs: jnp.stack(xs), *ms)

            if stacked:
                batch_sh = self.plan.window_shardings(batch)

                # Named for the trace's module line: jit_train_window.
                def train_window(st, bs):
                    if unroll:
                        return unrolled(st, lambda i: jax.tree.map(
                            lambda x: x[i], bs))
                    return lax.scan(lambda s, b: self._step(s, b), st, bs,
                                    length=num_steps)
            else:
                batch_sh = self.plan.batch_shardings(batch)

                def train_window(st, b):
                    if unroll:
                        return unrolled(st, lambda i: b)
                    return lax.scan(lambda s, _: self._step(s, b), st, None,
                                    length=num_steps)
            fn = jax.jit(
                train_window,
                in_shardings=(self._state_shardings, batch_sh),
                out_shardings=(self._state_shardings, None),
                donate_argnums=(0,) if self._donate else (),
            )
            self._compiled_runs[key] = fn
        return fn

    def window_cost(self, state: TrainState, batch, num_steps: int = 1,
                    stacked: bool = False) -> Dict[str, float]:
        """FLOPs / HBM traffic of the compiled window program, from XLA's
        own per-executable cost analysis (not an analytical model) — the
        measured-over-measured MFU numerator the obs
        :class:`~autodist_tpu.obs.profiler.StepProfiler` reports.

        ``state``/``batch`` supply shapes only (nothing executes). Returns
        ``{"flops", "bytes_accessed"}`` plus ``memory_analysis`` sizes when
        the backend exposes them. See the in-body note on scan-body
        counting: request ``num_steps=1`` for per-step numbers.
        """
        fn = self._window_program(state, batch, num_steps, stacked, False)
        compiled = fn.lower(state, batch).compile()
        d = compiled.cost_analysis() or {}
        # NB: XLA's cost analysis counts a while/scan body ONCE regardless
        # of trip count, so for a scanned window these numbers are per-BODY
        # (≈ per step), not per window. Per-step consumers should ask for
        # ``num_steps=1`` explicitly (the obs StepProfiler does) rather
        # than divide a window's numbers by its length.
        out = {
            "flops": float(d.get("flops", 0.0)),
            "bytes_accessed": float(d.get("bytes accessed", 0.0)),
        }
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 - optional backend API
            mem = None
        if mem is not None:
            out["argument_bytes"] = float(
                getattr(mem, "argument_size_in_bytes", 0))
            out["output_bytes"] = float(
                getattr(mem, "output_size_in_bytes", 0))
            out["temp_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0))
        return out

    # ------------------------------------------------------------------ fit
    def fit(
        self,
        state: TrainState,
        batches,
        steps: Optional[int] = None,
        eval_batch=None,
        eval_every: int = 0,
        log_every: int = 0,
        window: int = 0,
        eval_metrics_fn=None,
    ):
        """Keras-``model.fit``-shaped training loop over an iterable of
        batches (a :class:`~autodist_tpu.data.DataLoader` or any batch
        iterator) — parity for the reference's patched ``model.fit`` path
        (``patch.py:96-116``, exercised by its integration case c7).

        Returns ``(state, history)`` where ``history["loss"]`` is the
        per-step loss and ``history["eval_loss"]`` the periodic eval losses
        (``eval_every`` > 0 with ``eval_batch``). ``eval_metrics_fn`` — a
        ``(params, batch) -> {name: value}`` function (see
        ``autodist_tpu.metrics`` factories) — adds ``history["eval_<name>"]``
        series computed at the same eval points against the logical
        parameter view.

        ``window=k`` (k > 1) bridges fit to the windowed hot loop: ``k``
        consecutive batches are stacked host-side and executed as ONE device
        program (``run(stacked=True)`` — a ``lax.scan`` over fresh data),
        paying one dispatch+transfer per window instead of per step.
        Windows are chopped so eval/steps boundaries land exactly between
        windows; per-step history is identical to ``window=0``.
        """
        import itertools

        if window and window > 1:
            return self._fit_windowed(
                state, batches, steps, eval_batch, eval_every, log_every,
                window, eval_metrics_fn)

        history = {"loss": []}
        eval_metrics = self._make_eval_metrics(eval_metrics_fn)
        if eval_every and eval_batch is not None:
            history["eval_loss"] = []
        # islice, not a break-on-index loop: breaking after enumerate() has
        # pulled the batch would silently consume (and discard) one extra
        # batch from a shared iterator per capped fit() call.
        if steps is not None:
            batches = itertools.islice(batches, steps)
        for i, batch in enumerate(batches):
            state, metrics = self(state, batch)
            loss = float(metrics["loss"])
            history["loss"].append(loss)
            if log_every and (i + 1) % log_every == 0:
                logging.info("fit step %d: loss=%.6f", i + 1, loss)
            if eval_every and eval_batch is not None and (i + 1) % eval_every == 0:
                ev_loss = float(self.evaluate(state, eval_batch)["loss"])
                history["eval_loss"].append(ev_loss)
                eval_metrics(state, eval_batch, history)
                if log_every:
                    logging.info("fit step %d: eval_loss=%.6f", i + 1, ev_loss)
        return state, history

    def compile_metrics(self, metrics_fn, state: "TrainState"):
        """Jit a ``(params, batch) -> {name: value}`` task-metric function
        against this step's parameter handling: host-offloaded leaves
        stream into HBM INSIDE the jitted program (the same `_stream`
        evaluate uses — no eager whole-tree device_put per call) and
        pad-and-mask storage is sliced back to logical shapes under the
        trace. The ONE way to run user metrics on live state
        (autodist_tpu.metrics.evaluate_dataset and fit's eval hook both
        come through here). ``state`` supplies shapes only."""
        if self.plan.has_offload:
            shaped = jax.eval_shape(lambda: state).params
            host_sh = self.plan.params_shardings(shaped)
            dev_sh = self.plan.params_shardings(shaped, device_view=True)
        else:
            host_sh = dev_sh = None

        def fn(params, batch):
            if host_sh is not None:
                params = _stream(params, host_sh, dev_sh)
            params = self.plan.unpad_params(params)
            return metrics_fn(params, batch)

        return jax.jit(fn)

    def _make_eval_metrics(self, eval_metrics_fn):
        """Task-metric hook for fit's eval points: appends ``eval_<name>``
        series to the history. ``<name>__weight`` entries (the masked-
        metric convention of autodist_tpu.metrics.evaluate_dataset) are
        stripped — a point-in-time series has no cross-batch weighting —
        and a metric named ``loss`` records as ``eval_metrics_loss`` so it
        can never interleave with the built-in ``eval_loss`` series."""
        if eval_metrics_fn is None:
            return lambda state, batch, history: None
        compiled = None

        def run(state, batch, history):
            nonlocal compiled
            if compiled is None:
                compiled = self.compile_metrics(eval_metrics_fn, state)
            out = compiled(state.params, batch)
            for k, v in out.items():
                if k.endswith("__weight"):
                    continue
                name = "eval_metrics_loss" if k == "loss" else f"eval_{k}"
                history.setdefault(name, []).append(float(v))

        return run

    def _fit_windowed(self, state, batches, steps, eval_batch, eval_every,
                      log_every, window, eval_metrics_fn=None):
        """The ``fit(window=k)`` body: stack host batches, one dispatch per
        window. See :meth:`fit` for the contract.

        Batch source: a DataLoader exposes ``host_batches()`` (raw
        per-process numpy batches — stacking must happen BEFORE the device
        transfer); any other iterable is consumed as-is and stacked via
        ``np.asarray``, which is single-process only (a generic iterator's
        leaves can't be assembled into multi-host global windows).

        A batch whose leaf shapes differ from the current window's (ragged
        final batch with ``drop_remainder=False``) flushes the window and
        runs alone. Look-ahead never over-consumes a shared iterator: a
        shape-mismatched pull is carried as ``pending`` into the next
        window, and since a window that defers a pull always ran fewer
        than ``steps - step_i`` batches, the loop always comes back around
        to run it — consumed == ran, pinned by
        ``tests/test_lowering.py::test_fit_windowed_consumes_exactly_ran``.
        """
        from_loader = hasattr(batches, "host_batches")
        if from_loader:
            it = iter(batches.host_batches())
        else:
            if jax.process_count() > 1:
                raise ValueError(
                    "fit(window>1) on a multi-process fleet requires a "
                    "DataLoader: generic iterator batches cannot be "
                    "assembled into global windows")
            it = iter(batches)

        history = {"loss": []}
        eval_metrics = self._make_eval_metrics(eval_metrics_fn)
        if eval_every and eval_batch is not None:
            history["eval_loss"] = []

        def sig(b):
            return tuple(tuple(np.shape(leaf)) for leaf in jax.tree.leaves(b))

        _end = object()
        pending = None
        step_i = 0
        while True:
            if steps is not None and step_i >= steps:
                break
            # Chop the window so steps/eval boundaries land between windows.
            chunk = window
            if steps is not None:
                chunk = min(chunk, steps - step_i)
            if eval_every and eval_batch is not None:
                chunk = min(chunk, eval_every - (step_i % eval_every))
            buf = []
            while len(buf) < chunk:
                if pending is not None:
                    b, pending = pending, None
                else:
                    b = next(it, _end)
                    if b is _end:
                        break
                if buf and sig(b) != sig(buf[0]):
                    pending = b  # ragged/shape-change batch: next window
                    break
                buf.append(b)
            if not buf:
                break
            if len(buf) == 1:
                batch = buf[0]
                if from_loader:
                    batch = self.plan.global_batch_from_local(
                        batch, broadcast=jax.tree.map(lambda _: False, batch))
                state, metrics = self(state, batch)
                losses = [float(metrics["loss"])]
            else:
                stacked = jax.tree.map(
                    lambda *xs: np.stack([np.asarray(x) for x in xs]), *buf)
                wnd = (self.plan.window_from_local(stacked) if from_loader
                       else stacked)
                state, metrics = self.run(state, wnd, len(buf), stacked=True)
                losses = [float(x) for x in np.asarray(metrics["loss"])]
            for loss in losses:
                step_i += 1
                history["loss"].append(loss)
                if log_every and step_i % log_every == 0:
                    logging.info("fit step %d: loss=%.6f", step_i, loss)
            if (eval_every and eval_batch is not None
                    and step_i % eval_every == 0):
                ev_loss = float(self.evaluate(state, eval_batch)["loss"])
                history["eval_loss"].append(ev_loss)
                eval_metrics(state, eval_batch, history)
                if log_every:
                    logging.info("fit step %d: eval_loss=%.6f", step_i, ev_loss)
        return state, history

    # ------------------------------------------------------------ evaluation
    def evaluate(self, state: TrainState, batch):
        """Loss (+aux) on a batch without gradients or state mutation — the
        reference's "fetch tensors without train ops" path
        (remapper.py:125-185: non-train fetches ran against the master
        replica). Params stay in their plan shardings; the batch shards on
        the data axis (replicating ragged leaves — eval tails needn't
        divide the mesh); nothing is donated. Compiles are cached per batch
        structure/shape.
        """
        key = (jax.tree.structure(batch), tuple(
            (getattr(x, "shape", ()), str(getattr(x, "dtype", type(x))))
            for x in jax.tree.leaves(batch)))
        fn = self._compiled_eval.get(key)
        if fn is None:
            if self._state_shardings is None:
                self._state_shardings = self.plan.state_shardings(
                    jax.eval_shape(lambda: state))

            if self.plan.has_offload:
                # Host view == the plan shardings already frozen in
                # _state_shardings; only the device view needs computing.
                host_sh = self._state_shardings.params
                dev_sh = self.plan.params_shardings(
                    jax.eval_shape(lambda: state).params, device_view=True)
            else:
                host_sh = dev_sh = None

            def eval_step(params, b):    # the trace's module: jit_eval_step
                if host_sh is not None:
                    params = _stream(params, host_sh, dev_sh)
                out = self.loss_fn(params, b)
                if self.has_aux:
                    loss, aux = out
                    return {"loss": loss, "aux": aux}
                return {"loss": out}

            fn = jax.jit(
                eval_step,
                in_shardings=(self._state_shardings.params,
                              self.plan.batch_shardings(batch, strict=False)),
                out_shardings=None,
            )
            self._compiled_eval[key] = fn
        return fn(state.params, batch)

    def save(self, saver, state: TrainState, path: Optional[str] = None,
             step: Optional[int] = None, block: bool = True) -> str:
        """Checkpoint ``state`` in its LOGICAL shapes — the safe way to save
        a train state (ADVICE r1: a plain ``saver.save(state)`` under a
        pad-and-mask plan would write padded storage shapes that no other
        plan could restore). Defaults the checkpoint step to the state's
        own step counter. ``init_or_restore`` is the matching load."""
        if step is None:
            step = int(state.step)
        return saver.save(self.logical_state(state), path=path, step=step,
                          block=block)

    def init_or_restore(self, params, saver=None, restore_fn=None) -> TrainState:
        """Fresh state, or the latest checkpoint when one exists — the
        crash-resume entry point (the reference's closest fault-tolerance
        mechanism was checkpoint/resume, SURVEY §5). The restored state is
        re-sharded onto this run's plan, so resuming onto a different mesh
        or strategy works like any cross-sharding restore. Checkpoints hold
        *logical* shapes (write them with
        ``saver.save(step.logical_state(state))``); a padded plan re-pads
        the loaded leaves into its storage view here.

        ``restore_fn(target=..., shardings=...)`` overrides where the state
        comes from (default: ``saver.restore_latest``) — the ft subsystem
        passes ``SnapshotManager.restore_latest_valid`` so elastic resume
        rides this exact path with integrity-verified snapshots.
        """
        if restore_fn is None:
            restore_fn = saver.restore_latest
        state = self.init(params)
        if not self.plan.has_padding:
            restored = restore_fn(
                target=jax.eval_shape(lambda: state), shardings=self._state_shardings
            )
            return restored if restored is not None else state
        logical_shapes = jax.eval_shape(self.plan.unpad_state, state)
        restored = restore_fn(target=logical_shapes)
        if restored is None:
            return state
        return jax.device_put(self.plan.pad_state(restored), self._state_shardings)

    def trace_step(self, state: TrainState, batch, name: str = "train_step"):
        """One profiled step -> TensorBoard trace dir (runner.py:64-75 analog).

        Returns ``(new_state, metrics), trace_dir``."""
        from autodist_tpu.utils import tracing

        fn = self._compiled or self._compile(state, batch)
        with tracing.trace(name) as trace_dir:
            out = fn(state, batch)
            jax.block_until_ready(out)
        return out, trace_dir

    @staticmethod
    def _chaos_batch(batch, num_steps: int, stacked: bool):
        """Chaos seam (docs/chaos.md): an installed plant may poison the
        batch (NaN gradients, loss spikes) before dispatch. Inert — one
        predicate call — without a plant. ONE helper for the windowed
        (:meth:`run`) and per-step (:meth:`__call__`) paths."""
        if chaos_hooks.active():
            batch = chaos_hooks.apply(chaos_hooks.SEAM_TRAIN_BATCH, batch,
                                      num_steps=num_steps, stacked=stacked)
        return batch

    @staticmethod
    def _chaos_metrics(out, num_steps: int):
        """Post-step chaos seam: advances the plant's step cursor (and may
        transform metrics). Same inertness contract as _chaos_batch."""
        if chaos_hooks.active():
            new_state, metrics = out
            out = (new_state, chaos_hooks.apply(
                chaos_hooks.SEAM_TRAIN_METRICS, metrics,
                num_steps=num_steps))
        return out

    def __call__(self, state: TrainState, batch) -> Tuple[TrainState, Dict[str, Any]]:
        fresh = self._compiled is None
        fn = self._compiled or self._compile(state, batch)
        batch = self._chaos_batch(batch, num_steps=1, stacked=False)
        t0 = time.perf_counter()
        with obs_spans.span("train.window_dispatch", program="step",
                            fresh=fresh):
            out = fn(state, batch)
        if fresh:
            self.compile_log.append(
                {"program": "step", "first_call_s": time.perf_counter() - t0})
        return self._chaos_metrics(out, num_steps=1)

    def lower_text(self, state: TrainState, batch) -> str:
        """Stable-HLO dump of the compiled step — the TPU analog of the
        reference's per-stage TensorBoard graph snapshots
        (visualization_util.py:24-36)."""
        fn = self._compiled or self._compile(state, batch)
        return fn.lower(state, batch).as_text()
