"""Resource model (L0): describe a TPU cluster and derive a logical mesh.

TPU-native re-imagining of the reference resource layer
(``/root/reference/autodist/resource_spec.py:45-215``). The reference parses a
``resource_spec.yml`` of GPU hosts joined by Ethernet + SSH into ``DeviceSpec``
objects, a chief address, SSH configs and per-node bandwidth. Here the same
file shape describes TPU hosts: each node carries TPU *chips* instead of GPUs,
SSH gives way to the jax.distributed multi-controller model, and
``network_bandwidth`` generalizes into distinct ICI (intra-slice) and DCN
(cross-slice) bandwidths, which strategy builders use the way the reference
used ``Connectivity`` / bandwidth hints.

Spec shape (all keys optional except ``nodes`` when a file is given)::

    nodes:
      - address: 10.0.0.1
        chips: 4            # TPU chips attached to this host ("gpus" accepted
        chief: true         # for drop-in compat with reference specs)
      - address: 10.0.0.2
        chips: 4
    tpu:
      accelerator: v5p      # informational
      topology: 2x2x2       # physical ICI torus of the slice
      ici_bandwidth_gbps: 900
      dcn_bandwidth_gbps: 50
    mesh:                   # optional logical-mesh override
      data: 4
      model: 2

Reference parity notes:
- chief detection / exactly-one-chief validation: resource_spec.py:160-183
- loopback validation for multi-node: resource_spec.py:185-188
- per-node bandwidth default (1 GbE): resource_spec.py:209-215
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass, field
from enum import Enum
from typing import Dict, List, Optional, Sequence, Tuple

import yaml

_LOOPBACK_ADDRESSES = ("localhost", "127.0.0.1", "0.0.0.0", "::1")

# Reference default bandwidth is 1 GbE (resource_spec.py:209-215). TPU
# defaults reflect v5p-class hardware: ~4800 Gbps ICI per chip aggregate is
# overkill for planning, we use a conservative per-link figure.
DEFAULT_ICI_BANDWIDTH_GBPS = 900.0
DEFAULT_DCN_BANDWIDTH_GBPS = 50.0
DEFAULT_CHIPS_PER_HOST = 4

# Per-chip HBM capacity (GB) and bandwidth (GB/s) by accelerator generation —
# public figures, used by the strategy cost model for memory-feasibility and
# weight-update-time estimates. Longest-substring match on the accelerator
# name (so jax ``device_kind`` strings like "TPU v5 lite" resolve too);
# a `tpu: {hbm_gb, hbm_gb_per_s}` spec entry overrides.
HBM_BY_ACCELERATOR = {
    "v5litepod": (16.0, 819.0),
    "v5 lite": (16.0, 819.0),
    "v5e": (16.0, 819.0),
    "v5p": (95.0, 2765.0),
    # Bare "v5" (real v5p device_kind is "TPU v5") must come after the longer
    # lite variants in match precedence; longest-substring-first ensures that.
    "v5": (95.0, 2765.0),
    "v6 lite": (32.0, 1640.0),
    "v6e": (32.0, 1640.0),
    "v6": (32.0, 1640.0),
    "v4": (32.0, 1228.0),
    "v3": (16.0, 900.0),
    "v2": (8.0, 700.0),
}
# Unknown/unspecified accelerator: assume the smallest-HBM generation so the
# cost model's feasibility check is conservative — an optimistic default
# certifies strategies that OOM at runtime, the exact failure the check
# exists to prevent.
DEFAULT_HBM = min(HBM_BY_ACCELERATOR.values())


def _hbm_key(kind: str) -> Optional[str]:
    """The HBM_BY_ACCELERATOR key a device-kind string resolves to
    (longest-substring-first), or None when the table does not list it."""
    kind = (kind or "").lower()
    for key in sorted(HBM_BY_ACCELERATOR, key=len, reverse=True):
        if key in kind:
            return key
    return None


def hbm_spec_for_kind(kind: str) -> Tuple[float, float]:
    """(HBM GB, HBM GB/s) for a device-kind string (e.g. jax's ``device_kind``
    \"TPU v5 lite\"); DEFAULT_HBM for a kind a hand-written spec names that
    the table does not list. A kind read from a live TPU is checked where it
    is read (:meth:`ResourceSpec.from_local_devices`) and raises instead."""
    key = _hbm_key(kind)
    return HBM_BY_ACCELERATOR[key] if key is not None else DEFAULT_HBM


class DeviceType(Enum):
    """Device kinds (reference: resource_spec.py DeviceType{CPU,GPU})."""

    CPU = "CPU"
    TPU = "TPU"


@dataclass(frozen=True)
class DeviceSpec:
    """One addressable device: ``<host-address>:<type>:<index>``.

    String form mirrors the reference's AutoDist device strings
    (``ip:GPU:0`` → ``ip:TPU:0``) so strategy protos stay readable.
    """

    host_address: str
    device_type: DeviceType = DeviceType.TPU
    device_index: int = 0

    def name_string(self) -> str:
        return f"{self.host_address}:{self.device_type.value}:{self.device_index}"

    @classmethod
    def from_string(cls, s: str) -> "DeviceSpec":
        host, dtype, idx = s.rsplit(":", 2)
        return cls(host, DeviceType(dtype), int(idx))

    def __str__(self) -> str:  # pragma: no cover - convenience
        return self.name_string()


@dataclass
class NodeSpec:
    """One host in the cluster (reference: a ``nodes:`` entry)."""

    address: str
    chips: int = DEFAULT_CHIPS_PER_HOST
    cpus: int = 1
    chief: bool = False
    ssh_config: str = ""  # name of an ``ssh:`` entry (reference parity)


@dataclass
class SSHConfig:
    """Per-host SSH parameters for the coordinator's remote launch
    (reference: ``resource_spec.py`` SSHConfig/SSHConfigMap — username,
    key_file, port, python venv; ``:291-331``). Only the fields the
    subprocess-ssh transport consumes are kept."""

    user: str = ""
    port: int = 22
    key_file: str = ""
    python_venv: str = ""  # sourced before the remote re-exec

    @classmethod
    def from_dict(cls, d: dict) -> "SSHConfig":
        return cls(
            user=str(d.get("user", d.get("username", ""))),
            port=int(d.get("port", 22)),
            key_file=str(d.get("key_file", "")),
            python_venv=str(d.get("python_venv", "")),
        )

    def to_dict(self) -> dict:
        out = {}
        if self.user:
            out["user"] = self.user
        if self.port != 22:
            out["port"] = self.port
        if self.key_file:
            out["key_file"] = self.key_file
        if self.python_venv:
            out["python_venv"] = self.python_venv
        return out


@dataclass
class TPUTopology:
    """Physical slice description: accelerator kind + ICI torus shape.

    ``accelerator=None`` means "unspecified": HBM planning figures fall back
    to the smallest known generation (conservative), and callers that can see
    the runtime (``ResourceSpec.from_local_devices``) fill it in from jax's
    ``device_kind``.
    """

    accelerator: Optional[str] = None
    topology: Optional[Tuple[int, ...]] = None  # e.g. (2, 2, 2)
    ici_bandwidth_gbps: float = DEFAULT_ICI_BANDWIDTH_GBPS
    dcn_bandwidth_gbps: float = DEFAULT_DCN_BANDWIDTH_GBPS
    hbm_gb: Optional[float] = None              # per-chip HBM capacity override
    hbm_gb_per_s: Optional[float] = None  # per-chip HBM bandwidth override (GB/s)

    @property
    def num_chips(self) -> Optional[int]:
        if self.topology is None:
            return None
        return int(math.prod(self.topology))

    def _hbm_defaults(self) -> Tuple[float, float]:
        if self.accelerator is None:
            return DEFAULT_HBM
        return hbm_spec_for_kind(self.accelerator)

    @property
    def hbm_bytes(self) -> float:
        """Per-chip HBM capacity in bytes (spec override or generation table)."""
        gb = self.hbm_gb if self.hbm_gb is not None else self._hbm_defaults()[0]
        return gb * 1e9

    @property
    def hbm_bandwidth_bytes(self) -> float:
        """Per-chip HBM bandwidth in bytes/s."""
        gbs = (
            self.hbm_gb_per_s
            if self.hbm_gb_per_s is not None
            else self._hbm_defaults()[1]
        )
        return gbs * 1e9


def _parse_topology(s) -> Tuple[int, ...]:
    if isinstance(s, (list, tuple)):
        return tuple(int(x) for x in s)
    return tuple(int(x) for x in str(s).lower().split("x"))


class ResourceSpec:
    """Parsed cluster description + derived logical mesh shape.

    Construct from a YAML file path (reference-compatible), a dict, or from
    the local JAX runtime via :meth:`from_local_devices`.
    """

    def __init__(self, resource_file: Optional[str] = None, resource_dict: Optional[dict] = None):
        if resource_file is not None and resource_dict is not None:
            raise ValueError("pass either resource_file or resource_dict, not both")
        if resource_file is not None:
            with open(resource_file, "r", encoding="utf-8") as f:
                resource_dict = yaml.safe_load(f) or {}
            if not isinstance(resource_dict, dict):
                raise ValueError(
                    f"resource spec {resource_file!r} must be a YAML mapping, "
                    f"got {type(resource_dict).__name__}"
                )
        self._raw = dict(resource_dict or {})
        self._nodes: List[NodeSpec] = []
        self._tpu = TPUTopology()
        self._mesh_override: Optional[Dict[str, int]] = None
        self._ssh_configs: Dict[str, SSHConfig] = {}
        self._allow_uneven_chips = bool(self._raw.get("allow_uneven_chips", False))
        self._parse(self._raw)
        self._validate()

    # ------------------------------------------------------------------ parse
    def _parse(self, d: dict) -> None:
        for entry in d.get("nodes", []) or []:
            chips = entry.get("chips", entry.get("gpus", DEFAULT_CHIPS_PER_HOST))
            self._nodes.append(
                NodeSpec(
                    address=str(entry["address"]),
                    chips=int(chips),
                    cpus=int(entry.get("cpus", 1)),
                    chief=bool(entry.get("chief", False)),
                    ssh_config=str(entry.get("ssh_config", "")),
                )
            )
        # Reference-shaped ssh block: either a map of named configs
        # ({"conf1": {...}}, nodes reference by ssh_config) or one flat
        # config applying to every node (stored under "").
        ssh = d.get("ssh", {}) or {}
        if ssh and all(isinstance(v, dict) for v in ssh.values()):
            self._ssh_configs = {
                str(k): SSHConfig.from_dict(v) for k, v in ssh.items()
            }
        elif ssh:
            self._ssh_configs = {"": SSHConfig.from_dict(ssh)}
        if not self._nodes:
            # Single-host default: one loopback node.
            self._nodes.append(NodeSpec(address="localhost", chief=True))

        # Reference behavior: if no node is marked chief, the first is
        # (resource_spec.py:160-183).
        if not any(n.chief for n in self._nodes):
            self._nodes[0].chief = True

        tpu = d.get("tpu", {}) or {}
        self._tpu = TPUTopology(
            accelerator=(
                str(tpu["accelerator"]) if tpu.get("accelerator") is not None else None
            ),
            topology=_parse_topology(tpu["topology"]) if "topology" in tpu else None,
            ici_bandwidth_gbps=float(tpu.get("ici_bandwidth_gbps", DEFAULT_ICI_BANDWIDTH_GBPS)),
            dcn_bandwidth_gbps=float(
                tpu.get("dcn_bandwidth_gbps", d.get("network_bandwidth", DEFAULT_DCN_BANDWIDTH_GBPS))
            ),
            hbm_gb=float(tpu["hbm_gb"]) if "hbm_gb" in tpu else None,
            hbm_gb_per_s=(
                float(tpu["hbm_gb_per_s"]) if "hbm_gb_per_s" in tpu else None
            ),
        )
        mesh = d.get("mesh")
        if mesh:
            self._mesh_override = {str(k): int(v) for k, v in mesh.items()}

    def _validate(self) -> None:
        chiefs = [n for n in self._nodes if n.chief]
        if len(chiefs) != 1:
            raise ValueError(f"exactly one chief required, got {len(chiefs)}")
        addrs = [n.address for n in self._nodes]
        if len(set(addrs)) != len(addrs):
            raise ValueError(f"duplicate node addresses in resource spec: {addrs}")
        # Loopback validation (reference: resource_spec.py:185-188): a
        # multi-node spec must use real addresses so processes can find the
        # coordinator.
        if len(self._nodes) > 1 and any(a in _LOOPBACK_ADDRESSES for a in addrs):
            raise ValueError("multi-node resource specs cannot contain loopback addresses")
        if any(n.chips < 0 for n in self._nodes):
            raise ValueError("chips must be >= 0")
        # TPU homogeneity check (VERDICT open item 6): every host in a real
        # TPU slice carries the SAME chip count — v4/v5/v6 pods expose 4 (or
        # 8) chips per host, uniformly. An uneven `chips:` table therefore
        # almost always means a typo'd spec (the reference's uneven-GPU case
        # needed weighted gradient averaging; here chips are the replica
        # unit, so *semantics* stay exact, but jax.distributed still expects
        # every process to contribute the same local device count and the
        # mesh math inherits that assumption). Fail loudly at parse time —
        # not as a mesh/runtime mismatch three layers later. Genuinely
        # heterogeneous clusters (CPU sims, GPU fleets wearing the TPU spec
        # shape) can declare intent with `allow_uneven_chips: true`.
        counts = sorted({n.chips for n in self._nodes})
        if len(self._nodes) > 1 and len(counts) > 1 and not self._allow_uneven_chips:
            detail = ", ".join(f"{n.address}={n.chips}" for n in self._nodes)
            raise ValueError(
                f"uneven per-host chips counts ({detail}): TPU slices are "
                f"homogeneous — every host exposes the same number of chips "
                f"— so this spec is almost certainly a typo. If this cluster "
                f"really is heterogeneous (CPU simulation, mixed GPU hosts), "
                f"set `allow_uneven_chips: true` in the resource spec. See "
                f"docs/parity.md (heterogeneity position)."
            )
        if self._mesh_override:
            if math.prod(self._mesh_override.values()) != self.num_chips:
                raise ValueError(
                    f"mesh override {self._mesh_override} does not cover "
                    f"{self.num_chips} chips"
                )
        topo_chips = self._tpu.num_chips
        if topo_chips is not None and topo_chips != self.num_chips:
            raise ValueError(
                f"tpu.topology implies {topo_chips} chips but nodes declare {self.num_chips}"
            )
        # Dangling ssh_config references fail HERE, not mid-launch after
        # some workers are already running.
        for n in self._nodes:
            if n.ssh_config and n.ssh_config not in self._ssh_configs:
                raise ValueError(
                    f"node {n.address!r} names ssh_config {n.ssh_config!r} "
                    f"but the spec's ssh block has {sorted(self._ssh_configs)}"
                )

    # ------------------------------------------------------------- properties
    @property
    def nodes(self) -> List[NodeSpec]:
        return list(self._nodes)

    @property
    def num_nodes(self) -> int:
        return len(self._nodes)

    @property
    def chief(self) -> NodeSpec:
        return next(n for n in self._nodes if n.chief)

    @property
    def chief_address(self) -> str:
        return self.chief.address

    @property
    def is_single_node(self) -> bool:
        return len(self._nodes) == 1

    @property
    def num_chips(self) -> int:
        return sum(n.chips for n in self._nodes)

    @property
    def tpu(self) -> TPUTopology:
        return self._tpu

    @property
    def tpu_devices(self) -> List[DeviceSpec]:
        """All TPU chips as DeviceSpecs, chief-first then sorted by address.

        Deterministic ordering across processes matters for the same reason
        the reference sorts its ip:port list (cluster.py:78-80): every
        process must agree on device numbering.
        """
        ordered = sorted(self._nodes, key=lambda n: (not n.chief, n.address))
        out = []
        for node in ordered:
            for i in range(node.chips):
                out.append(DeviceSpec(node.address, DeviceType.TPU, i))
        return out

    @property
    def cpu_devices(self) -> List[DeviceSpec]:
        """Host CPU devices — PS-style reduction destinations live here."""
        ordered = sorted(self._nodes, key=lambda n: (not n.chief, n.address))
        return [DeviceSpec(n.address, DeviceType.CPU, 0) for n in ordered]

    def ssh_config_for(self, address: str) -> Optional[SSHConfig]:
        """SSH parameters for one host: the node's named ``ssh_config``
        entry, else the spec-wide flat config, else None (reference
        SSHConfigMap resolution, resource_spec.py:291-331). Dangling
        references were rejected by ``_validate`` at construction."""
        node = next((n for n in self._nodes if n.address == address), None)
        if node is not None and node.ssh_config:
            return self._ssh_configs[node.ssh_config]
        return self._ssh_configs.get("")

    @property
    def network_bandwidth(self) -> float:
        """Cross-host (DCN) bandwidth in Gbps — the planning-relevant figure
        for multi-host strategies, like the reference's per-node bandwidth."""
        return self._tpu.dcn_bandwidth_gbps

    @property
    def ici_bandwidth(self) -> float:
        return self._tpu.ici_bandwidth_gbps

    # ------------------------------------------------------------------ mesh
    def mesh_shape(self, axes: Sequence[str] = ("data",)) -> Dict[str, int]:
        """Derive a logical mesh shape covering every chip.

        With no override: all chips go on the first axis ("data"), matching
        the reference's pure-data-parallel replica set
        (``architecture.rst:49-51``). An explicit ``mesh:`` block in the spec
        wins; extra requested axes get size 1.
        """
        if self._mesh_override:
            shape = dict(self._mesh_override)
            for ax in axes:
                shape.setdefault(ax, 1)
            return shape
        shape = {ax: 1 for ax in axes}
        first = axes[0] if axes else "data"
        shape[first] = max(self.num_chips, 1)
        return shape

    # ------------------------------------------------------- constructors/io
    @classmethod
    def from_local_devices(cls) -> "ResourceSpec":
        """Build a spec from the current JAX runtime (single- or multi-host).

        Reads the accelerator generation from the runtime's ``device_kind``
        (e.g. "TPU v5 lite") so HBM-feasibility planning uses the real chip's
        capacity instead of the conservative unspecified-accelerator default.
        """
        import jax  # local import: keep L0 importable without jax configured

        n_proc = jax.process_count()
        local = jax.local_device_count()
        d = {}
        dev0 = jax.devices()[0]
        if dev0.platform == "tpu":
            kind = str(dev0.device_kind)
            if _hbm_key(kind) is None:
                # The conservative default is for specs written by hand; a
                # chip that is actually here has a real capacity, and
                # planning (or sizing a KV page pool) against a guess for
                # it would be silently wrong in either direction.
                raise ValueError(
                    f"the local TPU reports device_kind {kind!r}, which "
                    f"HBM_BY_ACCELERATOR does not list "
                    f"({sorted(HBM_BY_ACCELERATOR)}); add its HBM capacity "
                    f"and bandwidth, or pass a resource spec with "
                    f"tpu.hbm_gb / tpu.hbm_gb_per_s")
            d["tpu"] = {"accelerator": kind}
        if n_proc == 1:
            d["nodes"] = [{"address": "localhost", "chips": local, "chief": True}]
        else:
            d["nodes"] = [
                {"address": f"process-{p}", "chips": local, "chief": p == 0}
                for p in range(n_proc)
            ]
        return cls(resource_dict=d)

    def to_dict(self) -> dict:
        return {
            "nodes": [
                {
                    "address": n.address, "chips": n.chips, "cpus": n.cpus,
                    "chief": n.chief,
                    **({"ssh_config": n.ssh_config} if n.ssh_config else {}),
                }
                for n in self._nodes
            ],
            **(
                {
                    "ssh": {
                        k: v.to_dict() for k, v in self._ssh_configs.items()
                    } if "" not in self._ssh_configs
                    else self._ssh_configs[""].to_dict()
                }
                if self._ssh_configs else {}
            ),
            "tpu": {
                **(
                    {"accelerator": self._tpu.accelerator}
                    if self._tpu.accelerator is not None
                    else {}
                ),
                **({"topology": "x".join(map(str, self._tpu.topology))} if self._tpu.topology else {}),
                "ici_bandwidth_gbps": self._tpu.ici_bandwidth_gbps,
                "dcn_bandwidth_gbps": self._tpu.dcn_bandwidth_gbps,
                **({"hbm_gb": self._tpu.hbm_gb} if self._tpu.hbm_gb is not None else {}),
                **(
                    {"hbm_gb_per_s": self._tpu.hbm_gb_per_s}
                    if self._tpu.hbm_gb_per_s is not None
                    else {}
                ),
            },
            **({"mesh": dict(self._mesh_override)} if self._mesh_override else {}),
            **({"allow_uneven_chips": True} if self._allow_uneven_chips else {}),
        }

    def fingerprint(self) -> str:
        """Stable hash of the spec — used in strategy ids so a strategy built
        for one cluster is never silently reused on another."""
        blob = yaml.safe_dump(self.to_dict(), sort_keys=True).encode()
        return hashlib.md5(blob).hexdigest()[:8]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"ResourceSpec(nodes={self.num_nodes}, chips={self.num_chips}, "
            f"chief={self.chief_address!r}, accel={self._tpu.accelerator})"
        )
