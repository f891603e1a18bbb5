"""Resource spec tests (parity: reference tests/test_resource_spec.py)."""
import pytest
import yaml

from autodist_tpu.resource_spec import (
    DeviceSpec,
    DeviceType,
    ResourceSpec,
)


@pytest.fixture
def multi_node_yaml(tmp_path):
    spec = {
        "nodes": [
            {"address": "10.0.0.1", "chips": 4, "chief": True},
            {"address": "10.0.0.2", "chips": 4},
        ],
        "tpu": {"accelerator": "v5p", "topology": "2x2x2", "ici_bandwidth_gbps": 900},
    }
    p = tmp_path / "spec.yml"
    p.write_text(yaml.safe_dump(spec))
    return str(p)


def test_parse_multi_node(multi_node_yaml):
    rs = ResourceSpec(multi_node_yaml)
    assert rs.num_nodes == 2
    assert rs.num_chips == 8
    assert rs.chief_address == "10.0.0.1"
    assert rs.tpu.topology == (2, 2, 2)
    assert rs.tpu.num_chips == 8
    assert not rs.is_single_node


def test_device_ordering_chief_first(multi_node_yaml):
    rs = ResourceSpec(multi_node_yaml)
    devs = rs.tpu_devices
    assert len(devs) == 8
    assert devs[0].host_address == "10.0.0.1"
    assert [d.device_index for d in devs[:4]] == [0, 1, 2, 3]
    assert devs[4].host_address == "10.0.0.2"


def test_device_spec_string_roundtrip():
    d = DeviceSpec("10.0.0.1", DeviceType.TPU, 3)
    assert d.name_string() == "10.0.0.1:TPU:3"
    assert DeviceSpec.from_string("10.0.0.1:TPU:3") == d
    c = DeviceSpec.from_string("localhost:CPU:0")
    assert c.device_type == DeviceType.CPU


def test_default_single_node():
    rs = ResourceSpec(resource_dict={})
    assert rs.num_nodes == 1
    assert rs.chief.chief
    assert rs.is_single_node


def test_first_node_becomes_chief():
    rs = ResourceSpec(resource_dict={"nodes": [{"address": "a", "chips": 2}, {"address": "b", "chips": 2}]})
    assert rs.chief_address == "a"


def test_two_chiefs_rejected():
    with pytest.raises(ValueError, match="exactly one chief"):
        ResourceSpec(
            resource_dict={
                "nodes": [
                    {"address": "a", "chips": 1, "chief": True},
                    {"address": "b", "chips": 1, "chief": True},
                ]
            }
        )


def test_multi_node_loopback_rejected():
    # Parity: reference resource_spec.py:185-188 loopback validation.
    with pytest.raises(ValueError, match="loopback"):
        ResourceSpec(
            resource_dict={
                "nodes": [
                    {"address": "localhost", "chips": 1, "chief": True},
                    {"address": "10.0.0.2", "chips": 1},
                ]
            }
        )


def test_gpus_key_compat():
    # Reference-style specs with "gpus:" still parse; gpus are read as chips.
    rs = ResourceSpec(resource_dict={"nodes": [{"address": "x", "gpus": 2, "chief": True}]})
    assert rs.num_chips == 2


def test_mesh_shape_default_all_data():
    rs = ResourceSpec(resource_dict={"nodes": [{"address": "x", "chips": 8, "chief": True}]})
    assert rs.mesh_shape(("data", "model")) == {"data": 8, "model": 1}


def test_mesh_override():
    rs = ResourceSpec(
        resource_dict={
            "nodes": [{"address": "x", "chips": 8, "chief": True}],
            "mesh": {"data": 4, "model": 2},
        }
    )
    assert rs.mesh_shape(("data", "model")) == {"data": 4, "model": 2}


def test_mesh_override_must_cover_chips():
    with pytest.raises(ValueError, match="mesh override"):
        ResourceSpec(
            resource_dict={
                "nodes": [{"address": "x", "chips": 8, "chief": True}],
                "mesh": {"data": 4},
            }
        )


def test_topology_chip_mismatch_rejected():
    with pytest.raises(ValueError, match="topology"):
        ResourceSpec(
            resource_dict={
                "nodes": [{"address": "x", "chips": 4, "chief": True}],
                "tpu": {"topology": "2x2x2"},
            }
        )


def test_fingerprint_stable_and_distinct(multi_node_yaml):
    rs1 = ResourceSpec(multi_node_yaml)
    rs2 = ResourceSpec(resource_dict=rs1.to_dict())
    assert rs1.fingerprint() == rs2.fingerprint()
    rs3 = ResourceSpec(resource_dict={})
    assert rs1.fingerprint() != rs3.fingerprint()


def test_from_local_devices():
    rs = ResourceSpec.from_local_devices()
    assert rs.num_chips == 8  # conftest forces 8 host-platform devices
    assert rs.is_single_node


def test_unspecified_accelerator_gets_conservative_hbm():
    # ADVICE r1 (medium): an unspecified accelerator must NOT default to the
    # largest-HBM generation — the feasibility check would certify strategies
    # that OOM on smaller chips. Smallest known generation (v2: 8 GB) wins.
    rs = ResourceSpec(resource_dict={})
    assert rs.tpu.accelerator is None
    assert rs.tpu.hbm_bytes == pytest.approx(8.0e9)


def test_device_kind_style_names_resolve():
    # jax device_kind strings ("TPU v4", "TPU v5 lite") are substrings, not
    # prefixes — the table lookup must still land on the right generation.
    from autodist_tpu.resource_spec import TPUTopology

    assert TPUTopology(accelerator="TPU v4").hbm_bytes == pytest.approx(32.0e9)
    assert TPUTopology(accelerator="TPU v5 lite").hbm_bytes == pytest.approx(16.0e9)
    assert TPUTopology(accelerator="TPU v5p").hbm_bytes == pytest.approx(95.0e9)
    assert TPUTopology(accelerator="mystery-chip").hbm_bytes == pytest.approx(8.0e9)


def test_from_local_devices_cpu_mesh_leaves_accelerator_unset():
    # On the CPU test mesh there is no TPU device_kind to read; the spec must
    # stay conservative rather than inventing a generation.
    rs = ResourceSpec.from_local_devices()
    assert rs.tpu.accelerator is None


def test_real_device_kind_strings_for_newer_generations():
    # Real device_kind strings: v5p reports "TPU v5", Trillium "TPU v6 lite".
    from autodist_tpu.resource_spec import TPUTopology

    assert TPUTopology(accelerator="TPU v5").hbm_bytes == pytest.approx(95.0e9)
    assert TPUTopology(accelerator="TPU v6 lite").hbm_bytes == pytest.approx(32.0e9)
    assert TPUTopology(accelerator="TPU v6e").hbm_bytes == pytest.approx(32.0e9)


def test_empty_accelerator_key_stays_unset():
    rs = ResourceSpec(resource_dict={"tpu": {"accelerator": None}})
    assert rs.tpu.accelerator is None
    assert "accelerator" not in rs.to_dict()["tpu"]
    assert rs.fingerprint() == ResourceSpec(resource_dict={}).fingerprint()


def test_uneven_chips_rejected_loudly():
    """TPU-homogeneity check (VERDICT open item 6): uneven per-host chips
    counts are almost always a typo'd spec — fail at parse time with the
    rationale and the override spelled out, not as a mesh mismatch later."""
    nodes = [
        {"address": "10.0.0.1", "chips": 4, "chief": True},
        {"address": "10.0.0.2", "chips": 2},
    ]
    with pytest.raises(ValueError) as e:
        ResourceSpec(resource_dict={"nodes": nodes})
    msg = str(e.value)
    assert "homogeneous" in msg          # the rationale
    assert "10.0.0.1=4" in msg and "10.0.0.2=2" in msg  # the actionable detail
    assert "allow_uneven_chips" in msg   # the declared-intent escape hatch


def test_uneven_chips_allowed_with_declared_intent():
    rs = ResourceSpec(resource_dict={
        "nodes": [
            {"address": "10.0.0.1", "chips": 4, "chief": True},
            {"address": "10.0.0.2", "chips": 2},
        ],
        "allow_uneven_chips": True,
    })
    assert rs.num_chips == 6
    # The intent survives serialization (fingerprint stability + re-parse).
    assert rs.to_dict()["allow_uneven_chips"] is True
    assert ResourceSpec(resource_dict=rs.to_dict()).num_chips == 6


def test_even_multi_node_and_single_node_unaffected():
    ResourceSpec(resource_dict={"nodes": [
        {"address": "10.0.0.1", "chips": 4, "chief": True},
        {"address": "10.0.0.2", "chips": 4},
    ]})
    ResourceSpec(resource_dict={"nodes": [
        {"address": "localhost", "chips": 3, "chief": True},
    ]})  # single node: any count is trivially homogeneous


def test_unknown_live_tpu_device_kind_raises(monkeypatch):
    """A hand-written spec naming an unlisted accelerator gets the
    conservative default; a chip that is actually here does not — its kind
    came from the runtime, so planning against a guessed capacity for it
    would be silently wrong."""
    import jax

    from autodist_tpu.resource_spec import DEFAULT_HBM, hbm_spec_for_kind

    assert hbm_spec_for_kind("TPU v9 hypothetical") == DEFAULT_HBM

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v9 hypothetical"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    with pytest.raises(ValueError, match="does not list"):
        ResourceSpec.from_local_devices()

    _Dev.device_kind = "TPU v5 lite"
    rs = ResourceSpec.from_local_devices()
    assert rs.tpu.accelerator == "TPU v5 lite"
    assert rs.tpu.hbm_bytes == 16e9
