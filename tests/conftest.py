"""Test configuration: run everything on a virtual 8-device CPU mesh.

The reference needed real GPUs + SSH containers for its integration matrix
(``/root/reference/Jenkinsfile:93-131``); the TPU build tests sharding
semantics on a host-platform mesh instead (SURVEY.md §4 lesson), so the whole
suite runs anywhere.
"""
import os

# The env pins the platform for this process and every child a test starts;
# jax.config below covers a jax that was imported (but not yet used) before
# this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("AUTODIST_IS_TESTING", "True")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.device_count() == 8, "tests require the 8-device host-platform mesh"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "integration: slow multi-process tests")
    # Fast lane: `pytest tests/ -m "not slow"` targets a sub-minute smoke
    # tier for pre-commit runs; the plain (slow-inclusive) suite stays the
    # gate. Mark tests/parametrizations that cost multiple seconds.
    config.addinivalue_line("markers", "slow: expensive tests, excluded from the fast lane")


def pytest_addoption(parser):
    # Mirror of reference tests/conftest.py:4-15 --run-integration opt-in.
    parser.addoption(
        "--run-integration",
        action="store_true",
        default=False,
        help="run slow integration tests",
    )


# Tests costing multiple seconds each (measured via --durations; dominated
# by big-model builds and oracle comparisons). Centralized here so the fast
# lane stays curated in one place; matched as nodeid substrings. A renamed
# test silently drops OUT of this list into the fast lane — re-check with
# `pytest tests/ -m "not slow" --durations=20` when the lane exceeds ~60s.
_SLOW_NODEID_PARTS = (
    "test_models.py::test_model_loss_and_grads",
    "test_models.py::test_end_to_end_build",
    "test_models.py::test_batchnorm_high_mean_low_variance_no_nan",
    "test_graft_entry.py::test_dryrun_runs_on_preprovisioned_mesh",
    "test_tensor_parallel.py::test_tp_training_matches_unsharded",
    "test_examples.py::test_long_context_example",
    "test_examples.py::test_benchmark_runner",
    "test_moe_pipeline.py::TestMoE",
    "test_moe_pipeline.py::Test1F1B",
    "test_moe_pipeline.py::TestPipeline",  # also matches TestPipelineRemat, intended
    "test_parallel.py::test_transformer_ring_impl_end_to_end",
    "test_parallel.py::test_seq_parallel_matches_reference",
    "test_parallel.py::test_ring_with_sharded_inputs",
    "test_api.py::test_remat_matches_baseline",
    "test_ops.py::test_transformer_with_flash_impl",
    "test_ops.py::test_gradients_match_reference",
    "test_ops.py::test_nonaligned_seq_falls_back",
    "test_ops.py::test_forward_matches_reference",
    "test_runtime.py::TestCoordinator::test_chief_fail_fast_on_worker_death",
    "test_compressor.py::test_powersgd",
    "test_compressor.py::test_compressed_path_with_sparse_embedding",
    "test_lowering.py::TestMultiStepRun::test_run_matches_sequential_compressed",
    "test_lowering.py::TestMultiStepRun::test_run_matches_sequential_staleness",
    "test_e2e_numeric.py::test_embedding_sparse_step_matches_single_device",
    # Control-flow matrix cases: keep one representative ([AllReduce]) in
    # the fast lane, the other 8 builders run in the full gate.
    "test_e2e_numeric.py::test_scan_model_matches_single_device[PS",
    "test_e2e_numeric.py::test_scan_model_matches_single_device[Partitioned",
    "test_e2e_numeric.py::test_scan_model_matches_single_device[UnevenPartitionedPS",
    "test_e2e_numeric.py::test_scan_model_matches_single_device[RandomAxisPartitionAR",
    "test_e2e_numeric.py::test_scan_model_matches_single_device[Parallax",
    "test_e2e_numeric.py::test_cond_model_matches_single_device[PS",
    "test_e2e_numeric.py::test_cond_model_matches_single_device[Partitioned",
    "test_e2e_numeric.py::test_cond_model_matches_single_device[UnevenPartitionedPS",
    "test_e2e_numeric.py::test_cond_model_matches_single_device[RandomAxisPartitionAR",
    "test_e2e_numeric.py::test_cond_model_matches_single_device[Parallax",
    "test_models.py::test_batchnorm_custom_vjp_matches_autodiff",
    "test_lowering.py::TestGradAccumulation",
    "test_checkpoint.py::test_partitioned_save_restores_into_unpartitioned",
    "test_compressor.py::test_compression_on_data_model_mesh",
    "test_api.py::TestTune::test_tune_picks_a_candidate_and_trains_correctly",
    "test_api.py::test_remat_preserves_sparse_detection",
    "test_models.py::test_sparse_detection",
    "test_models.py::test_space_to_depth_stem_exactly_equivalent",
    "test_examples.py::test_launcher_cli_runs_trivial_command",
    "test_runtime.py::TestCoordinator::test_local_worker_launch_and_join",
    "test_runtime.py::TestStaleCleanup",
    "test_integrations.py::test_flax_module_trains",
    "test_parallel.py::test_trivial_seq_axis_falls_back",
    # r6 re-tier (pytest --durations=40, VERDICT open item 8): the profile
    # test alone was 11-25s of the fast lane.
    "test_tracing.py::test_trace_context_produces_profile",
)


def pytest_collection_modifyitems(config, items):
    slow = pytest.mark.slow
    for item in items:
        if any(part in item.nodeid for part in _SLOW_NODEID_PARTS):
            item.add_marker(slow)
    if config.getoption("--run-integration"):
        return
    skip = pytest.mark.skip(reason="needs --run-integration option to run")
    for item in items:
        if "integration" in item.keywords:
            item.add_marker(skip)
