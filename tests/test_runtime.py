"""Runtime (L1) tests: cluster determinism, env contract, process cleanup,
coordinator launch/monitor semantics.

Reference parity model: tests/integration/test_dist.py ran real 2-host
clusters; here the contract pieces (ordering, env, fail-fast) are unit-tested
and the multi-process jax.distributed path is an opt-in integration test.
"""
import os
import subprocess
import sys
import textwrap
import time

import pytest

from autodist_tpu.const import ENV
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runtime.cluster import (
    Cluster,
    _deterministic_port,
    clean_stale_processes,
    _pidfile_dir,
)
from autodist_tpu.runtime.coordinator import Coordinator, _is_local


TWO_NODE = {
    "nodes": [
        {"address": "10.0.0.2", "chips": 4},
        {"address": "10.0.0.1", "chips": 4, "chief": True},
    ]
}


def make_cluster():
    return Cluster(ResourceSpec(resource_dict=TWO_NODE))


class TestCluster:
    def test_deterministic_port_in_range(self):
        spec = ResourceSpec(resource_dict=TWO_NODE)
        p1 = _deterministic_port(spec)
        p2 = _deterministic_port(ResourceSpec(resource_dict=TWO_NODE))
        assert p1 == p2  # all cluster members agree
        assert 15000 <= p1 < 16000

    def test_process_ordering_chief_first_then_sorted(self):
        c = make_cluster()
        assert c.process_id("10.0.0.1") == 0  # chief first
        assert c.process_id("10.0.0.2") == 1
        assert c.num_processes == 2

    def test_unknown_address_raises(self):
        with pytest.raises(ValueError, match="not in resource spec"):
            make_cluster().process_id("10.9.9.9")

    def test_coordinator_address_is_chief(self):
        c = make_cluster()
        host, port = c.coordinator_address.rsplit(":", 1)
        assert host == "10.0.0.1"
        assert int(port) == c.coordinator_port

    def test_env_contract(self):
        c = make_cluster()
        env = c.env_for_worker("10.0.0.2", strategy_id="20260729T000000M0")
        assert env[ENV.AUTODIST_WORKER.name] == "10.0.0.2"
        assert env[ENV.AUTODIST_PROCESS_ID.name] == "1"
        assert env[ENV.AUTODIST_NUM_PROCESSES.name] == "2"
        assert env[ENV.AUTODIST_STRATEGY_ID.name] == "20260729T000000M0"
        assert env[ENV.AUTODIST_COORDINATOR.name] == c.coordinator_address

    def test_single_node_initialize_noop(self):
        c = Cluster(ResourceSpec(resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]}))
        c.initialize()  # must not call jax.distributed for 1 process
        assert c.num_processes == 1


class TestStaleCleanup:
    def test_dead_pidfile_removed(self):
        d = _pidfile_dir()
        # PID that almost surely doesn't exist (max_pid is usually 4M+, but
        # use a dead child to be exact).
        child = subprocess.Popen([sys.executable, "-c", "pass"])
        child.wait()
        path = os.path.join(d, f"{child.pid}.pid")
        with open(path, "w") as f:
            f.write(str(child.pid))
        clean_stale_processes()
        assert not os.path.exists(path)

    def test_live_stale_process_killed(self):
        d = _pidfile_dir()
        child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
        path = os.path.join(d, f"{child.pid}.pid")
        with open(path, "w") as f:
            f.write(str(child.pid))
        killed = clean_stale_processes()
        assert killed >= 1
        child.wait(timeout=10)
        assert not os.path.exists(path)


class TestCoordinator:
    def test_is_local(self):
        assert _is_local("localhost")
        assert _is_local("127.0.0.1")
        assert not _is_local("10.0.0.9")

    def test_debug_remote_short_circuits_ssh(self, monkeypatch):
        monkeypatch.setenv(ENV.AUTODIST_DEBUG_REMOTE.name, "True")
        c = make_cluster()
        coord = Coordinator(c, argv=["python", "train.py"])
        coord.launch_clients()
        for p in coord.procs:
            assert p.wait(timeout=10) == 0  # "true" stub, no real ssh
        assert not coord.any_failed

    def test_local_worker_launch_and_join(self, tmp_path):
        """A localhost 'remote' worker runs the argv with the role env."""
        out = tmp_path / "worker_env.txt"
        script = tmp_path / "w.py"
        script.write_text(textwrap.dedent(f"""
            import os
            with open({str(out)!r}, "w") as f:
                f.write(os.environ.get("AUTODIST_WORKER", "") + "," +
                        os.environ.get("AUTODIST_PROCESS_ID", ""))
        """))
        spec = ResourceSpec(resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
        c = Cluster(spec)
        coord = Coordinator(c, argv=[sys.executable, str(script)])
        # Manufacture a worker entry: patch the node list post-validation
        # (loopback multi-node specs are rejected by design, but the local
        # subprocess path is exactly what --num-local-processes uses).
        import autodist_tpu.runtime.coordinator as cmod
        workers_env = c.env_for_worker("localhost", "")
        proc = coord._launch_local(workers_env)
        assert proc.wait(timeout=30) == 0
        addr, pid = out.read_text().split(",")
        assert addr == "localhost"
        assert pid == "0"

    def test_chief_fail_fast_on_worker_death(self, tmp_path):
        """Worker exits non-zero → chief process os._exit(1)s.

        Run the whole scenario in a subprocess since fail-fast kills the
        process (reference coordinator.py:98-110 semantics).
        """
        driver = tmp_path / "driver.py"
        driver.write_text(textwrap.dedent("""
            import sys, time
            from autodist_tpu.resource_spec import ResourceSpec
            from autodist_tpu.runtime.cluster import Cluster
            from autodist_tpu.runtime.coordinator import Coordinator

            spec = ResourceSpec(resource_dict={"nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
            c = Cluster(spec)
            coord = Coordinator(c, argv=[sys.executable, "-c", "raise SystemExit(3)"])
            import threading
            proc = coord._launch_local(c.env_for_worker("localhost"))
            coord.procs.append(proc)
            t = threading.Thread(target=coord._monitor, args=("localhost", proc), daemon=True)
            t.start()
            time.sleep(30)   # monitor must kill us long before this
            print("chief survived", flush=True)
        """))
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        res = subprocess.run(
            [sys.executable, str(driver)], env=env, cwd="/root/repo",
            capture_output=True, text=True, timeout=60,
        )
        assert res.returncode == 1, res.stdout + res.stderr
        assert "chief survived" not in res.stdout


def _free_port() -> int:
    """An OS-assigned free TCP port for a test fleet's coordinator.

    Fixed ports collide when two checkouts run this suite concurrently on
    one machine (observed: Gloo rendezvous timing out against the *other*
    run's coordinator); bind-and-release keeps each fleet isolated.
    """
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _scrubbed_cpu_env():
    """Fleet env without the host's accelerator settings (JAX_/XLA_/TPU_
    vars): the 2-process tests must really run on CPU."""
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith(("JAX_", "XLA_", "PALLAS_", "TPU_"))
        and k != "PYTHONPATH"
    }
    env["PYTHONPATH"] = "/root/repo"
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.integration
def test_two_process_cpu_cluster(tmp_path):
    """Full multi-controller path: 2 local processes, jax.distributed,
    a cross-process psum — the reference's 2-host docker CI distilled
    (Jenkinsfile:93-131) onto one machine."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import jax.numpy as jnp
        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() == 4
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import numpy as np
        mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
        x = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")), np.ones((2,), np.float32) * (jax.process_index() + 1), (4,))
        total = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(x)
        assert float(total) == 6.0, float(total)

        # Multi-host checkpoint: every process calls save (process 0 writes,
        # the barrier holds the rest), then all restore and compare.
        import tempfile
        from autodist_tpu.checkpoint import Saver
        ckdir = os.environ["AUTODIST_TEST_CKPT_DIR"]
        saver = Saver(directory=ckdir)
        path = saver.save({"x": x}, step=1)
        loaded = saver.restore(path)
        np.testing.assert_array_equal(loaded["x"], np.array([1, 1, 2, 2], np.float32))
        print("OK", jax.process_index(), flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    # Scrubbed env: drop the host's default accelerator platform so the
    # fleet really runs on CPU.
    env = _scrubbed_cpu_env()
    env["AUTODIST_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(), base_env=env
    )
    assert code == 0


@pytest.mark.integration
def test_two_process_autodist_training(tmp_path):
    """Full AutoDist pipeline across 2 processes started simultaneously:
    strategy built on the chief and broadcast over the runtime (no shared
    launch env), sharded train step, per-process batch shards assembled via
    the plan, identical losses everywhere."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from autodist_tpu.api import AutoDist
        from autodist_tpu.model_item import OptimizerSpec
        import autodist_tpu.strategy as S

        assert jax.process_count() == 2
        ad = AutoDist(strategy_builder=S.AllReduce())   # spec from runtime

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        params = {"w": np.ones((4, 2), np.float32)}
        # Global batch 8 = 4 rows per process; same global data everywhere,
        # each process holds its own slice.
        full = np.arange(32, dtype=np.float32).reshape(8, 4) / 32.0
        local = full[jax.process_index() * 4:(jax.process_index() + 1) * 4]
        example = {"x": np.zeros((8, 4), np.float32)}
        step = ad.build(loss_fn, params, example,
                        optimizer=OptimizerSpec("sgd", {"learning_rate": 0.1}))
        state = step.init(params)
        batch = step.plan.global_batch_from_local({"x": local})
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])

        # Oracle: single-device math on the full batch.
        want_loss = float((((full @ np.ones((4, 2), np.float32)) ** 2)).mean())
        np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
        print("OK", jax.process_index(), loss, flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    env = _scrubbed_cpu_env()
    # Regression: any earlier chief-side build() in the parent process
    # exports AUTODIST_STRATEGY_ID into os.environ; a fleet inheriting it
    # sent workers down the coordinator-shipped-strategy path (waiting 60s
    # for a never-shipped file) while the chief hung in the runtime
    # broadcast. The launcher must scrub role vars from the base env.
    env[ENV.AUTODIST_STRATEGY_ID.name] = "20990101T000000-stale-id-from-parent"
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(), base_env=env
    )
    assert code == 0


def test_fleet_launcher_scrubs_inherited_role_vars():
    """Unit-level pin of the same contract (no fleet spin-up): the env a
    fleet child receives must not carry the parent's role/strategy vars."""
    import autodist_tpu.runtime.launcher as launcher_mod

    captured = []

    class FakeProc:
        def __init__(self, argv, env=None, **kw):
            captured.append(env)
        def wait(self, timeout=None):
            return 0

    orig = launcher_mod.subprocess.Popen
    launcher_mod.subprocess.Popen = FakeProc
    try:
        base = {
            "PATH": "/usr/bin",
            ENV.AUTODIST_STRATEGY_ID.name: "stale",
            ENV.AUTODIST_WORKER.name: "10.0.0.9",
            "AUTODIST_MIN_LOG_LEVEL": "DEBUG",   # behavior knob: must survive
            "AUTODIST_TEST_CKPT_DIR": "/tmp/x",  # user var: must survive
        }
        launcher_mod._launch_local_fleet(["true"], 2, 15900, base_env=base)
    finally:
        launcher_mod.subprocess.Popen = orig
    assert captured
    for env in captured:
        assert env.get(ENV.AUTODIST_STRATEGY_ID.name) != "stale"
        assert env.get("AUTODIST_MIN_LOG_LEVEL") == "DEBUG"
        assert env.get("AUTODIST_TEST_CKPT_DIR") == "/tmp/x"
        assert env.get(ENV.AUTODIST_WORKER.name) != "10.0.0.9"


@pytest.mark.integration
def test_two_process_dataloader_feed(tmp_path):
    """DataLoader on multi-host: each process loads only its slice; the
    loader assembles global sharded batches via the plan (the remapper
    feed contract in reverse). Windowed training over the loader works."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from autodist_tpu.api import AutoDist
        from autodist_tpu.data import DataLoader
        from autodist_tpu.model_item import OptimizerSpec
        import autodist_tpu.strategy as S

        assert jax.process_count() == 2
        ad = AutoDist(strategy_builder=S.AllReduce())

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        params = {"w": np.ones((4, 2), np.float32)}
        example = {"x": np.zeros((8, 4), np.float32)}  # global batch 8
        step = ad.build(loss_fn, params, example,
                        optimizer=OptimizerSpec("sgd", {"learning_rate": 0.1}))
        state = step.init(params)

        # Each process owns half the dataset rows (16 of 32).
        full = np.arange(32 * 4, dtype=np.float32).reshape(32, 4) / 128.0
        local = full[jax.process_index() * 16:(jax.process_index() + 1) * 16]
        loader = DataLoader({"x": local}, batch_size=4, epochs=1,
                            shuffle=False, plan=step.plan)
        batches = list(loader)
        assert len(batches) == 4, len(batches)
        b0 = batches[0]
        assert b0["x"].shape == (8, 4), b0["x"].shape  # global = 2x local
        # Global batch 0 row content: process 0 rows 0-3 then process 1
        # rows 16-19 (deterministic order, shuffle off). The array spans
        # both processes, so assemble it for the value check.
        from jax.experimental import multihost_utils
        got = multihost_utils.process_allgather(b0["x"], tiled=True)
        want = np.concatenate([full[0:4], full[16:20]])
        np.testing.assert_allclose(got, want)

        state, metrics = step.run(state, b0, 2)
        assert np.isfinite(float(metrics["loss"][-1]))
        print("OK", jax.process_index(), flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    env = _scrubbed_cpu_env()
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(), base_env=env
    )
    assert code == 0


@pytest.mark.integration
def test_two_process_sharded_checkpoint(tmp_path):
    """v2 sharded checkpoints on a real 2-process fleet: each process
    writes only its own shard blocks (no process-0 global assembly —
    process_allgather is rigged to fail), and the sharded restore reads
    back block-wise into the same sharding (VERDICT r1 next #5)."""
    script = tmp_path / "ckpt.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert jax.process_count() == 2

        mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
        sharding = NamedSharding(mesh, P("data", None))
        local = np.arange(8, dtype=np.float32).reshape(2, 4) + 10 * jax.process_index()
        x = jax.make_array_from_process_local_data(sharding, local, (4, 4))
        replicated = jax.device_put(
            np.float32(3.5), NamedSharding(mesh, P()))

        # Any global-assembly fallback on a distributed array leaf is a
        # failure: every jax.Array must ride the block layout. (The save
        # barrier itself legitimately uses collectives, so the guard sits
        # on the saver's assembly helper, not on process_allgather.)
        import autodist_tpu.checkpoint.saver as saver_mod
        _orig_to_host = saver_mod._to_host
        def _banned(leaf):
            # Local shard conversion is fine; assembling a globally-sharded
            # array (the process_allgather branch) is the failure mode.
            if hasattr(leaf, "sharding") and not leaf.is_fully_addressable:
                raise AssertionError("_to_host on a non-addressable array: "
                                     "a sharded leaf took the "
                                     "global-assembly path")
            return _orig_to_host(leaf)
        saver_mod._to_host = _banned

        from autodist_tpu.checkpoint import Saver
        saver = Saver(directory=os.environ["AUTODIST_TEST_CKPT_DIR"])
        path = saver.save({"w": x, "c": replicated}, step=2)

        meta = Saver.read_metadata(path)
        shards = meta["entries"]["w"]["shards"]
        assert len(shards) == 4, meta
        for sh in shards:
            assert os.path.exists(os.path.join(path, sh["file"]))

        # Sharded restore: block-wise reads into the destination sharding.
        target = {"w": jax.ShapeDtypeStruct((4, 4), np.float32),
                  "c": jax.ShapeDtypeStruct((), np.float32)}
        restored = saver.restore(path, target=target,
                                 shardings={"w": sharding,
                                            "c": NamedSharding(mesh, P())})
        got_local = {tuple(int(v) for v in (s.index[0].start or 0,)):
                     np.asarray(s.data) for s in restored["w"].addressable_shards}
        for s in x.addressable_shards:
            key = (int(s.index[0].start or 0),)
            np.testing.assert_array_equal(got_local[key], np.asarray(s.data))
        assert float(restored["c"]) == 3.5
        print("OK", jax.process_index(), flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    env = _scrubbed_cpu_env()
    env["AUTODIST_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(), base_env=env
    )
    assert code == 0


@pytest.mark.integration
def test_two_process_async_checkpoint(tmp_path):
    """Async (block=False) save on a real 2-process fleet (VERDICT r2 #7):
    the background writer's barriers ride the coordination service, so
    device collectives issued by the main thread WHILE the write is in
    flight don't deadlock against them; wait() then finalizes and the
    checkpoint restores."""
    script = tmp_path / "async_ckpt.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        assert jax.process_count() == 2

        mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
        sharding = NamedSharding(mesh, P("data", None))
        local = np.arange(32, dtype=np.float32).reshape(2, 16) + 10 * jax.process_index()
        x = jax.make_array_from_process_local_data(sharding, local, (4, 16))

        from autodist_tpu.checkpoint import Saver
        saver = Saver(directory=os.environ["AUTODIST_TEST_CKPT_DIR"])
        path = saver.save({"w": x}, step=1, block=False)
        # Training-style device collectives while the writer is in flight:
        # these enqueue in launch order on the main thread; the writer's
        # coordination-service barriers must not interleave with them.
        y = jax.device_put(np.ones((4, 16), np.float32), sharding)
        for _ in range(5):
            y = jax.jit(
                lambda a: jax.lax.with_sharding_constraint(a * 2.0, sharding)
            )(y)
        total = float(jnp.sum(y))
        saver.wait()
        meta = Saver.read_metadata(path)
        assert len(meta["entries"]["w"]["shards"]) == 4, meta
        restored = saver.restore(path)
        got = np.asarray(restored["w"])
        want = np.concatenate([
            np.arange(32, dtype=np.float32).reshape(2, 16),
            np.arange(32, dtype=np.float32).reshape(2, 16) + 10,
        ])
        np.testing.assert_array_equal(got, want)
        assert total == 32 * 4 * 16
        print("OK", jax.process_index(), flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    env = _scrubbed_cpu_env()
    env["AUTODIST_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(), base_env=env
    )
    assert code == 0


@pytest.mark.integration
def test_two_process_measured_tune_elects_same_winner(tmp_path):
    """Fleet tune(): both processes time the candidates in lockstep, the
    chief's measurements decide, and every process rebuilds the same
    winner (VERDICT r1 next #8 — measured election, no cost-model
    fallback)."""
    script = tmp_path / "tune.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from autodist_tpu.api import AutoDist
        from autodist_tpu.model_item import OptimizerSpec
        import autodist_tpu.strategy as S

        assert jax.process_count() == 2
        ad = AutoDist(strategy_builder=S.AllReduce())

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        params = {"w": np.ones((8, 4), np.float32)}
        example = {"x": np.zeros((8, 8), np.float32)}
        step = ad.tune(
            loss_fn, params, example, window=2,
            candidates=[("AR", S.AllReduce()), ("PSLB", S.PSLoadBalancing())],
            optimizer=OptimizerSpec("sgd", {"learning_rate": 0.1}),
        )
        # Every process must have elected the same strategy (same builder
        # class and same per-var synchronizers); print for cross-checking.
        kinds = ",".join(type(n.synchronizer).__name__
                         for n in ad.strategy.node_config)
        print(f"ELECTED {jax.process_index()} {type(ad.strategy_builder).__name__} {kinds}",
              flush=True)
        # And the winner trains.
        state = step.init(params)
        batch = step.plan.global_batch_from_local(
            {"x": np.ones((4, 8), np.float32)})
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("OK", jax.process_index(), flush=True)
    """))
    import subprocess as sp

    # Run the fleet launcher in a subprocess so both workers' stdout can be
    # captured and the elected winners compared across processes.
    env = _scrubbed_cpu_env()
    proc = sp.run(
        [sys.executable, "-c", textwrap.dedent(f"""
            import sys
            sys.path.insert(0, "/root/repo")
            from autodist_tpu.runtime.launcher import _launch_local_fleet
            import os
            env = {{k: v for k, v in os.environ.items()}}
            code = _launch_local_fleet(
                [sys.executable, "-u", {str(script)!r}], 2,
                coordinator_port={_free_port()}, base_env=env)
            sys.exit(code)
        """)],
        env=env, stdout=sp.PIPE, stderr=sp.STDOUT, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout[-4000:]
    # Regex, not line-splitting: the two processes' prints can interleave
    # on one line in the merged stream.
    import re

    elected = re.findall(r"ELECTED (\d) (\S+) (\S+?)(?=ELECTED|\s|$)", proc.stdout)
    assert len(elected) == 2, proc.stdout[-4000:]
    winners = {(builder, kinds) for _, builder, kinds in elected}
    assert len(winners) == 1, f"processes elected different winners: {elected}"


@pytest.mark.integration
def test_two_process_file_backed_feed(tmp_path):
    """Multi-host file-backed feed: both processes mmap the SAME dataset
    directory (shared filesystem), keep disjoint row ranges via
    ``from_files(process_slice=True)``, and the plan assembles global
    batches — the storage-layer rendering of the remapper feed contract."""
    import numpy as np

    from autodist_tpu.data import write_dataset

    full = np.arange(32 * 4, dtype=np.float32).reshape(32, 4) / 128.0
    ds_dir = tmp_path / "ds"
    write_dataset(str(ds_dir), {"x": full}, shard_rows=12)  # 12,12,8: ranges cross shards

    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from autodist_tpu.api import AutoDist
        from autodist_tpu.data import DataLoader
        from autodist_tpu.model_item import OptimizerSpec
        import autodist_tpu.strategy as S

        assert jax.process_count() == 2
        ad = AutoDist(strategy_builder=S.AllReduce())

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        params = {"w": np.ones((4, 2), np.float32)}
        example = {"x": np.zeros((8, 4), np.float32)}  # global batch 8
        step = ad.build(loss_fn, params, example,
                        optimizer=OptimizerSpec("sgd", {"learning_rate": 0.1}))
        state = step.init(params)

        loader = DataLoader.from_files(
            os.environ["AUTODIST_TEST_DS_DIR"], batch_size=4, epochs=1,
            shuffle=False, plan=step.plan, process_slice=True)
        assert loader.n_rows == 16  # this process's half of 32
        batches = list(loader)
        assert len(batches) == 4, len(batches)
        b0 = batches[0]
        assert b0["x"].shape == (8, 4), b0["x"].shape

        full = np.arange(32 * 4, dtype=np.float32).reshape(32, 4) / 128.0
        from jax.experimental import multihost_utils
        got = multihost_utils.process_allgather(b0["x"], tiled=True)
        # Process 0 owns rows 0-15, process 1 rows 16-31; batch 0 is each
        # process's first 4 local rows, concatenated in process order.
        want = np.concatenate([full[0:4], full[16:20]])
        np.testing.assert_allclose(got, want)

        state, metrics = step.run(state, b0, 2)
        assert np.isfinite(float(metrics["loss"][-1]))
        print("OK", jax.process_index(), flush=True)
    """))
    from autodist_tpu.runtime.launcher import _launch_local_fleet

    env = _scrubbed_cpu_env()
    env["AUTODIST_TEST_DS_DIR"] = str(ds_dir)
    code = _launch_local_fleet(
        [sys.executable, str(script)], 2, coordinator_port=_free_port(),
        base_env=env,
    )
    assert code == 0


class TestRestartSupervisor:
    """launch_supervised: the checkpoint-resume loop over launch()."""

    def _sup(self, monkeypatch, codes, max_restarts):
        import autodist_tpu.runtime.launcher as L

        calls = []

        def fake_launch(spec, argv, num_local_processes=0,
                        coordinator_port=None, extra_env=None,
                        supervised=False, ft_config=None):
            self.last_supervised = supervised
            calls.append((extra_env or {}).get("AUTODIST_RESTART"))
            return codes[len(calls) - 1]

        monkeypatch.setattr(L, "launch", fake_launch)
        monkeypatch.setattr("time.sleep", lambda s: None)
        rc = L.launch_supervised(
            None, ["true"], max_restarts=max_restarts, restart_backoff_s=0)
        return rc, calls

    def test_restarts_until_success(self, monkeypatch):
        rc, calls = self._sup(monkeypatch, [1, 1, 0], max_restarts=3)
        assert rc == 0
        assert calls == ["0", "1", "2"]  # AUTODIST_RESTART exported per attempt

    def test_gives_up_after_budget(self, monkeypatch):
        rc, calls = self._sup(monkeypatch, [7, 7], max_restarts=1)
        assert rc == 7
        assert len(calls) == 2

    def test_zero_restarts_is_plain_launch(self, monkeypatch):
        # max_restarts=0: no loop to protect, keep exact unsupervised
        # fail-fast semantics (supervised=False through to launch()).
        rc, calls = self._sup(monkeypatch, [3], max_restarts=0)
        assert rc == 3
        assert len(calls) == 1
        assert self.last_supervised is False

    def test_restart_budget_runs_supervised(self, monkeypatch):
        self._sup(monkeypatch, [0], max_restarts=2)
        assert self.last_supervised is True


def test_supervised_failure_action_replaces_os_exit(tmp_path):
    """Coordinator.set_failure_action: worker death under supervision
    terminates the chief via the action instead of os._exit(1)ing the
    launcher process (which would kill the restart loop itself)."""
    import threading
    import time as _time

    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.cluster import Cluster
    from autodist_tpu.runtime.coordinator import Coordinator

    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    c = Cluster(spec)
    coord = Coordinator(c, argv=[sys.executable, "-c", "raise SystemExit(3)"])
    fired = threading.Event()
    coord.set_failure_action(fired.set)
    proc = coord._launch_local(c.env_for_worker("localhost"))
    coord.procs.append(proc)
    t = threading.Thread(target=coord._monitor, args=("localhost", proc),
                         daemon=True)
    t.start()
    assert fired.wait(timeout=30)   # action ran...
    _time.sleep(0.2)                # ...and we are demonstrably still alive
    assert coord.any_failed


def test_coordinator_extra_env_reaches_local_workers():
    """extra_env (the supervisor's AUTODIST_RESTART) must reach worker
    processes, and role env must still win over it."""
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.runtime.cluster import Cluster
    from autodist_tpu.runtime.coordinator import Coordinator

    spec = ResourceSpec(resource_dict={
        "nodes": [{"address": "localhost", "chips": 8, "chief": True}]})
    c = Cluster(spec)
    coord = Coordinator(
        c, argv=["true"],
        extra_env={"AUTODIST_RESTART": "2",
                   ENV.AUTODIST_WORKER.name: "must-not-win"})
    env = {**coord.extra_env, **c.env_for_worker("localhost")}
    assert env["AUTODIST_RESTART"] == "2"
    assert env[ENV.AUTODIST_WORKER.name] != "must-not-win"


@pytest.mark.integration
def test_supervised_crash_resume(tmp_path, monkeypatch):
    """End-to-end fault tolerance: a 2-process fleet whose chief crashes
    mid-training on the first attempt; the supervisor relaunches, the
    script's init_or_restore resumes from the latest checkpoint, and the
    final checkpoint reflects the full step count with no repeated work."""
    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        from autodist_tpu.runtime.launcher import initialize_from_env
        initialize_from_env()
        import jax
        import numpy as np
        from autodist_tpu.api import AutoDist
        from autodist_tpu.checkpoint import Saver
        from autodist_tpu.model_item import OptimizerSpec
        import autodist_tpu.strategy as S

        ad = AutoDist(strategy_builder=S.AllReduce())

        def loss_fn(params, batch):
            return ((batch["x"] @ params["w"]) ** 2).mean()

        params = {"w": np.ones((4, 2), np.float32)}
        batch = {"x": np.ones((8, 4), np.float32) / 4.0}
        step = ad.build(loss_fn, params, batch,
                        optimizer=OptimizerSpec("sgd", {"learning_rate": 0.05}))
        saver = Saver(directory=os.environ["AUTODIST_TEST_CKPT_DIR"])
        state = step.init_or_restore(params, saver)
        start = int(state.step)
        restart = int(os.environ.get("AUTODIST_RESTART", "0"))
        # Attempt 0 must start fresh; attempt 1 must resume past the crash.
        assert (start == 0) == (restart == 0), (start, restart)
        batch = step.plan.global_batch_from_local(
            {"x": batch["x"][jax.process_index() * 4:(jax.process_index() + 1) * 4]})
        while int(state.step) < 4:
            state, _ = step(state, batch)
            step.save(saver, state)
            if restart == 0 and int(state.step) == 2:
                os._exit(1)   # simulated mid-training crash on every process
        print("OK", jax.process_index(), int(state.step), flush=True)
    """))
    import autodist_tpu.runtime.launcher as L

    env = _scrubbed_cpu_env()
    env["AUTODIST_TEST_CKPT_DIR"] = str(tmp_path / "ckpt")
    port = _free_port()

    def launch_with_scrubbed_env(spec, argv, num_local_processes=0,
                                 coordinator_port=None, extra_env=None,
                                 supervised=False):
        base = {**env, **(extra_env or {})}
        return L._launch_local_fleet(argv, 2, coordinator_port=port,
                                     base_env=base)

    monkeypatch.setattr(L, "launch", launch_with_scrubbed_env)
    rc = L.launch_supervised(None, [sys.executable, str(script)],
                             max_restarts=2, restart_backoff_s=0.1)
    assert rc == 0
    import numpy as np

    from autodist_tpu.checkpoint import Saver

    final = Saver(directory=str(tmp_path / "ckpt")).restore_latest()
    assert int(np.asarray(final["step"])) == 4
