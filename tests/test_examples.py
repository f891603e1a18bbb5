"""Smoke tests for the L6 example/benchmark layer (CPU mesh, tiny configs).

The reference's examples were exercised only by its integration CI; here the
universal runner and launcher CLI get direct coverage so flag plumbing can't
rot.
"""
import json
import sys

import pytest

from autodist_tpu.api import AutoDist


@pytest.fixture(autouse=True)
def fresh_autodist():
    AutoDist.reset_default()
    yield
    AutoDist.reset_default()


def test_benchmark_runner_ncf(monkeypatch, capsys):
    sys.path.insert(0, "/root/repo/examples/benchmark")
    import importlib

    train = importlib.import_module("train")
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--model", "ncf", "--strategy", "PSLoadBalancing",
        "--steps", "4", "--warmup", "1", "--batch-size", "32",
    ])
    train.main()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["metric"] == "ncf_examples_per_sec"
    assert result["value"] > 0
    assert result["strategy"] == "PSLoadBalancing"
    assert len(result["first_loss_to_last"]) == 2


def test_benchmark_runner_model_kwargs(monkeypatch, capsys):
    sys.path.insert(0, "/root/repo/examples/benchmark")
    import importlib

    train = importlib.import_module("train")
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--model", "transformer", "--strategy", "Auto",
        "--steps", "3", "--warmup", "1", "--batch-size", "8",
        "--model-kwargs",
        '{"num_layers":1,"d_model":32,"num_heads":4,"d_ff":64,'
        '"vocab_size":128,"max_seq_len":16}',
    ])
    train.main()
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["metric"] == "transformer_tokens_per_sec"
    assert result["value"] > 0


def test_launcher_cli_requires_command():
    from autodist_tpu.runtime.launcher import main

    with pytest.raises(SystemExit):
        main(["--resource-spec", "x.yml"])


def test_launcher_cli_runs_trivial_command(tmp_path):
    from autodist_tpu.runtime.launcher import main

    marker = tmp_path / "ran.txt"
    code = main([
        "--", sys.executable, "-c",
        f"open({str(marker)!r}, 'w').write('yes')",
    ])
    assert code == 0
    assert marker.read_text() == "yes"


def test_long_context_example(monkeypatch, capsys):
    import runpy

    monkeypatch.setattr(sys, "argv", [
        "long_context.py", "--seq-len", "32", "--seq-par", "4",
        "--batch-size", "2", "--steps", "4",
    ])
    runpy.run_path("/root/repo/examples/long_context.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "impl=ring" in out and "->" in out


@pytest.mark.slow
def test_async_ps_example(monkeypatch, capsys):
    import runpy

    import autodist_tpu as ad

    ad.AutoDist.reset_default()
    monkeypatch.setattr(sys, "argv", ["async_ps.py"])
    runpy.run_path("/root/repo/examples/async_ps.py", run_name="__main__")
    out = capsys.readouterr().out
    assert "async :" in out and "sync  :" in out
    line = json.loads([l for l in out.splitlines() if l.startswith("{")][-1])
    assert line["max_lag"] <= line["ssp_bound"]
    ad.AutoDist.reset_default()
