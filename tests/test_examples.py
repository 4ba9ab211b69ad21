"""Examples run end-to-end (subprocess smoke; slow)."""
import subprocess
import sys

import pytest

RUN = dict(capture_output=True, text=True, timeout=540,
           env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})


def run_example(args):
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"   # tests leave it off
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=540, env=env, cwd="/root/repo")
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    return out.stdout


@pytest.mark.slow
def test_quickstart():
    out = run_example(["examples/quickstart.py"])
    assert "max |heterogeneous - dense matmul|" in out
    assert "EDP improvement" in out


@pytest.mark.slow
def test_moe_hetero():
    out = run_example(["examples/moe_hetero.py"])
    assert "combine via EIE-like SpMM kernel" in out


@pytest.mark.slow
def test_dse_search():
    out = run_example(["examples/dse_search.py"])
    assert "AESPA-opt fractions" in out
    assert "vs homogeneous baselines" in out
    assert "Pareto frontier" in out
    assert "joint design × memory search" in out
    assert "winner: hbm_bw=" in out
    assert "Pareto front (runtime × energy × area × memory)" in out
    assert "design × policy co-DSE" in out


@pytest.mark.slow
def test_serve_cluster():
    out = run_example(["examples/serve_cluster.py"])
    assert "every response matches the dense reference" in out
    assert ("p99 wait and per-cluster utilization consistent with the "
            "offline schedule_many_kernels run") in out
    assert "deploy_from_dse" in out
    assert "replayable trace out" in out


@pytest.mark.slow
def test_fleet_serve(tmp_path):
    trace = tmp_path / "fleet.json"
    out = run_example(["examples/fleet_serve.py", "--trace-out", str(trace)])
    assert "2/3 replicas live" in out
    assert "1 requeued by failover" in out
    assert ("every response matches the single-server run to float32 "
            "tolerance") in out
    assert "router aggregated 3 replica snapshots" in out
    assert "preemption:" in out
    assert "autoscaler: 4 replicas launched" in out
    assert trace.exists()


@pytest.mark.slow
def test_serve_lm():
    out = run_example(["examples/serve_lm.py", "--arch", "qwen1.5-0.5b",
                       "--requests", "2", "--gen-len", "6"])
    assert "generated" in out


@pytest.mark.slow
def test_train_lm_short(tmp_path):
    # fresh checkpoint dir: the driver (correctly) resumes from an existing
    # one, which would make this run 0 steps.
    out = run_example(["examples/train_lm.py", "--arch", "qwen1.5-0.5b",
                       "--steps", "6", "--batch", "2", "--seq", "32",
                       "--ckpt-every", "3", "--ckpt-dir", str(tmp_path)])
    assert "ran 6 steps" in out
