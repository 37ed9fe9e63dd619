"""scripts/launch.py smoke: spawn 3 local ranks, run a DCN allreduce."""

import os
import socket
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_coordinator() -> str:
    """Pick a coordinator ip:port whose store port (port+1) is also free."""
    for _ in range(50):
        with socket.socket() as a:
            a.bind(("127.0.0.1", 0))
            port = a.getsockname()[1]
        try:
            with socket.socket() as b:
                b.bind(("127.0.0.1", port + 1))
            return f"127.0.0.1:{port}"
        except OSError:
            continue
    raise RuntimeError("no free port pair")


def test_launch_local_allreduce():
    r = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "scripts", "launch.py"),
            "--nproc", "3", "--no-jax-dist",
            "--coordinator", _free_coordinator(),
            os.path.join(_REPO, "examples", "launch_allreduce.py"),
        ],
        capture_output=True, text=True, timeout=180,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    for rank in range(3):
        assert f"rank {rank}/3: allreduce sum=6.0 OK" in r.stdout, r.stdout
        assert f"rank {rank}/3: hierarchical sum=24.0 OK" in r.stdout, r.stdout


def test_launch_failure_propagates(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import sys; sys.exit(3)\n")
    r = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "scripts", "launch.py"),
            "--nproc", "2", "--no-jax-dist",
            "--coordinator", _free_coordinator(), str(bad),
        ],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 3


def test_refuses_multiple_ranks_that_could_reach_for_the_chips(tmp_path):
    """The launcher does not divide a host's chips among processes: without
    JAX_PLATFORMS=cpu (or a --devices argument to the script) --nproc > 1 is
    refused before anything is spawned."""
    script = tmp_path / "never_runs.py"
    script.write_text("print('spawned')\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "scripts", "launch.py"),
            "--nproc", "2", "--no-jax-dist", str(script),
        ],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert r.returncode != 0
    assert "refusing --nproc 2" in r.stderr and "spawned" not in r.stdout
    # the script's own virtual-CPU-mesh flag is enough to let it through
    r = subprocess.run(
        [
            sys.executable, os.path.join(_REPO, "scripts", "launch.py"),
            "--nproc", "2", "--no-jax-dist",
            "--coordinator", _free_coordinator(), str(script),
            "--devices", "2",
        ],
        capture_output=True, text=True, timeout=60, env=env,
    )
    assert r.returncode == 0 and r.stdout.count("spawned") == 2, r.stdout
