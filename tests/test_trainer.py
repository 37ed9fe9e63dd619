"""python -m uccl_tpu.train: the unified trainer entry.

Contract under test: an interrupted run (checkpoint at step k, restart
with --resume) replays the exact trajectory of an uninterrupted run —
the synthetic data stream is a function of the step index and the state
trees are checkpoint-transparent (tests/test_checkpoint.py), so final
losses must agree bit-for-bit at print precision.
"""

import json
import os
import re
import subprocess
import sys

import pytest

pytest.importorskip("orbax.checkpoint")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_COMMON = [
    "--devices", "8", "--mesh", "dp=2,cp=2,tp=2", "--batch", "4",
    "--seq", "32", "--log-every", "0",
]


def _run(extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "uccl_tpu.train"] + _COMMON + extra,
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    return summary, r.stdout


def test_resume_matches_uninterrupted(tmp_path):
    straight, _ = _run(["--steps", "6"])
    ck = str(tmp_path / "ck")
    first, out1 = _run(
        ["--steps", "3", "--ckpt-dir", ck, "--ckpt-every", "3"]
    )
    assert "checkpointed step 3" in out1
    resumed, out2 = _run(["--steps", "6", "--ckpt-dir", ck, "--resume"])
    assert re.search(r"resumed from .*step_3", out2)
    assert resumed["steps"] == 3  # only ran 4..6
    assert resumed["final_loss"] == straight["final_loss"]


def test_mesh_size_mismatch_fails_cleanly(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "uccl_tpu.train", "--devices", "8",
         "--mesh", "dp=3", "--steps", "1"],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO,
    )
    assert r.returncode != 0
    assert "mesh size 3 != device count 8" in r.stderr


def test_joins_launcher_session(tmp_path):
    """UCCL_TPU_COORD et al (set by scripts/launch.py) make the trainer
    join the multi-host session before touching devices."""
    port = _free_port_pair()
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        UCCL_TPU_COORD=f"127.0.0.1:{port}", UCCL_TPU_RANK="0",
        UCCL_TPU_WORLD="1", UCCL_TPU_INIT_JAX="0",
    )
    r = subprocess.run(
        [sys.executable, "-m", "uccl_tpu.train", "--devices", "8",
         "--mesh", "dp=2,cp=2,tp=2", "--batch", "4", "--seq", "32",
         "--steps", "1", "--log-every", "1"],
        capture_output=True, text=True, timeout=420, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "joined session rank 0/1" in r.stdout
    assert "step     1 loss" in r.stdout


def _free_port_pair():
    """The store binds coordinator-port + 1, so reserve the PAIR."""
    import socket

    for _ in range(50):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            cand = s.getsockname()[1]
        try:
            with socket.socket() as s2:
                s2.bind(("127.0.0.1", cand + 1))
            return cand
        except OSError:
            continue
    raise RuntimeError("no free port pair")


def test_two_process_training_matches_single(tmp_path):
    """TRUE multi-controller training: two processes under jax.distributed,
    each owning 4 virtual devices of the same 8-device global mesh, must
    replay the single-controller trajectory exactly — the data is global
    and deterministic, so the sharding substrate is the only variable.
    The 2-process run also checkpoints (collective orbax save), and a
    SINGLE-controller resume from that checkpoint — a different process
    topology — must land on the same trajectory (elastic restart)."""
    single, _ = _run(["--steps", "4"])

    ck = str(tmp_path / "ck2p")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "launch.py"),
         "--nproc", "2", "--coordinator", f"127.0.0.1:{_free_port_pair()}",
         os.path.join(_REPO, "uccl_tpu", "train.py"),
         "--devices", "4", "--mesh", "dp=2,cp=2,tp=2",
         "--batch", "4", "--seq", "32", "--steps", "4", "--log-every", "0",
         "--ckpt-dir", ck, "--ckpt-every", "3"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stdout.splitlines() if '"processes": 2' in l]
    assert line, r.stdout
    multi = json.loads(line[-1].split("] ", 1)[-1])
    assert multi["final_loss"] == single["final_loss"]

    # cross-topology elastic resume: 1 controller picks up the 2-process
    # checkpoint (step 3, saved mid-run) and finishes the trajectory.
    # Tolerance, not equality: restored state carries committed shardings
    # (e.g. adam's count replicated) where a fresh run holds uncommitted
    # scalars, so XLA compiles an equivalent-but-not-identical program —
    # observed drift is 1 ulp at the 6th decimal.
    resumed, out = _run(["--steps", "4", "--ckpt-dir", ck, "--resume"])
    assert re.search(r"resumed from .*step_3", out), out
    assert abs(resumed["final_loss"] - single["final_loss"]) < 1e-4


def test_data_corpus_mode(tmp_path):
    """--data: batches are next-token windows from a memmapped token file,
    deterministic per step (resume-consistent) — loss should drop fast on
    a trivially periodic corpus."""
    import numpy as np

    path = str(tmp_path / "corpus.npy")
    np.save(path, (np.arange(5000) % 200).astype(np.int32))
    out1, _ = _run(["--steps", "3", "--data", path])
    out2, _ = _run(["--steps", "3", "--data", path])
    assert out1["final_loss"] == out2["final_loss"]  # deterministic stream

    bad = str(tmp_path / "bad.npy")
    np.save(bad, np.zeros((4, 4), np.int32))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "uccl_tpu.train"] + _COMMON
        + ["--steps", "1", "--data", bad],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO,
    )
    assert r.returncode != 0 and "1-D integer token array" in r.stderr
