"""python -m uccl_tpu.serve: trainer checkpoints served through the EP
prefill/decode paths (the train -> checkpoint -> serve handoff)."""

import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("orbax.checkpoint")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(mod, extra, timeout=560):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", mod] + extra,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_serve_trained_checkpoint_both_ep_paths(tmp_path):
    ck = str(tmp_path / "ck")
    _run("uccl_tpu.train", [
        "--devices", "8", "--mesh", "dp=2,cp=2,tp=2", "--batch", "4",
        "--seq", "32", "--steps", "2", "--log-every", "0",
        "--ckpt-dir", ck, "--ckpt-every", "2",
    ])
    seqs = {}
    for impl in ("ll", "sort"):
        out = _run("uccl_tpu.serve", [
            "--devices", "8", "--ckpt-dir", ck, "--batch", "8",
            "--prompt-len", "6", "--new-tokens", "8", "--impl", impl,
        ])
        assert f"serving {ck}/step_2" in out
        summary = json.loads(out.strip().splitlines()[-1])
        assert summary["ckpt_step"] == 2 and summary["impl"] == impl
        seqs[impl] = [
            l for l in out.splitlines() if l.startswith("first sequence")
        ][0]
    # greedy decode over the same params: the packed LL path and the sorted
    # throughput path must emit the same tokens (EP-path generation parity)
    assert seqs["ll"] == seqs["sort"]


def test_serve_rejects_mismatched_size_flags(tmp_path):
    ck = str(tmp_path / "ck")
    _run("uccl_tpu.train", [
        "--devices", "8", "--batch", "8", "--seq", "32", "--steps", "1",
        "--log-every", "0", "--ckpt-dir", ck, "--ckpt-every", "1",
    ])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "uccl_tpu.serve", "--devices", "8",
         "--ckpt-dir", ck, "--vocab", "512"],
        capture_output=True, text=True, timeout=420, env=env, cwd=_REPO,
    )
    assert r.returncode != 0
    # conflicts are caught against the recorded config.json (new ckpts) or
    # by leaf-shape checks ("size flags") for config-less checkpoints
    assert ("!= checkpoint config" in r.stderr
            or "pass the training run's size flags" in r.stderr)


def test_serve_cross_topology(tmp_path):
    """A checkpoint trained on an 8-device mesh serves on a 4-device world:
    params restore to host numpy (metadata-derived restore args), so the
    serving topology is free."""
    ck = str(tmp_path / "ck")
    _run("uccl_tpu.train", [
        "--devices", "8", "--batch", "8", "--seq", "32", "--steps", "1",
        "--log-every", "0", "--ckpt-dir", ck, "--ckpt-every", "1",
    ])
    out = _run("uccl_tpu.serve", [
        "--devices", "4", "--ckpt-dir", ck, "--batch", "8",
        "--prompt-len", "4", "--new-tokens", "4",
    ])
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["world"] == 4 and summary["ckpt_step"] == 1


def test_serve_dense_checkpoint(tmp_path):
    """Dense-family trainer checkpoints generate through the cached
    single-shard KV path (config.json routes the family)."""
    ck = str(tmp_path / "ck")
    _run("uccl_tpu.train", [
        "--devices", "8", "--model", "dense", "--batch", "8", "--seq", "32",
        "--steps", "1", "--log-every", "0",
        "--ckpt-dir", ck, "--ckpt-every", "1",
    ])
    out = _run("uccl_tpu.serve", [
        "--devices", "8", "--ckpt-dir", ck, "--batch", "4",
        "--prompt-len", "4", "--new-tokens", "6",
    ])
    assert "(dense)" in out
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["impl"] == "dense" and summary["new_tokens"] == 6
