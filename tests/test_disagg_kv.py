"""The prefill→decode KV handoff, regression-tested (not just an example).

examples/disagg_kv.py runs the chunk-streamed disaggregated serving pair
(PrefillWorker/DecodeWorker over the P2P one-sided write path, with the
prefix-reuse cache) across two real processes and asserts the
disaggregated output matches single-worker generation bit-for-bit — with
at least one prefix-cache hit counted. Promoting that assertion here makes
the KV-transfer contract a tested invariant: the script exits non-zero on
any token mismatch OR a hitless run, so a returncode check carries both
guarantees. The --metrics-out dump is additionally asserted to carry the
disagg telemetry series (p2p bytes per verb, KV stream chunks, prefix
cache hits) — the same series scripts/check_obs.py --disagg validates in
CI."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(extra, timeout=420):
    env = dict(os.environ)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", "disagg_kv.py"),
         "--new-tokens", "12", *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=_REPO,
    )


# ~25s wall (two fresh jax processes + compiles): marked slow to protect the
# tier-1 suite's global timeout budget. The unfiltered CI pytest job and
# scripts/qa.sh still run it on every change.
@pytest.mark.slow
def test_disagg_stream_exact_match_and_metrics(tmp_path):
    metrics = tmp_path / "disagg_metrics.prom"
    r = _run(["--metrics-out", str(metrics)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "disaggregated tokens match single-worker generation: True" in r.stdout
    # the run demonstrably reused cached prefix tokens
    assert "prefix-cache hit" in r.stdout
    text = metrics.read_text()
    for series in ("p2p_bytes_total", "kv_stream_chunks_total",
                   "prefix_cache_hits_total", "prefix_cache_misses_total",
                   "serving_prefill_tokens_total"):
        assert series in text, f"missing {series} in --metrics-out dump"

    def sample(prefix):
        vals = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
                if ln.startswith(prefix)]
        assert vals, f"no sample for {prefix}"
        return sum(vals)

    assert sample('p2p_bytes_total{verb="write"}') > 0
    assert sample('kv_stream_chunks_total{role="tx"}') > 0
    assert sample("prefix_cache_hits_total") >= 1


@pytest.mark.slow
def test_disagg_kv_one_shot_exact_match():
    """The original whole-cache advertise→write→notif handoff (kept for
    the compressed/elastic wire demos) still matches the oracle exactly."""
    r = _run(["--one-shot"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "disaggregated tokens match single-worker generation: True" in r.stdout
