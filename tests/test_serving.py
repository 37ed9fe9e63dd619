"""Continuous-batching engine: scheduler/slot properties + oracle exactness.

Two layers of test:

* **Properties** (stub backend, host-only, fast): FIFO admission order, no
  leaked slots after drain, retirement on EOS and on max-tokens,
  backpressure under a bounded queue, metrics conservation
  (submitted == completed + active + queued + rejected), lowest-slot-first
  pool reuse, and the chunked-prefill scheduling contract — cursor
  resumption, budget-gated admission, and the decode stall bound (no
  active slot goes more than one step without a decode while another
  request prefills).
* **Oracle exactness** (real models): with ≥2 slots and staggered
  mixed-length arrivals, every request's tokens are bit-identical to the
  one-shot ``generate`` oracle — for the dense stack and for the EP MoE
  stack on a multi-shard mesh (whose oracle is the world-1 server; the
  repo's parity tests prove world-independence separately) — in
  whole-prompt mode AND under chunked prefill (chunk sizes odd /
  non-dividing, pow2, and ≥ the longest prompt).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu.serving import (
    DenseBackend, MoEBackend, RequestState, ServingEngine, SlotPool,
)
from uccl_tpu.serving.metrics import percentile


class _StubBackend:
    """Deterministic token emitter: prefill emits 0, the i-th decode step
    emits i — EOS behavior is then fully predictable with no model."""

    def __init__(self, n_slots=2, max_seq=64):
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.n_decodes = 0

    def prefill(self, tokens, lens, mask):
        return np.zeros(self.n_slots, np.int32)

    def decode(self, tokens, active):
        self.n_decodes += 1
        return np.full(self.n_slots, self.n_decodes, np.int32)


class _ChunkStubBackend:
    """Chunk-aware stub: records every backend call (kind, masked slots,
    start offsets) so scheduling order and cursor resumption are directly
    assertable. Prefill emits 100, the i-th decode step emits i."""

    def __init__(self, n_slots=2, max_seq=64):
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.n_decodes = 0
        self.calls = []

    def prefill(self, tokens, lens, mask, start=None):
        if start is None:
            start = np.zeros(self.n_slots, np.int32)
        slots = tuple(int(s) for s in np.flatnonzero(mask))
        self.calls.append(
            ("prefill", slots, tuple(int(start[s]) for s in slots))
        )
        return np.full(self.n_slots, 100, np.int32)

    def decode(self, tokens, active):
        self.n_decodes += 1
        self.calls.append(
            ("decode", tuple(int(s) for s in np.flatnonzero(active)))
        )
        return np.full(self.n_slots, self.n_decodes, np.int32)


def _prompt(rng, n):
    return rng.integers(0, 64, n).astype(np.int32)


class TestSchedulerProperties:
    def test_fifo_admission_order(self):
        eng = ServingEngine(_StubBackend(n_slots=2))
        reqs = [eng.submit([1, 2], max_new_tokens=3) for _ in range(7)]
        eng.drain()
        seqs = [r.admit_seq for r in reqs]
        assert seqs == sorted(seqs), "admission must preserve FIFO order"
        assert all(r.state is RequestState.FINISHED for r in reqs)

    def test_no_leaked_slots_after_drain(self):
        eng = ServingEngine(_StubBackend(n_slots=3))
        for i in range(8):
            eng.submit([1, 2, 3], max_new_tokens=2 + i % 3)
        eng.drain()
        assert eng.pool.leaked() == 0
        assert eng.pool.total_admits == eng.pool.total_frees == 8
        assert eng.pool.high_water <= eng.pool.n_slots

    def test_retirement_on_max_tokens(self):
        eng = ServingEngine(_StubBackend(n_slots=1))
        r = eng.submit([5], max_new_tokens=4)
        eng.drain()
        assert r.finish_reason == "length"
        assert r.n_generated == 4

    def test_retirement_on_eos(self):
        # stub emits 0 (prefill), 1, 2, ... — eos_id=2 retires mid-decode
        # after exactly 3 tokens, well under the 10-token budget
        eng = ServingEngine(_StubBackend(n_slots=1))
        r = eng.submit([5], max_new_tokens=10, eos_id=2)
        eng.drain()
        assert r.finish_reason == "eos"
        assert r.out_tokens == [0, 1, 2]

    def test_eos_at_prefill(self):
        eng = ServingEngine(_StubBackend(n_slots=1))
        r = eng.submit([5], max_new_tokens=10, eos_id=0)
        eng.drain()
        assert r.finish_reason == "eos" and r.out_tokens == [0]

    def test_backpressure_rejects_when_full(self):
        # 2 slots + queue bound 2: submissions beyond slots+queue reject
        eng = ServingEngine(_StubBackend(n_slots=2), max_queue=2)
        results = [eng.submit([1], max_new_tokens=3) for _ in range(8)]
        rejected = [r for r in results if r is None]
        accepted = [r for r in results if r is not None]
        assert len(rejected) == 6  # nothing admitted before the first step
        assert eng.metrics.rejected == 6
        eng.drain()
        assert eng.metrics.completed == len(accepted)
        assert eng.pool.leaked() == 0

    def test_queue_drains_between_steps(self):
        # backpressure QUEUES when slots are busy but the queue has room
        eng = ServingEngine(_StubBackend(n_slots=1), max_queue=8)
        reqs = [eng.submit([1], max_new_tokens=2) for _ in range(4)]
        assert all(r is not None for r in reqs)
        snap = eng.snapshot()
        assert snap["queued"] == 4 and snap["active"] == 0
        eng.drain()
        assert all(r.state is RequestState.FINISHED for r in reqs)

    def test_metrics_snapshot_consistency(self):
        eng = ServingEngine(_StubBackend(n_slots=2), max_queue=3)
        for _ in range(9):
            eng.submit([1, 2], max_new_tokens=6)
        # mid-flight and at every step boundary, requests are conserved:
        for _ in range(3):
            eng.step()
            s = eng.snapshot()
            assert (s["submitted"]
                    == s["completed"] + s["active"] + s["queued"]
                    + s["rejected"]), s
        eng.drain()
        s = eng.snapshot()
        assert s["active"] == s["queued"] == 0
        assert s["submitted"] == s["completed"] + s["rejected"]
        assert s["admitted"] == s["completed"]

    def test_submit_validation(self):
        eng = ServingEngine(_StubBackend(n_slots=1, max_seq=16))
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit([], max_new_tokens=2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1], max_new_tokens=0)
        with pytest.raises(ValueError, match="overflow"):
            eng.submit(np.arange(14), max_new_tokens=4)

    def test_percentile_helper(self):
        assert percentile([], 50) is None
        assert percentile([3.0], 95) == 3.0
        xs = [1.0, 2.0, 3.0, 4.0]
        assert percentile(xs, 50) == pytest.approx(2.5)
        assert percentile(xs, 100) == 4.0
        np.testing.assert_allclose(
            [percentile(xs, q) for q in (25, 95)],
            [np.percentile(xs, 25), np.percentile(xs, 95)],
        )

    def test_queue_wait_reported_separately(self):
        eng = ServingEngine(_StubBackend(n_slots=1))
        for _ in range(3):
            eng.submit([1, 2], max_new_tokens=2)
        eng.drain()
        s = eng.snapshot()
        # one queue-wait sample per admission, its own series next to TTFT
        assert len(eng.metrics.queue_wait_s) == s["admitted"] == 3
        assert "p50" in s["queue_wait_ms"] and "p50" in s["ttft_ms"]
        # queued-behind requests waited at least one engine step; the wait
        # is the admit mark minus the submit mark, never negative
        assert all(w >= 0.0 for w in eng.metrics.queue_wait_s)


class TestSlotPoolOrder:
    def test_lowest_slot_first_reuse(self):
        """Reuse must be lowest-slot-first, not FIFO-of-frees: after
        interleaved admits/frees the pool hands out the smallest free id."""
        pool = SlotPool(4)
        assert [pool.admit(r) for r in range(4)] == [0, 1, 2, 3]
        pool.free(2)
        pool.free(0)
        pool.free(3)  # frees arrive in order 2, 0, 3 — reuse must not
        assert pool.admit(10) == 0  # ...replay that order
        assert pool.admit(11) == 2
        pool.free(1)
        assert pool.admit(12) == 1  # 1 freed later but lower than 3
        assert pool.admit(13) == 3
        assert pool.n_free == 0

    def test_interleaved_admit_free_order(self):
        pool = SlotPool(3)
        a = pool.admit(0)
        b = pool.admit(1)
        assert (a, b) == (0, 1)
        pool.free(a)
        assert pool.admit(2) == 0  # lowest id again, not slot 2
        pool.free(b)
        pool.free(0)
        assert pool.admit(3) == 0 and pool.admit(4) == 1


class TestChunkedScheduling:
    def test_validation(self):
        with pytest.raises(ValueError, match="prefill_chunk must be"):
            ServingEngine(_ChunkStubBackend(), prefill_chunk=0)
        with pytest.raises(ValueError, match="requires prefill_chunk"):
            ServingEngine(_ChunkStubBackend(), step_tokens=8)
        with pytest.raises(ValueError, match="must be >= prefill_chunk"):
            ServingEngine(_ChunkStubBackend(), prefill_chunk=8,
                          step_tokens=4)

    @pytest.mark.parametrize("traits,kw,says", [
        ({}, dict(spec_k=3, prefix_cache=4, preempt=True), None),
        (dict(rows_stay="rows stay: "), dict(prefix_cache=4),
         "rows stay: prefix_cache copies"),
        (dict(rows_stay="rows stay: "), dict(prefix_cache=4, kv_tiers=True),
         "rows stay: kv_tiers demotes"),
        (dict(rows_stay="rows stay: "), dict(preempt=True),
         "rows stay: preempt saves"),
        (dict(rows_stay="rows stay: "), dict(spec_k=3), None),
        (dict(rollback=False), dict(spec_k=3), "spec_k verifies a window"),
        (dict(rollback=False), dict(), None),
        (dict(projections=False), dict(adapters=True), "LoRA adapters"),
        (dict(widest_write=5, tightest=("g", "taps", 7, 3)),
         dict(prefill_chunk=None), "requires prefill_chunk"),
        (dict(widest_write=5, tightest=("g", "taps", 7, 3)),
         dict(prefill_chunk=5, spec_k=4), None),
        (dict(widest_write=5, tightest=("g", "taps", 7, 3)),
         dict(prefill_chunk=6),
         r"the g layers' ring of 7 rows must hold taps - 1 \+ the widest "
         r"write \(3 - 1 \+ 6\): raise g_ring"),
        (dict(widest_write=5, tightest=("g", "taps", 7, 3)), dict(spec_k=5),
         "widest write"),
        (dict(window=8), dict(), None),
    ])
    def test_features_are_refused_by_the_pools_traits_alone(
            self, traits, kw, says):
        """A backend that carries ``traits`` and no model description: the
        engine asks the pool what it can do and reads nothing else."""
        from uccl_tpu.models.inference import PoolTraits
        from uccl_tpu.serving.adapters import AdapterStore
        from uccl_tpu.serving.kv_tiers import TieredKVCache
        from uccl_tpu.serving.prefix_cache import PrefixCache

        backend = _ChunkStubBackend()
        backend.traits = PoolTraits(**traits)
        kw = {"prefill_chunk": 4, **kw}
        if kw.get("prefix_cache"):
            kw["prefix_cache"] = PrefixCache(kw["prefix_cache"])
        if kw.get("kv_tiers"):
            kw["kv_tiers"] = TieredKVCache(host_bytes=1 << 20)
        if kw.get("preempt"):
            kw["priority_classes"] = True
        if kw.get("adapters"):  # refused before any use
            kw["adapters"] = AdapterStore.__new__(AdapterStore)
        if says is not None:
            with pytest.raises(ValueError, match=says):
                ServingEngine(backend, **kw)
            return
        eng = ServingEngine(backend, **kw)
        assert eng._window == traits.get("window", 0)
        if set(kw) == {"prefill_chunk"}:  # what the stub can serve
            r = eng.submit(list(range(10)), max_new_tokens=2)
            eng.drain()
            assert r.state is RequestState.FINISHED

    def test_cursor_resumes_across_steps(self):
        """A 10-token prompt under chunk 4 prefills at starts 0, 4, 8 and
        only then emits its first token (PARTIAL_PREFILL → ACTIVE)."""
        eng = ServingEngine(_ChunkStubBackend(n_slots=1), prefill_chunk=4)
        r = eng.submit(list(range(10)), max_new_tokens=2)
        eng.step()
        assert r.state is RequestState.PARTIAL_PREFILL
        assert r.prefill_pos == 4 and r.n_generated == 0
        eng.step()
        assert r.prefill_pos == 8 and r.n_generated == 0
        eng.step()  # final (partial) chunk: emit + join decode same step
        assert r.state is not RequestState.PARTIAL_PREFILL
        assert r.prefill_pos == 10 and r.n_generated == 2
        starts = [c[2] for c in eng.backend.calls if c[0] == "prefill"]
        assert starts == [(0,), (4,), (8,)]
        eng.drain()
        assert eng.pool.leaked() == 0

    def test_decode_stall_bound(self):
        """THE property chunking buys: while one request prefills chunk by
        chunk, every in-flight decode advances one token per step — no
        active slot ever goes a step without a decode."""
        eng = ServingEngine(_ChunkStubBackend(n_slots=2), prefill_chunk=2)
        a = eng.submit([1], max_new_tokens=12)
        eng.step()  # A: single-chunk prefill + first decode
        assert a.n_generated == 2
        b = eng.submit(list(range(10)), max_new_tokens=2)  # 5 chunks
        n0 = a.n_generated
        for i in range(1, 6):
            eng.step()
            assert a.n_generated == n0 + i, (
                "decode stalled behind a prefill chunk"
            )
        assert b.n_generated >= 1  # B emitted at its final chunk
        # call-log shape: a step never runs two prefill calls, and every
        # prefill while A decoded is followed by A's decode in-step
        kinds = [c[0] for c in eng.backend.calls]
        for i in range(len(kinds) - 1):
            assert not (kinds[i] == kinds[i + 1] == "prefill")
        eng.drain()
        assert eng.pool.leaked() == 0

    def test_budget_gates_admission(self):
        """step_tokens caps the step's committed spend (decode = 1, chunk
        = C): admissions defer until budget frees up, FIFO order intact."""
        eng = ServingEngine(_ChunkStubBackend(n_slots=4), prefill_chunk=4,
                            step_tokens=8)
        reqs = [eng.submit(list(range(8)), max_new_tokens=3)
                for _ in range(3)]
        eng.step()  # budget 8 admits floor(8/4) = 2; third stays queued
        assert [r.state for r in reqs] == [
            RequestState.PARTIAL_PREFILL, RequestState.PARTIAL_PREFILL,
            RequestState.QUEUED,
        ]
        s = eng.snapshot()
        assert s["active"] == 2 and s["queued"] == 1
        assert (s["submitted"]
                == s["completed"] + s["active"] + s["queued"]
                + s["rejected"])
        eng.step()  # both mid-prefill slots still charge 2C = 8: no admit
        assert reqs[2].state is RequestState.QUEUED
        # first two finished prefill this step (first token) AND took the
        # step's decode pass immediately
        assert reqs[0].n_generated == 2
        eng.step()  # spend now 2 decodes = 2 → room for one chunk: admit
        assert reqs[2].state is RequestState.PARTIAL_PREFILL
        eng.drain()
        assert all(r.state is RequestState.FINISHED for r in reqs)
        assert eng.pool.leaked() == 0
        seqs = [r.admit_seq for r in reqs]
        assert seqs == sorted(seqs)

    def test_chunked_eos_and_conservation(self):
        """EOS at the first token retires straight out of prefill; metrics
        stay conserved with PARTIAL_PREFILL requests counted as active."""
        eng = ServingEngine(_ChunkStubBackend(n_slots=1), prefill_chunk=2,
                            max_queue=4)
        r = eng.submit([1, 2, 3], max_new_tokens=10, eos_id=100)
        eng.step()
        s = eng.snapshot()
        assert r.state is RequestState.PARTIAL_PREFILL
        assert (s["submitted"]
                == s["completed"] + s["active"] + s["queued"]
                + s["rejected"])
        eng.drain()
        assert r.finish_reason == "eos" and r.out_tokens == [100]
        assert eng.pool.leaked() == 0


MAX_SEQ = 32


@pytest.fixture(scope="module")
def dense_setup():
    """Params + ONE shared backend: its per-shape jit cache then makes the
    later tests' compiles cache hits (and exercises cross-engine slot-pool
    reuse for free). Tier-1 wall time matters — the oracle (len, N) pairs
    below repeat across tests for the same reason (_GEN_CACHE hits)."""
    from uccl_tpu.models import dense

    cfg = dense.DenseConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8,
        ffn=64,
    )
    params = dense.init_params(jax.random.PRNGKey(0), cfg)
    backend = DenseBackend(params, cfg, n_slots=2, max_seq=MAX_SEQ)
    return cfg, params, backend


class TestDenseOracle:
    def _oracle(self, params, cfg, req):
        from uccl_tpu.models.inference import generate

        toks = generate(params, jnp.asarray(req.prompt)[None], cfg,
                        max_new_tokens=req.max_new_tokens, max_seq=MAX_SEQ)
        return np.asarray(toks)[0, : req.n_generated].tolist()

    def test_staggered_mixed_lengths_exact(self, dense_setup):
        """The acceptance anchor: 2 slots, 6 mixed-length requests arriving
        mid-decode of each other — every emitted sequence bit-equals the
        one-shot oracle."""
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        eng = ServingEngine(backend)
        reqs = [eng.submit(_prompt(rng, 5), max_new_tokens=6),
                eng.submit(_prompt(rng, 3), max_new_tokens=4)]
        eng.step()  # both admitted, mid-decode...
        eng.step()
        for n, m in ((8, 5), (2, 6), (6, 3), (7, 5)):  # ...arrivals join
            reqs.append(eng.submit(_prompt(rng, n), max_new_tokens=m))
        eng.drain()
        assert eng.pool.leaked() == 0
        for r in reqs:
            assert r.n_generated == r.max_new_tokens
            assert r.out_tokens == self._oracle(params, cfg, r), r.rid
        # lifecycle timing populated for every request
        assert all(r.ttft is not None and r.latency is not None
                   for r in reqs)

    def test_eos_retirement_matches_oracle_prefix(self, dense_setup):
        """Using a token the oracle emits mid-stream as EOS, the engine
        must stop exactly there with the oracle's prefix."""
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(1)
        prompt = _prompt(rng, 5)
        eng = ServingEngine(backend)
        probe = eng.submit(prompt, max_new_tokens=6)
        eng.drain()
        full = probe.out_tokens
        assert full == self._oracle(params, cfg, probe)
        eos = full[3]
        k = full.index(eos)  # first occurrence may precede position 3
        r = eng.submit(prompt, max_new_tokens=6, eos_id=eos)
        eng.drain()
        assert r.finish_reason == "eos"
        assert r.out_tokens == full[: k + 1]
        assert eng.pool.leaked() == 0

    def test_slot_reuse_after_retirement(self, dense_setup):
        """More requests than slots: retired slots are re-prefilled by
        later requests and stale KV never bleeds into their outputs.
        (len, N) pairs repeat the staggered test's — fresh tokens, cached
        oracle programs."""
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(2)
        eng = ServingEngine(backend)
        reqs = [eng.submit(_prompt(rng, n), max_new_tokens=m)
                for n, m in ((5, 6), (3, 4), (8, 5), (2, 6), (6, 3), (7, 5))]
        eng.drain()
        assert eng.pool.total_admits == 6 and eng.pool.high_water == 2
        for r in reqs:
            assert r.out_tokens == self._oracle(params, cfg, r), r.rid


class TestDenseChunkedOracle:
    """Chunked prefill stays bit-exact: the same math split along the
    sequence axis. (len, N) pairs repeat the whole-prompt tests' so oracle
    programs are _GEN_CACHE hits; the shared module backend means each
    chunk size costs exactly ONE new prefill compile ([n_slots, C])."""

    def _drive(self, backend, rng, *, prefill_chunk, step_tokens=None):
        eng = ServingEngine(backend, prefill_chunk=prefill_chunk,
                            step_tokens=step_tokens)
        reqs = [eng.submit(_prompt(rng, 5), max_new_tokens=6),
                eng.submit(_prompt(rng, 3), max_new_tokens=4)]
        eng.step()  # both mid-flight (prefilling or decoding)...
        eng.step()
        for n, m in ((8, 5), (2, 6), (6, 3), (7, 5)):  # ...arrivals join
            reqs.append(eng.submit(_prompt(rng, n), max_new_tokens=m))
        eng.drain()
        assert eng.pool.leaked() == 0
        return eng, reqs

    @pytest.mark.parametrize(
        "chunk,budget",
        [(3, None),   # odd, divides no prompt length here
         (4, 8),      # pow2 + a per-step token budget
         (64, None)], # ≥ every prompt: whole prompt in one chunk
    )
    def test_staggered_chunked_exact(self, dense_setup, chunk, budget):
        cfg, params, backend = dense_setup
        eng, reqs = self._drive(
            backend, np.random.default_rng(0),
            prefill_chunk=chunk, step_tokens=budget,
        )
        oracle = TestDenseOracle()
        for r in reqs:
            assert r.n_generated == r.max_new_tokens
            assert r.out_tokens == oracle._oracle(params, cfg, r), (
                f"chunk={chunk} rid={r.rid}"
            )
        if chunk < 8:
            # multi-chunk prompts really resumed: more chunk calls than
            # requests, every one through the single [n_slots, C] program
            assert eng.metrics.prefill_chunks > len(reqs)

    def test_chunk_none_is_whole_prompt_path(self, dense_setup):
        """prefill_chunk=None ≡ the PR 3 path: identical prompts through a
        None engine and a chunked engine produce identical tokens (and the
        None engine still buckets — no chunk calls)."""
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(7)
        prompts = [_prompt(rng, n) for n, _ in
                   ((5, 6), (3, 4), (8, 5), (2, 6), (6, 3), (7, 5))]
        outs = {}
        for chunk in (None, 3):
            eng = ServingEngine(backend, prefill_chunk=chunk)
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, (_, m) in zip(prompts, ((5, 6), (3, 4), (8, 5),
                                                   (2, 6), (6, 3), (7, 5)))]
            eng.drain()
            outs[chunk] = [r.out_tokens for r in reqs]
            if chunk is None:
                assert eng.metrics.prefill_chunks == 0
        assert outs[None] == outs[3]


class _ReplayDrafter:
    """Drafts from known full sequences (prompt + oracle continuation) —
    the deterministic full-acceptance driver for spec-decode tests: every
    proposal is exactly what the target will emit, so the accept path
    (multi-token commits, bonus tokens, cursor jumps) is exercised on
    every step while the output must STILL be bit-exact."""

    def __init__(self, seqs):
        self.seqs = [np.asarray(s, np.int32) for s in seqs]

    def draft(self, context, k):
        c = np.asarray(context)
        for s in self.seqs:
            if s.size >= c.size and np.array_equal(s[:c.size], c):
                return s[c.size:c.size + k]
        return np.zeros(0, np.int32)


class _GarbageDrafter:
    """Near-certain rejection: proposes off-by-17 tokens (still in-vocab),
    driving the correction path — one committed token per window."""

    def draft(self, context, k):
        return (np.asarray(context)[-1] + 17
                + np.arange(k, dtype=np.int32)) % 64


class TestDenseSpecOracle:
    """Speculative decoding stays bit-exact on the dense stack: greedy
    acceptance only ever commits the target's own argmaxes, so any
    drafter — always right, always wrong, or the real prompt-lookup
    NGramDrafter — yields the vanilla greedy output. (len, N) pairs repeat
    the whole-prompt tests' so oracle programs are _GEN_CACHE hits; the
    only new compiles are the [n_slots, k+1] verify programs."""

    _PAIRS = ((5, 6), (3, 4), (8, 5), (2, 6), (6, 3), (7, 5))

    def _oracle_seqs(self, params, cfg, prompts):
        from uccl_tpu.models.inference import generate

        seqs = []
        for p, (_, m) in zip(prompts, self._PAIRS):
            toks = np.asarray(generate(
                params, jnp.asarray(p)[None], cfg, max_new_tokens=m,
                max_seq=MAX_SEQ,
            ))[0]
            seqs.append(np.concatenate([p, toks]))
        return seqs

    def _drive(self, backend, prompts, drafter, spec_k, **engine_kw):
        from uccl_tpu.serving import ServingEngine

        eng = ServingEngine(backend, spec_k=spec_k, drafter=drafter,
                            **engine_kw)
        reqs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts[:2], self._PAIRS[:2])]
        eng.step()  # staggered arrivals mid-flight, like the vanilla test
        eng.step()
        for p, (_, m) in zip(prompts[2:], self._PAIRS[2:]):
            reqs.append(eng.submit(p, max_new_tokens=m))
        eng.drain()
        assert eng.pool.leaked() == 0
        return eng, reqs

    def test_spec_staggered_exact_across_drafters(self, dense_setup):
        """The acceptance anchor: staggered mixed-length arrivals with
        slot reuse under spec_k=2, across the acceptance spectrum —
        full-accept (replay), near-full-reject (garbage) and the real
        NGramDrafter — every request bit-equals the one-shot oracle."""
        from uccl_tpu.serving import NGramDrafter

        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        prompts = [_prompt(rng, n) for n, _ in self._PAIRS]
        seqs = self._oracle_seqs(params, cfg, prompts)
        oracle = TestDenseOracle()
        accepted = {}
        for name, drafter in (("replay", _ReplayDrafter(seqs)),
                              ("garbage", _GarbageDrafter()),
                              ("ngram", NGramDrafter())):
            eng, reqs = self._drive(backend, prompts, drafter, spec_k=2)
            for r in reqs:
                assert r.n_generated == r.max_new_tokens
                assert r.out_tokens == oracle._oracle(params, cfg, r), (
                    f"drafter={name} rid={r.rid}"
                )
            accepted[name] = eng.metrics.spec_accepted
            if name == "replay":
                # full acceptance really multiplied tokens per model
                # call — strictly more commits than verify calls
                assert eng.metrics.decode_tokens > eng.metrics.decode_calls
        assert accepted["replay"] > accepted["garbage"]

    def test_spec_k1_equivalent_to_vanilla(self, dense_setup):
        """spec_k=1 emits the same stream as the vanilla engine — same
        tokens, same per-request counts — just 1-2 tokens per window."""
        from uccl_tpu.serving import NGramDrafter, ServingEngine

        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        prompts = [_prompt(rng, n) for n, _ in self._PAIRS]
        outs = {}
        for mode in ("vanilla", "spec"):
            eng = ServingEngine(
                backend,
                spec_k=1 if mode == "spec" else None,
                drafter=NGramDrafter() if mode == "spec" else None,
            )
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, (_, m) in zip(prompts, self._PAIRS)]
            eng.drain()
            outs[mode] = [r.out_tokens for r in reqs]
            assert eng.pool.leaked() == 0
        assert outs["spec"] == outs["vanilla"]

    def test_spec_composes_with_chunked_prefill(self, dense_setup):
        """spec_k x prefill_chunk: chunk-resumed prompts join the same
        step's verify when their cursor lands — outputs stay exact and
        chunks really resumed. Chunk 3 + verify [2, 3] are compile cache
        hits from the chunked and spec suites above."""
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        prompts = [_prompt(rng, n) for n, _ in self._PAIRS]
        seqs = self._oracle_seqs(params, cfg, prompts)
        eng, reqs = self._drive(backend, prompts, _ReplayDrafter(seqs),
                                spec_k=2, prefill_chunk=3)
        oracle = TestDenseOracle()
        for r in reqs:
            assert r.out_tokens == oracle._oracle(params, cfg, r), r.rid
        assert eng.metrics.prefill_chunks > len(reqs)
        assert eng.metrics.spec_accepted > 0

    def test_spec_composes_with_prefix_cache_hit(self, dense_setup):
        """spec_k x prefix cache: a hit resumes prefill at the matched
        boundary AND the continuation decodes speculatively — both
        requests bit-equal the oracle."""
        from uccl_tpu.serving import (
            NGramDrafter, PrefixCache, ServingEngine,
        )

        cfg, params, backend = dense_setup
        eng = ServingEngine(backend, prefill_chunk=4,
                            prefix_cache=PrefixCache(4), spec_k=2,
                            drafter=NGramDrafter())
        rng = np.random.default_rng(3)
        p0 = rng.integers(0, 64, 12).astype(np.int32)
        sharer = np.concatenate(
            [p0[:8], rng.integers(0, 64, 4).astype(np.int32)]
        )
        oracle = TestDenseOracle()
        cold = eng.submit(p0, max_new_tokens=4)
        eng.drain()
        hit = eng.submit(sharer, max_new_tokens=4)
        eng.drain()
        assert cold.cache_hit_len == 0 and hit.cache_hit_len == 8
        for r in (cold, hit):
            assert r.out_tokens == oracle._oracle(params, cfg, r), r.rid
        assert eng.pool.leaked() == 0


class TestDensePreemptionOracle:
    """Chunk-boundary preemption stays bit-exact on the dense stack: a
    paused victim's KV rows round-trip through the host save/restore (raw
    f32 — the PR 8 slot-row views), its cursor resumes via the PR 4 start
    offset, and every output — victim, survivor, and the interactive
    arrival that caused the pause — equals the one-shot oracle. Chunk 3
    and the [2, 3] verify window are compile-cache hits from the chunked
    and spec suites; the only new programs are the slot-row export/import
    jits (one each per pool shape)."""

    def _engine(self, backend, **kw):
        return ServingEngine(backend, prefill_chunk=3,
                             priority_classes=True, preempt=True, **kw)

    def _check(self, params, cfg, reqs):
        oracle = TestDenseOracle()
        for r in reqs:
            assert r.n_generated == r.max_new_tokens
            assert r.out_tokens == oracle._oracle(params, cfg, r), r.rid

    def test_preempt_mid_decode_exact(self, dense_setup):
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        eng = self._engine(backend)
        b1 = eng.submit(_prompt(rng, 5), max_new_tokens=6,
                        priority="batch")
        b2 = eng.submit(_prompt(rng, 3), max_new_tokens=6,
                        priority="batch")
        for _ in range(4):
            eng.step()  # both past prefill, mid-decode
        assert b1.state is RequestState.ACTIVE
        assert b2.state is RequestState.ACTIVE
        ia = eng.submit(_prompt(rng, 6), max_new_tokens=3,
                        priority="interactive")
        eng.step()
        assert b2.state is RequestState.PREEMPTED, (
            "newest batch request must pause for the interactive arrival"
        )
        assert b2.n_generated >= 1  # really paused MID-decode
        eng.drain()
        assert b2.preemptions == 1
        self._check(params, cfg, [b1, b2, ia])
        assert eng.pool.leaked() == 0
        assert eng.metrics.preempted == 1 and eng.metrics.resumed == 1

    def test_preempt_mid_prefill_exact(self, dense_setup):
        cfg, params, backend = dense_setup
        rng = np.random.default_rng(1)
        eng = self._engine(backend)
        bb = eng.submit(_prompt(rng, 8), max_new_tokens=5,
                        priority="batch")
        other = eng.submit(_prompt(rng, 2), max_new_tokens=6,
                           priority="batch")
        eng.step()  # bb one 3-token chunk in, other already decoding
        assert bb.state is RequestState.PARTIAL_PREFILL
        assert bb.prefill_pos == 3
        i1 = eng.submit(_prompt(rng, 6), max_new_tokens=3,
                        priority="interactive")
        i2 = eng.submit(_prompt(rng, 7), max_new_tokens=5,
                        priority="interactive")
        eng.step()  # preempts bb (newest), i1 takes its slot
        assert bb.state is RequestState.PREEMPTED
        assert bb.prefill_pos == 3, "the cursor is the saved state"
        eng.drain()
        assert bb.preemptions >= 1
        self._check(params, cfg, [bb, other, i1, i2])
        assert eng.pool.leaked() == 0

    def test_preempt_spec_victim_exact(self, dense_setup):
        """Preemption × speculative decoding: the victim pauses between
        verify windows (its cursor already advanced by multi-token
        commits) and resumes speculating — still bit-exact."""
        from uccl_tpu.serving import NGramDrafter

        cfg, params, backend = dense_setup
        rng = np.random.default_rng(0)
        eng = self._engine(backend, spec_k=2, drafter=NGramDrafter())
        b1 = eng.submit(_prompt(rng, 5), max_new_tokens=6,
                        priority="batch")
        b2 = eng.submit(_prompt(rng, 3), max_new_tokens=6,
                        priority="batch")
        for _ in range(3):
            eng.step()
        ia = eng.submit(_prompt(rng, 6), max_new_tokens=3,
                        priority="interactive")
        eng.step()
        assert RequestState.PREEMPTED in (b1.state, b2.state)
        eng.drain()
        assert eng.metrics.preempted >= 1
        self._check(params, cfg, [b1, b2, ia])
        assert eng.pool.leaked() == 0

    def test_preempt_prefix_cache_hit_victim_exact(self, dense_setup):
        """Preemption × prefix cache: the victim resumed prefill from a
        cached prefix (its KV partly COPIED, not computed), then got
        preempted and resumed again — the save/restore must carry the
        copied rows bit-exactly too. Chunk 4 matches the prefix-cache
        suite's compiled programs."""
        from uccl_tpu.serving import PrefixCache

        cfg, params, backend = dense_setup
        rng = np.random.default_rng(3)
        eng = ServingEngine(backend, prefill_chunk=4,
                            prefix_cache=PrefixCache(4),
                            priority_classes=True, preempt=True)
        p0 = rng.integers(0, 64, 12).astype(np.int32)
        donor = eng.submit(p0, max_new_tokens=4, priority="batch")
        eng.drain()  # donor parks as a reuse donor
        sharer = np.concatenate(
            [p0[:8], rng.integers(0, 64, 8).astype(np.int32)]
        )
        hit = eng.submit(sharer, max_new_tokens=4, priority="batch")
        eng.step()  # hit copies [0, 8) and prefills [8, 12) — mid-prefill
        assert hit.cache_hit_len == 8
        assert hit.state is RequestState.PARTIAL_PREFILL
        # two interactive arrivals: the first evicts the parked donor for
        # its slot, the second must preempt the mid-prefill hit victim
        i1 = eng.submit(_prompt(rng, 6), max_new_tokens=3,
                        priority="interactive")
        i2 = eng.submit(_prompt(rng, 7), max_new_tokens=3,
                        priority="interactive")
        eng.step()
        assert hit.state is RequestState.PREEMPTED
        eng.drain()
        assert hit.preemptions >= 1 and hit.cache_hit_len == 8
        self._check(params, cfg, [donor, hit, i1, i2])
        assert eng.pool.leaked() == 0


@pytest.fixture(scope="module")
def moe_setup(devices):
    """ONE 2-shard server/backend + ONE world-1 oracle server for every MoE
    serving test: MoE programs are shard_map compiles (the expensive kind),
    so both the whole-prompt and chunked tests must share them. Oracle
    (len, N) pairs repeat across tests for the same reason."""
    from jax.sharding import Mesh

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )

    cfg = MoEServeConfig(
        vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, moe_experts=8, moe_topk=2, moe_ffn=64,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = MoEServer(cfg, Mesh(np.array(devices[:2]), ("dp",)))
    backend = MoEBackend(
        srv, srv.shard_params(params), batch_local=1, max_seq=MAX_SEQ,
    )
    srv1 = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    return backend, srv1, srv1.shard_params(params)


class TestMoEOracle:
    def _check(self, reqs, srv1, p1):
        for r in reqs:
            want = srv1.generate(
                p1, jnp.asarray(r.prompt)[None, None], r.max_new_tokens,
                MAX_SEQ, impl="ll",
            )
            assert r.out_tokens == np.asarray(want)[0, 0].tolist(), r.rid

    def test_staggered_mixed_lengths_exact(self, moe_setup):
        """EP MoE stack on a 2-shard mesh (1 slot per shard): masked
        continuous batching bit-equals the world-1 one-shot oracle under
        staggered mixed-length arrivals. Lean on purpose — every distinct
        prompt shape costs a shard_map compile in the oracle, and tier-1
        wall time is budgeted: 3 lengths in one prefill bucket, one N."""
        backend, srv1, p1 = moe_setup
        eng = ServingEngine(backend)
        rng = np.random.default_rng(0)
        reqs = [eng.submit(_prompt(rng, 5), max_new_tokens=4),
                eng.submit(_prompt(rng, 6), max_new_tokens=4)]
        eng.step()  # admit + first decode...
        reqs.append(eng.submit(_prompt(rng, 8), max_new_tokens=4))
        eng.drain()
        assert eng.pool.leaked() == 0
        self._check(reqs, srv1, p1)

    def test_staggered_chunked_exact(self, moe_setup):
        """Chunked prefill on the EP MoE stack: chunk 3 divides none of the
        prompt lengths (5, 8) fully, so final partial chunks and the
        write-gate beyond the prompt end are exercised on the sharded
        cache. Same (len, N) pairs as above — oracle cache hits; the only
        new compile is the [W, 1, 3] chunk program."""
        backend, srv1, p1 = moe_setup
        eng = ServingEngine(backend, prefill_chunk=3, step_tokens=8)
        rng = np.random.default_rng(0)
        reqs = [eng.submit(_prompt(rng, 5), max_new_tokens=4),
                eng.submit(_prompt(rng, 6), max_new_tokens=4)]
        eng.step()  # both mid-prefill...
        reqs.append(eng.submit(_prompt(rng, 8), max_new_tokens=4))
        eng.drain()
        assert eng.pool.leaked() == 0
        assert eng.metrics.prefill_chunks > len(reqs)  # really multi-chunk
        self._check(reqs, srv1, p1)

    def test_spec_staggered_exact(self, moe_setup):
        """Speculative decoding on the EP-sharded MoE stack: the
        [W, B_loc, k+1] verify window routes every slot's draft through
        the drop-free sorted EP path, and full-acceptance drafting (the
        replay drafter) still bit-equals the world-1 oracle under
        staggered arrivals. Same (len, N) pairs as above — the only new
        compile is the verify program."""
        backend, srv1, p1 = moe_setup
        rng = np.random.default_rng(0)
        prompts = [_prompt(rng, n) for n in (5, 6, 8)]
        seqs = []
        for p in prompts:
            toks = srv1.generate(p1, jnp.asarray(p)[None, None], 4,
                                 MAX_SEQ, impl="ll")
            seqs.append(np.concatenate([p, np.asarray(toks)[0, 0]]))
        eng = ServingEngine(backend, spec_k=2,
                            drafter=_ReplayDrafter(seqs))
        reqs = [eng.submit(prompts[0], max_new_tokens=4),
                eng.submit(prompts[1], max_new_tokens=4)]
        eng.step()  # both mid-decode...
        reqs.append(eng.submit(prompts[2], max_new_tokens=4))
        eng.drain()
        assert eng.pool.leaked() == 0
        assert eng.metrics.spec_accepted > 0
        assert eng.metrics.decode_tokens > eng.metrics.decode_calls
        self._check(reqs, srv1, p1)

    def test_preemption_exact(self, moe_setup):
        """Chunk-boundary preemption on the EP-sharded MoE stack: the
        victim's KV rows round-trip through the MoESlotCache numpy
        mirrors (mid-prefill AND mid-decode victims across the two
        arrivals), and every output still bit-equals the world-1 oracle.
        Same (len, N) pairs as above — oracle + chunk programs are cache
        hits; export/import are host-side numpy, no new compiles."""
        backend, srv1, p1 = moe_setup
        eng = ServingEngine(backend, prefill_chunk=3,
                            priority_classes=True, preempt=True)
        rng = np.random.default_rng(0)
        b1 = eng.submit(_prompt(rng, 5), max_new_tokens=4,
                        priority="batch")
        b2 = eng.submit(_prompt(rng, 6), max_new_tokens=4,
                        priority="batch")
        eng.step()  # both mid-prefill (one 3-token chunk in)
        assert b2.state is RequestState.PARTIAL_PREFILL
        i1 = eng.submit(_prompt(rng, 8), max_new_tokens=4,
                        priority="interactive")
        eng.step()  # preempts the newest batch request mid-prefill
        assert b2.state is RequestState.PREEMPTED
        assert 0 < b2.prefill_pos < b2.prompt.size
        eng.drain()  # b2 resumes at its cursor and finishes
        assert b2.preemptions == 1
        # phase 2: a mid-DECODE victim (same shapes — cache-hit programs)
        b3 = eng.submit(_prompt(rng, 5), max_new_tokens=4,
                        priority="batch")
        b4 = eng.submit(_prompt(rng, 6), max_new_tokens=4,
                        priority="batch")
        for _ in range(16):
            if (b3.state is RequestState.ACTIVE
                    and b4.state is RequestState.ACTIVE):
                break
            eng.step()
        assert b4.state is RequestState.ACTIVE
        i2 = eng.submit(_prompt(rng, 5), max_new_tokens=4,
                        priority="interactive")
        eng.step()
        assert b4.state is RequestState.PREEMPTED, (
            "the newest decoding batch request must pause"
        )
        assert b4.n_generated >= 1  # really paused MID-decode
        eng.drain()
        assert eng.metrics.preempted == 2
        assert eng.metrics.resumed == eng.metrics.preempted
        assert eng.pool.leaked() == 0
        self._check([b1, b2, i1, i2, b3, b4], srv1, p1)

    def test_droppable_capacity_rejected(self, devices):
        """Slot serving's exactness needs a drop-free wire: a config whose
        per-expert capacity cannot cover worst-case routing is refused at
        the slot entry points (outputs would depend on batch neighbors)."""
        from jax.sharding import Mesh

        from uccl_tpu.models.moe_inference import MoEServeConfig, MoEServer

        cfg = MoEServeConfig(moe_experts=32, moe_topk=2,
                             capacity_factor=8.0)
        srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
        with pytest.raises(ValueError, match="drop-free"):
            srv.slot_cache(1, MAX_SEQ)
