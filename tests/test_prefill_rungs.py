"""The chunked-prefill program over the slots that are prefilling (ISSUE 28).

A chunk step sends the model only the rows of the prefilling slots, through
one of three programs — ``[1 | 2 | n_slots, C]`` (``engine.prefill_rung``).
Held here, on both backends: the engine's tokens equal the one-shot oracle's
whatever the rung; slots a call does not name keep their rows and lengths
bit for bit; the rung is a pure function of the occupancy; the counter and
the span say which rung ran; chunk-sink events, prefix-cache copies and
preempt / resume work under a compact rung; and start-up traces every
serving program exactly once and builds all rungs with the first.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu import obs
from uccl_tpu.serving import (
    DenseBackend, MoEBackend, PrefixCache, RequestState, ServingEngine,
)
from uccl_tpu.serving.engine import prefill_rung, prefill_rungs

MAX_SEQ = 32
N_SLOTS = 8
CHUNK = 4
RUNG = obs.counter("serving_prefill_rung_total")

MOE_CFG = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=8, moe_experts=8, moe_topk=2, moe_ffn=64)


def _dense(n_slots=N_SLOTS):
    from uccl_tpu.models import dense
    from uccl_tpu.models.inference import generate

    cfg = dense.DenseConfig(vocab=64, dim=32, n_layers=2, n_heads=4,
                            n_kv_heads=2, head_dim=8, ffn=64)
    params = dense.init_params(jax.random.PRNGKey(0), cfg)

    def oracle(req):
        toks = generate(params, jnp.asarray(req.prompt)[None], cfg,
                        max_new_tokens=req.max_new_tokens, max_seq=MAX_SEQ)
        return np.asarray(toks)[0, :req.n_generated].tolist()

    def rows(backend, slot):  # [L, S, ...] of one slot, and its length
        c = backend.cache
        return (np.asarray(c.k)[:, slot], np.asarray(c.v)[:, slot],
                int(np.asarray(c.lengths)[slot]))

    make = lambda n=n_slots: DenseBackend(params, cfg, n_slots=n,  # noqa: E731
                                          max_seq=MAX_SEQ)
    return make, oracle, rows


def _moe(devices, n_slots=N_SLOTS, world=1):
    from jax.sharding import Mesh

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )

    cfg = MoEServeConfig(**MOE_CFG)
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    placed = srv.shard_params(params)

    def oracle(req):
        want = srv.generate(placed, jnp.asarray(req.prompt)[None, None],
                            req.max_new_tokens, MAX_SEQ, impl="ll")
        return np.asarray(want)[0, 0, :req.n_generated].tolist()

    def rows(backend, slot):
        c = backend.cache
        return (np.asarray(c.k)[0][:, slot], np.asarray(c.v)[0][:, slot],
                int(np.asarray(c.lengths)[0, slot]))

    wide, wide_placed = srv, placed  # the oracle stays the one-shard server
    if world > 1:
        wide = MoEServer(cfg, Mesh(np.array(devices[:world]), ("dp",)))
        wide_placed = wide.shard_params(params)
    make = lambda n=n_slots: MoEBackend(  # noqa: E731
        wide, wide_placed, batch_local=n // world, max_seq=MAX_SEQ)
    return make, oracle, rows


@pytest.fixture(scope="module")
def stacks(devices):
    """{name: (backend of 8 slots, oracle, rows)}: ONE backend a stack, so
    its programs compile once; every slot starts with stale rows and a
    length from a finished request, so a stray write would show."""
    out = {}
    for name, (make, oracle, rows) in (("dense", _dense()),
                                       ("moe", _moe(devices))):
        backend = make()
        eng = ServingEngine(backend, prefill_chunk=CHUNK)
        rng = np.random.default_rng(11)
        for _ in range(N_SLOTS):
            eng.submit(rng.integers(0, 64, 5).astype(np.int32),
                       max_new_tokens=2)
        eng.drain()
        out[name] = (backend, oracle, rows)
    return out


def _prompt(rng, n):
    return rng.integers(0, 64, n).astype(np.int32)


class _Traces:
    """Traces (``jaxpr_trace``) and backend compiles of the serving
    programs, by name, through ``jax.monitoring``."""

    NAMES = ("prefill_slots", "verify_slots", "decode_slots")

    def __init__(self):
        self.on = True
        self.seen = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, fun_name=None, **_):
        kind = name.rsplit("/", 1)[-1]
        if self.on and fun_name and any(n in fun_name for n in self.NAMES):
            if kind in ("jaxpr_trace_duration", "backend_compile_duration"):
                self.seen.append((kind.split("_")[0], fun_name))

    def take(self):
        out, self.seen = self.seen, []
        return out


@pytest.fixture(scope="module")
def traces():
    t = _Traces()
    yield t
    t.on = False  # jax.monitoring has no way to take one listener off



# -- the rung is a pure function of the occupancy ----------------------------

@pytest.mark.parametrize("n,n_slots,rung", [
    (1, 8, 1), (2, 8, 2), (3, 8, 8), (4, 8, 8), (8, 8, 8),
    (1, 16, 1), (2, 16, 2), (3, 16, 16), (16, 16, 16),
    (1, 1, 1), (1, 2, 1), (2, 2, 2), (1, 3, 1), (2, 3, 2), (3, 3, 3),
])
def test_rung_is_a_pure_function_of_the_occupancy(n, n_slots, rung):
    assert prefill_rung(n, n_slots) == rung
    assert rung in prefill_rungs(n_slots)


def test_three_rungs_deduplicated_for_small_pools():
    assert [prefill_rungs(n) for n in (1, 2, 3, 8, 16)] == [
        (1,), (1, 2), (1, 2, 3), (1, 2, 8), (1, 2, 16)]


def test_backends_declare_their_rungs(stacks, devices):
    """Both backends get the three rungs on one shard; an EP world of two
    shards declares the whole-pool rung alone. The engine reads the
    attribute and nothing else about the backend."""
    assert stacks["dense"][0].prefill_rungs == (1, 2, N_SLOTS)
    assert stacks["moe"][0].prefill_rungs == (1, 2, N_SLOTS)
    assert _moe(devices, 4, world=2)[0]().prefill_rungs == (4,)


# -- tokens equal the oracle's; untouched slots stay bit-unchanged -----------

@pytest.mark.parametrize("stack", ["dense", "moe"])
@pytest.mark.parametrize("n", [1, 2, 3, N_SLOTS])
def test_n_prefilling_slots_exact_and_neighbours_untouched(stacks, stack, n):
    """``n`` slots prefill together beside two mid-decode neighbours (none
    when all 8 prefill): the step's prefill program is the rung for ``n``
    (``n = 3`` of 8 runs the pool rung with five masked rows), every
    request's tokens equal the one-shot oracle's, and every slot the call
    does not name keeps its rows and length bit for bit."""
    backend, oracle, rows = stacks[stack]
    eng = ServingEngine(backend, prefill_chunk=CHUNK)
    rng = np.random.default_rng(100 + n)
    reqs = []
    if n < N_SLOTS:
        reqs = [eng.submit(_prompt(rng, 5), max_new_tokens=12)
                for _ in range(2)]
        for _ in range(3):
            eng.step()
        assert all(r.state is RequestState.ACTIVE for r in reqs)
    late = [eng.submit(_prompt(rng, 9 + i % 2), max_new_tokens=3)
            for i in range(n)]
    before = [rows(backend, s) for s in range(N_SLOTS)]
    rung = prefill_rung(n, N_SLOTS)
    assert rung == {1: 1, 2: 2, 3: N_SLOTS, N_SLOTS: N_SLOTS}[n]
    calls = RUNG.get(rows=rung)
    eng.step()  # admits all n; ONE prefill program over them; one decode
    assert RUNG.get(rows=rung) == calls + 1
    assert sorted(eng._prefilling) == sorted(
        s for s, r in eng._by_slot.items() if r in late) and len(
            eng._prefilling) == n
    for slot in range(N_SLOTS):
        k0, v0, len0 = before[slot]
        k1, v1, len1 = rows(backend, slot)
        req = eng._by_slot.get(slot)
        if req in late:
            assert len1 == CHUNK  # the first chunk is in
        elif req is not None:  # a decoding neighbour: one token further
            assert len1 == len0 + 1
            assert np.array_equal(k0[:, :len0], k1[:, :len0])
            assert np.array_equal(v0[:, :len0], v1[:, :len0])
        else:  # named by nothing: stale rows and length, bit for bit
            assert len1 == len0 > 0
            assert np.array_equal(k0, k1) and np.array_equal(v0, v1)
    eng.drain()
    assert eng.pool.leaked() == 0
    for r in reqs + late:
        assert r.n_generated == r.max_new_tokens
        assert r.out_tokens == oracle(r), (stack, n, r.rid)


@pytest.mark.parametrize("stack", ["dense", "moe"])
def test_padding_rows_of_a_compact_rung_write_nothing(stacks, stack):
    """A compact call whose second row is padding (masked, naming an index
    past the pool): the named slot advances, every other slot — the last
    one, which a clipped gather reads, included — is bit-unchanged."""
    backend, _, rows = stacks[stack]
    before = [rows(backend, s) for s in range(N_SLOTS)]
    tokens = np.zeros((2, CHUNK), np.int32)
    tokens[0] = [5, 6, 7, 8]
    tok = backend.prefill(
        tokens, np.array([9, 1], np.int32), np.array([True, False]),
        start=np.zeros(2, np.int32),
        slots=np.array([3, N_SLOTS], np.int32))
    assert tok.shape == (2,)
    for slot in range(N_SLOTS):
        k0, v0, len0 = before[slot]
        k1, v1, len1 = rows(backend, slot)
        if slot == 3:
            assert len1 == CHUNK and not np.array_equal(k0, k1)
        else:
            assert len1 == len0
            assert np.array_equal(k0, k1) and np.array_equal(v0, v1)


# -- what says which rung ran -------------------------------------------------

def test_span_carries_rows_and_counter_counts_per_rung(stacks):
    backend, _, _ = stacks["dense"]
    eng = ServingEngine(backend, prefill_chunk=CHUNK)
    rng = np.random.default_rng(5)
    before = {r: RUNG.get(rows=r) for r in (1, 2, N_SLOTS)}
    tr = obs.enable_tracing()
    try:
        eng.submit(_prompt(rng, 9), max_new_tokens=2)
        eng.drain()  # three chunk steps with one slot prefilling
        for _ in range(2):
            eng.submit(_prompt(rng, 5), max_new_tokens=2)
        eng.drain()  # two with two
        for _ in range(3):
            eng.submit(_prompt(rng, 4), max_new_tokens=2)
        eng.drain()  # one with three: the pool
        spans = [e for e in tr.events()
                 if e.ph == "X" and e.name == "wire.prefill"]
    finally:
        obs.disable_tracing()
    assert [(e.args["n"], e.args["rows"], e.args["chunk"]) for e in spans] \
        == [(1, 1, CHUNK)] * 3 + [(2, 2, CHUNK)] * 2 + [(3, N_SLOTS, CHUNK)]
    assert {r: RUNG.get(rows=r) - before[r] for r in before} == {
        1: 3, 2: 2, N_SLOTS: 1}


def test_a_backend_without_rungs_gets_the_whole_pool():
    """A backend that declares no ``prefill_rungs`` (the stubs, anything
    external) is called as it always was: [n_slots, C], no ``slots``."""
    calls = []

    class Stub:
        n_slots, max_seq = 4, 64

        def prefill(self, tokens, lens, mask, start=None):
            calls.append((tokens.shape, tuple(np.flatnonzero(mask))))
            return np.zeros(self.n_slots, np.int32)

        def decode(self, tokens, active):
            return np.ones(self.n_slots, np.int32)

    eng = ServingEngine(Stub(), prefill_chunk=CHUNK)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.drain()
    assert calls == [((4, CHUNK), (0,))]


# -- the engine's other mechanisms under a compact rung -----------------------

def test_sampling_and_adapter_rows_travel_with_a_compact_rung(stacks, devices):
    """A sampled, adapter-carrying request arrives beside two greedy
    decoders, so it prefills in slot 2 as row 0 of one-row programs: its
    sampling row and adapter id are gathered with it, and its tokens equal
    the sampled one-shot oracle on the materialized weights (EP stack:
    ``_grid`` lays the [R] extras on the one shard)."""
    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )
    from uccl_tpu.serving import (
        AdapterStore, SamplingParams, make_lora, materialize,
    )

    backend, oracle, _ = stacks["moe"]
    cfg = MoEServeConfig(**MOE_CFG)
    widths = (cfg.n_layers, cfg.dim, cfg.n_heads * cfg.head_dim,
              cfg.n_kv_heads * cfg.head_dim)
    store = AdapterStore(*widths, max_rank=4, capacity=2)
    tree = make_lora(jax.random.PRNGKey(8), *widths, 2, scale=0.8)
    store.publish("acme", tree)
    eng = ServingEngine(backend, prefill_chunk=CHUNK, adapters=store)
    rng = np.random.default_rng(31)
    greedy = [eng.submit(_prompt(rng, 3), max_new_tokens=8)
              for _ in range(2)]
    eng.step()
    calls = RUNG.get(rows=1)
    req = eng.submit(_prompt(rng, 10), max_new_tokens=4, adapter="acme",
                     sampling=SamplingParams(temperature=0.9, top_k=9,
                                             seed=5))
    eng.step()
    assert list(eng._prefilling) == [2] and RUNG.get(rows=1) == calls + 1
    eng.drain()
    srv = backend.server
    want = srv.generate(
        srv.shard_params(materialize(
            init_params(jax.random.PRNGKey(0), cfg), tree)),
        jnp.asarray(req.prompt)[None, None], req.max_new_tokens, MAX_SEQ,
        impl="ll", sampling=req.sampling)
    assert req.out_tokens == np.asarray(want)[0, 0].tolist()
    for r in greedy:
        assert r.out_tokens == oracle(r), r.rid
    eng.close()


def test_chunk_sink_events_name_the_real_slot(stacks):
    """Two neighbours decode in slots 0 and 1, so the arrival prefills in
    slot 2 as row 0 of a one-row program: the sink's events name slot 2,
    cover the prompt chunk by chunk, and carry the oracle's first token."""
    backend, oracle, rows = stacks["dense"]
    events = []
    eng = ServingEngine(backend, prefill_chunk=CHUNK,
                        chunk_sink=events.extend)
    rng = np.random.default_rng(21)
    for _ in range(2):
        eng.submit(_prompt(rng, 3), max_new_tokens=10)
    eng.step()
    del events[:]
    calls = RUNG.get(rows=1)
    req = eng.submit(_prompt(rng, 10), max_new_tokens=3)
    eng.drain()
    assert RUNG.get(rows=1) == calls + 3
    mine = [e for e in events if e.req is req]
    assert [(e.slot, e.lo, e.hi, e.done) for e in mine] == [
        (2, 0, 4, False), (2, 4, 8, False), (2, 8, 10, True)]
    assert mine[-1].first_token == oracle(req)[0]
    assert req.out_tokens == oracle(req)


def test_prefix_cache_copy_then_compact_resume(stacks):
    """A prefix-cache hit copies the donor's rows into the admitted slot
    and resumes at the match; the resumed chunks run as one-row programs
    and the tokens equal the oracle's."""
    backend, oracle, _ = stacks["dense"]
    eng = ServingEngine(backend, prefill_chunk=CHUNK,
                        prefix_cache=PrefixCache(CHUNK))
    rng = np.random.default_rng(3)
    p0 = _prompt(rng, 12)
    cold = eng.submit(p0, max_new_tokens=3)
    eng.drain()
    calls = RUNG.get(rows=1)
    hit = eng.submit(np.concatenate([p0[:8], _prompt(rng, 4)]),
                     max_new_tokens=3)
    eng.drain()
    assert cold.cache_hit_len == 0 and hit.cache_hit_len == 8
    assert RUNG.get(rows=1) == calls + 1  # [8, 12) is one chunk
    for r in (cold, hit):
        assert r.out_tokens == oracle(r), r.rid
    eng.close()


@pytest.mark.parametrize("stack", ["dense", "moe"])
def test_preempt_and_resume_under_a_compact_rung(stack, traces, devices):
    """Two slots, both held by batch requests, one of them mid-prefill; two
    interactive arrivals pause both. The victims come back one by one as
    slots free up and finish their prefill in one-row programs, at their
    cursors. ``import_slot_kv`` hands the pool back placed as the programs
    return it (nothing traces again), and every output equals the
    oracle's."""
    make, oracle, _ = _dense(2) if stack == "dense" else _moe(devices, 2)
    eng = ServingEngine(make(), prefill_chunk=3, priority_classes=True,
                        preempt=True)
    rng = np.random.default_rng(1)
    bb = eng.submit(_prompt(rng, 8), max_new_tokens=5, priority="batch")
    other = eng.submit(_prompt(rng, 2), max_new_tokens=6, priority="batch")
    eng.step()
    assert bb.state is RequestState.PARTIAL_PREFILL and bb.prefill_pos == 3
    i1 = eng.submit(_prompt(rng, 6), max_new_tokens=3,
                    priority="interactive")
    i2 = eng.submit(_prompt(rng, 7), max_new_tokens=5,
                    priority="interactive")
    eng.step()
    assert bb.state is RequestState.PREEMPTED and bb.prefill_pos == 3
    eng.step()  # by now every program has run at least once
    traces.take()
    calls = RUNG.get(rows=1)
    eng.drain()
    assert traces.take() == []
    assert RUNG.get(rows=1) > calls  # bb's remaining chunks, alone
    assert bb.preemptions >= 1
    for r in (bb, other, i1, i2):
        assert r.n_generated == r.max_new_tokens
        assert r.out_tokens == oracle(r), r.rid
    assert eng.pool.leaked() == 0


# -- start-up: every serving program traced once, all rungs with the first ----

@pytest.mark.parametrize("stack", ["dense", "moe", "moe-2-shards"])
def test_startup_traces_each_program_once_and_builds_every_rung(
        stack, traces, devices):
    """After ONE request through a fresh engine — one slot prefilling, as a
    benchmark's warm-up has — every serving program has been traced and
    compiled exactly once: the prefill rungs (three; over two shards the
    pool's alone) and the decode program, none a second time for the pool
    the first call handed back. Later steps with two and with three slots
    prefilling trace nothing."""
    make, oracle, _ = {"dense": _dense, "moe": lambda: _moe(devices),
                       "moe-2-shards": lambda: _moe(devices, world=2)
                       }[stack]()
    traces.take()
    backend = make()
    rungs = len(backend.prefill_rungs)
    eng = ServingEngine(backend, prefill_chunk=CHUNK)
    rng = np.random.default_rng(9)
    first = eng.submit(_prompt(rng, 9), max_new_tokens=3)
    eng.drain()
    seen = traces.take()
    prefill = [k for k, name in seen if "prefill_slots" in name]
    decode = [k for k, name in seen if "prefill_slots" not in name]
    assert sorted(prefill) == ["backend"] * rungs + ["jaxpr"] * rungs, seen
    assert sorted(decode) == ["backend", "jaxpr"], seen
    later = []
    for n in (2, 3, 1):
        later += [eng.submit(_prompt(rng, 9), max_new_tokens=3)
                  for _ in range(n)]
        eng.drain()
    assert traces.take() == []
    for r in [first] + later:
        assert r.out_tokens == oracle(r), r.rid


def test_an_engine_that_never_chunks_builds_no_rung(traces):
    make, _, _ = _dense(4)
    traces.take()
    eng = ServingEngine(make())
    eng.submit(_prompt(np.random.default_rng(2), 6), max_new_tokens=2)
    eng.drain()
    seen = traces.take()
    assert [k for k, name in seen if "prefill_slots" in name] == [
        "jaxpr", "backend"], seen


def test_serving_imports_leave_pallas_alone():
    """The serving path's imports do not load Pallas (most of a second of
    start-up): the lax wire never builds a kernel, and the kernel modules
    load when something names them."""
    import subprocess
    import sys

    code = ("import sys\n"
            "import uccl_tpu.serving, uccl_tpu.models.moe_inference\n"
            "import uccl_tpu.ep as ep\n"
            "bad = [m for m in sys.modules if m.endswith('pallas_a2a')"
            " or m.startswith('jax.experimental.pallas')]\n"
            "assert not bad, bad\n"
            "assert ep.pallas_a2a.all_to_all and ep.ops._dma.MESH\n"
            "assert 'jax.experimental.pallas' in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
