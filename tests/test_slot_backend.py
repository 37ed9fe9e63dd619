"""The one slot backend's contract, over its two constructors (ISSUE 31).

``DenseBackend`` and ``MoEBackend`` are constructors of ``SlotBackend`` and
nothing else. Held here for both: every program call is one ``backend.stage
-> backend.launch -> backend.fetch`` inside the engine's ``wire.*`` span (the
nest ``chipbench/program_trace.py`` reads the device's idle time by);
``clone()`` and ``clone(params=tree)`` share the prototype's compiled programs,
own their pool and serve the oracle's tokens without a new program; a tree
that does not match is refused before it serves.

The pool's ownership (ISSUE 32), over gqa and latent (mla) rows: a program
CONSUMES the pool it is handed — donated, written in place, handed back as
the same buffers — which ``serving_pool_in_place_total{program}`` counts and
the compiled programs declare as input/output aliases; twins keep pools that
survive each other's steps; the three slot-row shims read and write
``backend.cache`` between donated steps bit-exactly.
"""

import re
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu import obs
from uccl_tpu.serving import (
    DenseBackend, MoEBackend, NGramDrafter, ServingEngine, replicate_backend,
)
from uccl_tpu.serving.backend import SlotBackend

MAX_SEQ = 32
N_SLOTS = 4
CHUNK = 4
STACKS = ("dense", "moe")
POOLS = STACKS + ("mla",)  # the pool's ownership: latent rows as well
IN_PLACE = obs.counter("serving_pool_in_place_total")

GQA = dict(vocab=64, dim=32, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=8,
           moe_experts=8, moe_topk=2, moe_ffn=64)
# two layers (a dense one, an expert one), a cache row of 8 + 6 numbers
MLA = dict(vocab=64, dim=40, n_layers=2, n_heads=3, rope_theta=1e4,
           norm_eps=1e-5, moe_experts=8, moe_topk=2, moe_ffn=24,
           capacity_factor=4.0, attn="mla", q_lora_rank=16, kv_lora_rank=8,
           qk_nope_dim=5, qk_rope_dim=6, v_head_dim=7, n_kv_heads=3,
           head_dim=11, first_k_dense=1, dense_ffn=36, shared_ffn=24,
           gate="sigmoid_bias", routed_scale=1.8, param_dtype="bfloat16")


def _dense():
    from uccl_tpu.models import dense
    from uccl_tpu.models.inference import generate

    cfg = dense.DenseConfig(vocab=64, dim=32, n_layers=1, n_heads=4,
                            n_kv_heads=2, head_dim=8, ffn=64)
    params = dense.init_params(jax.random.PRNGKey(0), cfg)

    def oracle(p, prompt, n):
        toks = generate(p, jnp.asarray(prompt)[None], cfg,
                        max_new_tokens=n, max_seq=MAX_SEQ)
        return np.asarray(toks)[0].tolist()

    backend = DenseBackend(params, cfg, n_slots=N_SLOTS, max_seq=MAX_SEQ)
    return backend, oracle, backend.programs._fns


def _moe(devices, desc=GQA, world=1, slots=N_SLOTS):
    from jax.sharding import Mesh

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )

    cfg = MoEServeConfig(**desc)
    srv = MoEServer(cfg, Mesh(np.array(devices[:world]), ("dp",)))
    placed = srv.shard_params(init_params(jax.random.PRNGKey(0), cfg))

    def oracle(p, prompt, n):
        want = srv.generate(p, jnp.asarray(prompt)[None, None], n, MAX_SEQ,
                            impl="sort")
        return np.asarray(want)[0, 0].tolist()

    backend = MoEBackend(srv, placed, batch_local=slots // world,
                         max_seq=MAX_SEQ, decode_impl="sort")
    return backend, oracle, srv._fns


@pytest.fixture(scope="module")
def stacks(devices):
    """{name: (prototype backend, oracle(params, prompt, n), the LRU its
    compiled programs live in)}, every program of a chunked engine and of a
    speculating one already built."""
    out = {"dense": _dense(), "moe": _moe(devices),
           "mla": _moe(devices, MLA)}
    for backend, _, _ in out.values():
        for kw in ({}, {"spec_k": 2, "drafter": NGramDrafter()}):
            _serve(backend, [[1, 2, 3, 1, 2, 3, 1, 2, 3]], 4, **kw)
    return out


def _serve(backend, prompts, n, **kw):
    eng = ServingEngine(backend, prefill_chunk=CHUNK, **kw)
    reqs = [eng.submit(np.asarray(p, np.int32), max_new_tokens=n)
            for p in prompts]
    eng.drain()
    return [r.out_tokens for r in reqs]


def _pool(backend):
    return [np.asarray(a).copy() for a in backend.cache]


def _inside(e, w):
    """Whether the ring's span ``e`` lies inside ``w``."""
    return (w.ts_us <= e.ts_us
            and e.ts_us + e.dur_us <= w.ts_us + w.dur_us + 1e-3)


@pytest.mark.parametrize("cls", [DenseBackend, MoEBackend])
def test_a_constructor_and_nothing_else(cls):
    assert issubclass(cls, SlotBackend)
    assert set(vars(cls)) <= {"__init__", "__doc__", "__module__",
                              "__firstlineno__", "__static_attributes__"}


@pytest.mark.parametrize("wire", ["prefill", "decode", "verify"])
@pytest.mark.parametrize("stack", STACKS)
def test_a_call_is_stage_launch_fetch_inside_its_wire_span(stacks, stack,
                                                           wire):
    backend = stacks[stack][0].clone()
    kw = {"spec_k": 2, "drafter": NGramDrafter()} if wire == "verify" else {}
    tr = obs.enable_tracing()
    try:
        _serve(backend, [[5, 6, 7, 5, 6, 7, 5, 6, 7]], 4, **kw)
        spans = [e for e in tr.events() if e.ph == "X"]
    finally:
        obs.disable_tracing()
    wires = [e for e in spans if e.name == "wire." + wire]
    assert wires and {e.track for e in wires} == {"wire"}
    inner = sorted((e for e in spans if e.name.startswith("backend.")),
                   key=lambda e: e.ts_us)
    assert {e.track for e in inner} == {"wire"}

    for w in wires:
        mine = [e for e in inner if _inside(e, w)]
        # a clone's first chunked call builds its other rungs: each is one
        # more whole triple inside the same wire span
        assert len(mine) % 3 == 0 and mine
        assert [e.name for e in mine] == [
            "backend.stage", "backend.launch", "backend.fetch"
        ] * (len(mine) // 3)
        for a, b in zip(mine, mine[1:]):  # one after the other, never nested
            assert a.ts_us + a.dur_us <= b.ts_us + 1e-3
    # every backend span lies in SOME wire span: none runs bare
    every_wire = [e for e in spans if e.name.startswith("wire.")]
    assert all(any(_inside(e, w) for w in every_wire) for e in inner)


@pytest.mark.parametrize("pushed", [False, True], ids=["clone", "params"])
@pytest.mark.parametrize("stack", STACKS)
def test_clone_shares_programs_and_owns_its_pool(stacks, stack, pushed):
    proto, oracle, fns = stacks[stack]
    tree = None
    if pushed:  # a host copy, as a weight push delivers it, at another value
        tree = jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32) * 0.5, proto.params)
    twin = proto.clone(tree)
    assert type(twin) is type(proto)
    assert twin.programs is proto.programs
    assert (twin.n_slots, twin.max_seq, twin.prefill_rungs, twin.cfg) == (
        proto.n_slots, proto.max_seq, proto.prefill_rungs, proto.cfg)
    assert twin.cache is not proto.cache
    assert all(a is not b for a, b in zip(twin.cache, proto.cache))
    if pushed:
        assert proto.clone().params is proto.params
        for a, b, w in zip(jax.tree_util.tree_leaves(twin.params),
                           jax.tree_util.tree_leaves(tree),
                           jax.tree_util.tree_leaves(proto.params)):
            assert a.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b).astype(w.dtype))
    else:
        assert twin.params is proto.params
    before, n_fns = _pool(proto), len(fns)
    prompt = [9, 8, 7, 6, 5, 4, 3]
    served = _serve(twin, [prompt], 5)
    assert len(fns) == n_fns  # nothing new was built
    assert served == [oracle(twin.params, prompt, 5)]
    for a, b in zip(before, _pool(proto)):  # the prototype's pool is its own
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fault", ["structure", "shape"])
@pytest.mark.parametrize("stack", STACKS)
def test_clone_refuses_a_tree_that_does_not_match(stacks, stack, fault):
    proto = stacks[stack][0]
    if fault == "structure":
        bad = {"not": np.zeros(3, np.float32)}
    else:
        bad = jax.tree_util.tree_map(np.asarray, proto.params)
        bad["embed"] = np.zeros(bad["embed"].shape + (1,), np.float32)
    with pytest.raises(ValueError, match="pushed weight"):
        proto.clone(bad)


# -- the pool's ownership (ISSUE 32) ----------------------------------------

def _count_runs(backend):
    """Count the backend's program calls by kind, from here on."""
    calls, launch = Counter(), backend._launch

    def counted(kind, *a, **kw):
        calls[kind] += 1
        return launch(kind, *a, **kw)

    backend._launch = counted
    return calls


def _where(leaf):
    """Where a pool leaf's one buffer lives."""
    return leaf.addressable_shards[0].data.unsafe_buffer_pointer()


def _call(backend, program):
    """One direct call of ``program`` on ``backend``: slot 0 alone is
    admitted / active."""
    first = np.arange(N_SLOTS) == 0
    toks = np.tile(np.arange(1, 1 + CHUNK, dtype=np.int32), (N_SLOTS, 1))
    lens = np.full(N_SLOTS, 2 * CHUNK, np.int32)
    start = np.zeros(N_SLOTS, np.int32)
    if program == "prefill":  # whole prompts, whole pool
        return backend.prefill(toks, np.full(N_SLOTS, CHUNK, np.int32), first)
    if program == "prefill-chunk":  # a chunk, the pool's rung
        return backend.prefill(toks, lens, first, start=start)
    if program == "prefill-compact":  # a chunk, the one-row rung
        return backend.prefill(toks[:1], lens[:1], first[:1], start=start[:1],
                               slots=np.zeros(1, np.int32))
    if program == "decode":
        return backend.decode(toks[:, 0], first)
    return backend.verify(toks[:, :3], first)


@pytest.mark.parametrize("program", ["prefill", "prefill-chunk",
                                     "prefill-compact", "decode", "verify"])
@pytest.mark.parametrize("stack", POOLS)
def test_a_program_consumes_the_pool_it_is_handed(stacks, stack, program):
    backend = stacks[stack][0].clone()
    calls = _count_runs(backend)
    kind = program.split("-")[0]
    handed = backend.cache
    at = [_where(handed.k), _where(handed.v)]
    before = IN_PLACE.get(program=kind)
    _call(backend, program)
    assert calls[kind] >= 1 and sum(calls.values()) == calls[kind]
    assert all(leaf.is_deleted() for leaf in handed)
    assert not any(leaf.is_deleted() for leaf in backend.cache)
    # K and V come back as the buffers handed in: written in place, not
    # copied (the few bytes of ``lengths`` the compiler may place anew)
    assert [_where(backend.cache.k), _where(backend.cache.v)] == at
    assert IN_PLACE.get(program=kind) - before == calls[kind]


@pytest.mark.parametrize("spec", [False, True], ids=["decode", "verify"])
@pytest.mark.parametrize("stack", POOLS)
def test_the_counter_equals_the_calls_an_engine_makes(stacks, stack, spec):
    """Every rung of the chunked prefill, decode (or verify) steps: the
    counter moves by exactly the calls made, program by program."""
    backend = stacks[stack][0].clone()
    calls = _count_runs(backend)
    before = {k: IN_PLACE.get(program=k)
              for k in ("prefill", "decode", "verify")}
    kw = {"spec_k": 2, "drafter": NGramDrafter()} if spec else {}
    _serve(backend, [[1, 2, 3, 1, 2, 3, 1, 2, 3], [4, 5, 6, 4, 5],
                     [7, 8, 9, 7, 8, 9, 7]], 4, **kw)
    assert calls["prefill"] and calls["verify" if spec else "decode"]
    for k, n in before.items():
        assert IN_PLACE.get(program=k) - n == calls[k], k


@pytest.mark.parametrize("stack", POOLS)
def test_compiled_programs_alias_the_pool_to_their_output(stacks, stack,
                                                          monkeypatch):
    """What the compiler is told and what it accepted: the pool's three
    leaves, and nothing else, are donated, and the compiled module carries
    an input/output alias for each."""
    proto, _, fns = stacks[stack]
    backend = proto.clone()
    seen, get = [], fns.get

    def spy(key, build):
        fn = get(key, build)

        def call(*args):
            seen.append((key, fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               jnp.result_type(a)), args)))
            return fn(*args)
        return call

    monkeypatch.setattr(fns, "get", spy)
    for program in ("prefill", "prefill-chunk", "prefill-compact", "decode",
                    "verify"):
        _call(backend, program)
    pool = sorted((a.shape, a.dtype) for a in backend.cache)
    # prefill (whole-pool, compact), decode, verify: programs of their own
    assert len({key for key, _, _ in seen}) >= 4
    for key, fn, shapes in seen:
        lowered = fn.lower(*shapes)
        donated = [i for i in jax.tree_util.tree_leaves(lowered.args_info)
                   if i.donated]
        assert sorted((i.shape, i.dtype) for i in donated) == pool, key
        header = lowered.compile().as_text().split("\n", 1)[0]
        aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", header)
        assert aliases, (key, header[:200])
        assert len(re.findall(r"\(\d+, \{\}", aliases.group(1))) == 3, key


@pytest.mark.parametrize("stack", POOLS)
def test_twins_keep_pools_that_survive_each_others_steps(stacks, stack):
    a, b = replicate_backend(stacks[stack][0].clone(), 2)
    c = a.clone()
    assert a.programs is b.programs is c.programs
    _call(a, "prefill")
    rows = {id(x): _pool(x) for x in (a, b, c)}  # every pool still readable
    for stepped in (a, b, c):
        for program in ("prefill-chunk", "prefill-compact", "decode",
                        "verify"):
            _call(stepped, program)
        for other in (a, b, c):
            if other is stepped:
                continue
            for x, y in zip(rows[id(other)], _pool(other)):
                np.testing.assert_array_equal(x, y)
        rows[id(stepped)] = _pool(stepped)


@pytest.mark.parametrize("stack", POOLS)
def test_slot_rows_move_bit_exactly_between_donated_steps(stacks, stack):
    """export -> (a step) -> import -> (a step) -> copy -> (a step) ->
    export: the shims see ``backend.cache`` between steps, whichever buffers
    it is by then."""
    backend = stacks[stack][0].clone()
    idle = np.zeros(N_SLOTS, bool)
    _call(backend, "prefill")  # slot 0 holds CHUNK rows
    k0, v0 = backend.export_slot_kv(0, 0, CHUNK)
    assert np.abs(k0).sum() > 0

    def step():  # consumes the pool; writes no row (nothing is active)
        handed = backend.cache
        backend.decode(np.zeros(N_SLOTS, np.int32), idle)
        assert handed.k.is_deleted()

    step()
    backend.import_slot_kv(1, k0, v0, length=CHUNK)
    step()
    backend.copy_slot_prefix(2, 1, CHUNK)
    step()
    for slot in (0, 1, 2):
        k, v = backend.export_slot_kv(slot, 0, CHUNK)
        np.testing.assert_array_equal(k, k0)
        np.testing.assert_array_equal(v, v0)
    assert np.asarray(backend.cache.lengths).reshape(-1)[:3].tolist() == [
        CHUNK] * 3


@pytest.mark.parametrize("stack", POOLS)
def test_a_program_that_fails_after_consuming_the_pool_says_so(stacks, stack):
    backend = stacks[stack][0].clone()

    class Broken:
        def decode(self, params, tokens, active, pool, **kw):
            for leaf in pool:
                leaf.delete()
            raise FloatingPointError("device fault")

        def prefill(self, *a, **kw):
            raise FloatingPointError("refused before it ran")

    backend.programs = Broken()
    with pytest.raises(FloatingPointError, match="refused"):  # pool intact
        _call(backend, "prefill")
    assert not backend.cache.k.is_deleted()
    with pytest.raises(RuntimeError, match="consumed the slot pool") as e:
        _call(backend, "decode")
    assert isinstance(e.value.__cause__, FloatingPointError)


# -- a call crosses to the device once each way (ISSUE 43) -------------------

class _Compiles:
    """Names of the programs the backend compiles while ``on``
    (``jax.monitoring`` can take no listener off again)."""

    def __init__(self):
        self.on, self.seen = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, fun_name=None, **_):
        if self.on and name.endswith("backend_compile_duration"):
            self.seen.append(fun_name)


@pytest.fixture(scope="module")
def compiles():
    c = _Compiles()
    yield c
    c.on = False


@pytest.mark.parametrize("world", [1, 2])
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_a_step_call_is_one_compiled_program(devices, compiles, kind, world):
    """A first ``decode`` / ``verify`` call at a pool shape nothing else
    here uses compiles ONE program, the step's own — no eager reshape of a
    device array before or after it — and serves what ``verify_slots``
    returns for the same window on a twin's pool, bit for bit: tokens,
    experts read, pool."""
    slots, width = 6, 3 if kind == "verify" else 1
    backend, _, _ = _moe(devices, world=world, slots=slots)
    srv, placed, twin = backend.server, backend.params, backend.clone()
    rng = np.random.default_rng(world)
    prompts = rng.integers(1, 64, (slots, CHUNK)).astype(np.int32)
    active = np.arange(slots) % 3 != 1
    for b in (backend, twin):
        b.prefill(prompts, np.full(slots, CHUNK, np.int32), active)
    window = rng.integers(1, 64, (slots, width)).astype(np.int32)
    read = obs.counter("ep_experts_read_total")
    before = read.get()
    compiles.seen, compiles.on = [], True
    try:
        if kind == "decode":
            got = [backend.decode(window[:, 0], active)]
        else:
            got = list(backend.verify(window, active))
    finally:
        compiles.on = False
    assert compiles.seen == [f"jit(uccl_moe_{kind}_slots)"]
    grid = (world, slots // world)
    tok, n_acc, n_read, pool = srv.verify_slots(
        placed, window.reshape(grid + (width,)), active.reshape(grid),
        twin.cache, impl="sort")
    want = ([np.asarray(tok)[..., 0]] if kind == "decode"
            else [np.asarray(tok), np.asarray(n_acc)])
    for g, w in zip(got, want):
        assert isinstance(g, np.ndarray) and g.dtype == np.int32
        np.testing.assert_array_equal(g, w.reshape((slots,) + w.shape[2:]))
    assert read.get() - before == int(np.asarray(n_read).sum())
    for a, b in zip(jax.tree.leaves(tuple(backend.cache)),
                    jax.tree.leaves(tuple(pool))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class _Stub:
    """``Programs`` that run nothing: they keep what they were handed and
    return outputs of the documented shapes, then the pool."""

    def __init__(self, world, counted):
        self.world, self.counted, self.calls = world, counted, []
        for kind in ("prefill", "decode", "verify"):
            setattr(self, kind, partial(self._call, kind))

    def _out(self, kind, tokens):
        lead = tokens.shape[:1 if self.world is None else 2]
        out = [np.zeros(lead if kind != "verify" else tokens.shape, np.int32)]
        if kind == "verify":
            out.append(np.ones(lead, np.int32))
        if self.counted and kind != "prefill":
            out.append(np.full(self.world or 1, 5, np.int32))
        return out

    def _call(self, kind, params, *args, **kw):
        *per_row, pool = args
        self.calls.append((kind, per_row, kw))
        return (*self._out(kind, per_row[0]), pool)


@pytest.mark.parametrize("counted", [False, True], ids=["bare", "counted"])
@pytest.mark.parametrize("world", [None, 1, 2])
def test_run_hands_the_programs_host_arrays(world, counted):
    """Every per-row argument reaches the program as a HOST array of its
    documented dtype, ``[rows, ...]`` with no world and ``[W, rows / W,
    ...]`` over one (the adapter tables, not per row, are broadcast); the
    outputs come back flat per row; the experts' count is popped, and
    counted, only where ``experts_held`` is set."""
    from uccl_tpu.models.inference import SlotKVCache

    rows, held = 4, 7
    stub = _Stub(world, counted)
    backend = SlotBackend(
        None, None, stub,
        lambda: SlotKVCache(*(jnp.zeros(1) for _ in range(3))),
        n_slots=rows, max_seq=MAX_SEQ, rungs=(rows,), world=world,
        experts_held=held if counted else 0)
    # what an engine might hand over: lists, wider ints, 0/1 masks
    toks = np.arange(rows * 3, dtype=np.int64).reshape(rows, 3)
    mask = [1, 0, 1, 1]
    sampling = (np.arange(rows), list(range(rows)), np.ones(rows),
                np.ones(rows, np.float64), np.arange(rows))
    tables = {t: (jnp.ones((2, 3)), jnp.ones((3, 2))) for t in ("wq", "wv")}
    adapters = (tables, [0, 1, 0, 1])
    read = obs.counter("ep_experts_read_total")
    before = read.get()
    out = {
        "prefill": backend.prefill(toks, toks[:, 0], mask, start=toks[:, 1],
                                   sampling=sampling, adapters=adapters,
                                   slots=np.arange(rows)),
        "decode": backend.decode(toks[:, 0], mask, sampling, adapters),
        "verify": backend.verify(toks, mask, sampling, adapters),
    }
    assert read.get() - before == (2 * 5 * (world or 1) if counted else 0)
    lead = (rows,) if world is None else (world, rows // world)
    assert [k for k, _, _ in stub.calls] == ["prefill", "decode", "verify"]
    for kind, args, kw in stub.calls:
        want = {"prefill": [(3, np.int32), (0, np.int32), (0, bool)],
                "decode": [(0, np.int32), (0, bool)],
                "verify": [(3, np.int32), (0, bool)]}[kind]
        per_row = list(args) + list(kw["sampling"]) + [kw["adapter_ids"]]
        want = want + [(0, dt) for dt in (np.int32, np.int32, np.float32,
                                          np.float32, np.int32, np.int32)]
        if kind == "prefill":
            per_row += [kw["start"], kw["slots"]]
            want = want + [(0, np.int32)] * 2
        else:
            assert not {"start", "slots"} & set(kw)
        assert len(per_row) == len(want)
        for a, (width, dtype) in zip(per_row, want):
            assert type(a) is np.ndarray, (kind, type(a))
            assert a.dtype == dtype, (kind, a.dtype, dtype)
            assert a.shape == lead + ((width,) if width else ()), kind
        for ab in kw["adapters"].values():
            assert all(a.shape[:-2] == lead[:-1] for a in ab)
    np.testing.assert_array_equal(stub.calls[2][1][0].reshape(rows, 3), toks)
    assert out["prefill"].shape == out["decode"].shape == (rows,)
    assert [o.shape for o in out["verify"]] == [(rows, 3), (rows,)]


# -- a chunk step launches both its programs before it reads either (ISSUE 47)

CHUNK_CALLS = obs.counter("serving_chunk_step_calls_total")
LABELS = ("together", "prompt_ends", "no_decode", "in_turn")
# {engine step: lengths of the prompts that arrive before it}: prompts keep
# arriving while earlier rows decode, some a multiple of the chunk
SCHEDULES = {
    "staggered": {0: [9], 2: [13], 5: [6, 17], 9: [8]},
    "burst": {0: [5], 3: [25, 7, 12], 9: [26]},
}


class _InTurn:
    """The backend with its two halves hidden: what a stub or an external
    backend looks like to the engine, which then keeps its calls in turn."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name in ("launch_prefill", "launch_decode", "fetch"):
            raise AttributeError(name)
        return getattr(self._inner, name)


def _labels():
    return {k: CHUNK_CALLS.get(calls=k) for k in LABELS}


def _drive(backend, chunk, schedule, n_new=6, steps=60, **kw):
    """Serve ``schedule`` step by step. Returns (every request's tokens with
    the engine step each was emitted in, the ring's spans, how the counter
    moved)."""
    eng = ServingEngine(backend, prefill_chunk=chunk, **kw)
    rng = np.random.default_rng(7)
    reqs, emitted = [], []
    before = _labels()
    tr = obs.enable_tracing()
    try:
        for i in range(steps):
            for n in schedule.get(i, ()):
                reqs.append(eng.submit(rng.integers(1, 64, n).astype(np.int32),
                                       max_new_tokens=n_new))
                emitted.append([])
            if eng.has_work():
                eng.step()
            for r, at in zip(reqs, emitted):
                at.extend([i] * (len(r.out_tokens) - len(at)))
        spans = sorted((e for e in tr.events() if e.ph == "X"),
                       key=lambda e: e.ts_us)
    finally:
        obs.disable_tracing()
    assert not eng.has_work() and all(r.is_done() for r in reqs)
    moved = {k: v - before[k] for k, v in _labels().items()}
    return [(r.out_tokens, at) for r, at in zip(reqs, emitted)], spans, moved


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("chunk", [CHUNK, 2 * CHUNK])
@pytest.mark.parametrize("stack", STACKS)
def test_together_serves_in_turns_tokens_in_in_turns_steps(stacks, stack,
                                                           chunk, schedule):
    proto, oracle, _ = stacks[stack]
    served, spans, moved = _drive(proto.clone(), chunk, SCHEDULES[schedule])
    in_turn, _, hidden = _drive(_InTurn(proto.clone()), chunk,
                                SCHEDULES[schedule])
    assert served == in_turn  # every token, and the step it came in
    # the counter's labels add up to the chunk steps taken, which are the
    # same steps either way; only how their calls went differs
    chunk_steps = sum(e.name == "wire.prefill" for e in spans)
    assert sum(moved.values()) == sum(hidden.values()) == chunk_steps
    assert moved["together"] > 0 and moved["in_turn"] == 0
    assert hidden["together"] == 0 and hidden["in_turn"] == moved["together"]
    for k in ("prompt_ends", "no_decode"):
        assert moved[k] == hidden[k]
    assert moved["prompt_ends"] > 0
    # the step's span carries the same word
    said = Counter(e.args["calls"] for e in spans
                   if e.name == "engine.step" and "calls" in (e.args or {}))
    assert said == Counter({k: int(v) for k, v in moved.items() if v})


@pytest.mark.parametrize("stack", STACKS)
def test_the_spans_of_a_step_by_how_its_calls_went(stacks, stack):
    """``together``: wire.prefill holds stage, launch, stage, launch and the
    prefill call's fetch, closes, and only then wire.decode opens, around
    the decode call's fetch alone. ``prompt_ends``: the two calls in turn,
    and the row whose prompt ended decodes in that same step."""
    served, spans, moved = _drive(stacks[stack][0].clone(), CHUNK,
                                  SCHEDULES["staggered"])
    steps = [e for e in spans if e.name == "engine.step"
             and (e.args or {}).get("calls") in ("together", "prompt_ends")]
    assert {e.args["calls"] for e in steps} == {"together", "prompt_ends"}
    for step in steps:
        inner = [e for e in spans if _inside(e, step) and e is not step
                 and e.name.startswith(("wire.", "backend."))]
        pre, = [e for e in inner if e.name == "wire.prefill"]
        dec, = [e for e in inner if e.name == "wire.decode"]
        assert pre.ts_us + pre.dur_us <= dec.ts_us + 1e-3
        assert dec.args["step"] == pre.args["step"] and dec.args["n"] >= 1
        assert "kv_rows" in dec.args
        names = lambda w: [e.name for e in inner
                           if e is not w and _inside(e, w)]
        triple = ["backend.stage", "backend.launch", "backend.fetch"]
        if step.args["calls"] == "together":
            assert names(pre) == triple[:2] * 2 + triple[2:]
            assert names(dec) == triple[2:]
        else:
            assert names(pre) == names(dec) == triple
    # a prompt that ends in a step takes its first AND its second token in it
    joined = [at for _, at in served if at[0] == at[1]]
    assert joined and moved["prompt_ends"] >= len(joined)


@pytest.mark.parametrize("stack", STACKS)
def test_chunk_sink_sees_a_steps_events_before_any_of_its_retirements(
        stacks, stack):
    """Also in a step whose decode program was launched before the sink ran:
    the rows it retires are still in their slots when the sink is called."""
    seen = []  # (rids not finished when the sink ran, the events' rids)

    def sink(events):
        seen.append(({r.rid for r in eng._by_slot.values()},
                     [ev.req.rid for ev in events]))

    eng = ServingEngine(stacks[stack][0].clone(), prefill_chunk=CHUNK,
                        chunk_sink=sink)
    first = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    before = _labels()
    retired_together = False
    for i in range(12):
        if i == 2:  # three more chunk steps, beside ``first``'s last tokens
            late = eng.submit(np.arange(1, 14, dtype=np.int32),
                              max_new_tokens=2)
        n_calls = len(seen)
        together = CHUNK_CALLS.get(calls="together")
        finished = eng.step() if eng.has_work() else []
        if (first in finished
                and CHUNK_CALLS.get(calls="together") > together):
            retired_together = True
            assert len(seen) == n_calls + 1
            live, rids = seen[-1]
            assert first.rid in live and rids == [late.rid]
    assert retired_together and first.is_done() and late.is_done()
    assert _labels()["together"] - before["together"] >= 2


@pytest.mark.parametrize("stack", STACKS)
def test_a_launch_that_fails_after_consuming_the_pool_leaves_nothing_unread(
        stacks, stack):
    """The launch half raises what the whole call raised, and a chunk step
    whose decode launch fails has read and booked the prefill call it had
    launched: the engine's state is in turn's at the failure."""
    backend = stacks[stack][0].clone()
    eng = ServingEngine(backend, prefill_chunk=CHUNK)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    late = eng.submit(np.arange(1, 14, dtype=np.int32), max_new_tokens=2)
    real = backend.programs

    class Broken:
        prefill = staticmethod(real.prefill)

        @staticmethod
        def decode(params, tokens, active, pool, **kw):
            for leaf in jax.tree.leaves((pool.k, pool.v)):
                leaf.delete()
            raise FloatingPointError("device fault")

    backend.programs = Broken()
    unread = Counter()
    launch, fetch = backend._launch, backend._fetch
    backend._launch = lambda kind, *a, **kw: (
        unread.update([kind]), launch(kind, *a, **kw))[1]
    backend._fetch = lambda kind, out: (
        unread.subtract([kind]), fetch(kind, out))[1]
    before = _labels()
    with pytest.raises(RuntimeError, match="consumed the slot pool") as e:
        eng.step()
    assert isinstance(e.value.__cause__, FloatingPointError)
    assert _labels()["together"] - before["together"] == 1
    # the prefill call was launched (counted before the program ran), read
    # and booked; the decode call never came to be
    assert unread == Counter({"prefill": 0, "decode": 1})
    assert late.prefill_pos == CHUNK
    with pytest.raises(RuntimeError, match="consumed the slot pool"):
        backend.launch_decode(np.zeros(N_SLOTS, np.int32),
                              np.zeros(N_SLOTS, bool))
