"""The one slot backend's contract, over its two constructors (ISSUE 31).

``DenseBackend`` and ``MoEBackend`` are constructors of ``SlotBackend`` and
nothing else. Held here for both: every program call is one ``backend.stage
-> backend.launch -> backend.fetch`` inside the engine's ``wire.*`` span (the
nest ``chipbench/program_trace.py`` reads the device's idle time by);
``clone()`` and ``clone(params=tree)`` share the prototype's compiled programs,
own their pool and serve the oracle's tokens without a new program; a tree
that does not match is refused before it serves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu import obs
from uccl_tpu.serving import (
    DenseBackend, MoEBackend, NGramDrafter, ServingEngine,
)
from uccl_tpu.serving.backend import SlotBackend

MAX_SEQ = 32
N_SLOTS = 4
CHUNK = 4
STACKS = ("dense", "moe")


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


def _moe(devices):
    from jax.sharding import Mesh

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )

    cfg = MoEServeConfig(vocab=64, dim=32, n_layers=1, n_heads=4,
                         n_kv_heads=2, head_dim=8, moe_experts=8,
                         moe_topk=2, moe_ffn=64)
    srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
    placed = srv.shard_params(init_params(jax.random.PRNGKey(0), cfg))

    def oracle(p, prompt, n):
        want = srv.generate(p, jnp.asarray(prompt)[None, None], n, MAX_SEQ,
                            impl="sort")
        return np.asarray(want)[0, 0].tolist()

    backend = MoEBackend(srv, placed, batch_local=N_SLOTS, max_seq=MAX_SEQ,
                         decode_impl="sort")
    return backend, oracle, srv._fns


@pytest.fixture(scope="module")
def stacks(devices):
    """{name: (prototype backend, oracle(params, prompt, n), the LRU its
    compiled programs live in)}, every program of a chunked engine and of a
    speculating one already built."""
    out = {"dense": _dense(), "moe": _moe(devices)}
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

    def inside(e, w):
        return (w.ts_us <= e.ts_us
                and e.ts_us + e.dur_us <= w.ts_us + w.dur_us + 1e-3)

    for w in wires:
        mine = [e for e in inner if inside(e, w)]
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
    assert all(any(inside(e, w) for w in every_wire) for e in inner)


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
