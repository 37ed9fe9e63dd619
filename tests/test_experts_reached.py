"""The decode / verify program's expert GEMMs run over the experts a row
which counts reached (``ep.ops.moe_ffn(..., rows=)``,
``_expert_gemms_reached``): the layer against the batched layer, the
reached count against a numpy count, the counter from 0 to 100 %, and the
lowered programs' texts — the loop in the decode program and in no other."""

import re

import jax
from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu import obs
from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, MoESlotCache, init_params,
)
from uccl_tpu.serving import ServingEngine
from uccl_tpu.serving.backend import MoEBackend


T, H, F, E, K = 12, 16, 24, 8, 2


@pytest.fixture(scope="module")
def devices():
    return jax.devices()


def _weights(seed, held, layers=3, dtype=jnp.bfloat16):
    """A stack of ``layers`` layers' expert leaves as the serving tree
    stores them, and one token batch with its router."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(H, E)) / 4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=E) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(layers, held, H, F)) / 4, dtype)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(layers, held, F, H)) / 5, dtype)
    return x, router, bias, wg, wu, wd


def _layer(devices, x, router, wg, wu, wd, impl="sort", rows=None, **kw):
    """``moe_ffn`` on one shard; every output it gives, as numpy."""
    mesh = Mesh(np.array(devices[:1]), ("dp",))
    counted = rows is not None

    def f_(x, wg, wu, wd, rows):
        res = ep_ops.moe_ffn(
            x[0], jnp.dot(x[0], router, precision="highest"), wg, wu, wd,
            "dp", num_selected=K, capacity_factor=float(E), impl=impl,
            rows=rows[0] if counted else None, **kw)
        return tuple(r[None] for r in res)

    rows = jnp.zeros(T, bool) if rows is None else jnp.asarray(rows)
    return [np.asarray(r)[0] for r in jax.jit(shard_map(
        f_, mesh=mesh, in_specs=(P("dp"), P(), P(), P(), P("dp")),
        out_specs=(P("dp"),) * (3 + counted),
        check_vma=False))(x[None], wg, wu, wd, rows[None])]


def _choices(x, router, bias, gate):
    """numpy's own top-k of the gate: [T, K] expert ids."""
    logits = np.asarray(jnp.dot(x, router, precision="highest"), np.float64)
    if gate == "sigmoid_bias":
        score = 1 / (1 + np.exp(-logits)) + np.asarray(bias, np.float64)
    else:
        score = logits
    return np.argsort(-score, axis=1, kind="stable")[:, :K]


ROWS = {"no_row": np.zeros(T, bool),
        "one_row": np.arange(T) == 5,
        "all_rows": np.ones(T, bool)}


@pytest.mark.parametrize("counting", list(ROWS))
@pytest.mark.parametrize("gate", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("share", ["all_experts", "held_share"])
def test_rows_that_count_are_computed_as_the_batched_layer_computes_them(
        devices, share, gate, counting):
    """Bit for bit on the rows that count, whoever else is computed; a held
    share has pairs for absent experts beside the masked rows' pairs, and
    both go to the one queue nobody gathers."""
    held, first = (E, 0) if share == "all_experts" else (3, 2)
    x, router, bias, wg, wu, wd = _weights(7, held)
    layer = 1
    gating = dict(gate=gate, gate_bias=bias if gate == "sigmoid_bias"
                  else None, routed_scale=1.5)
    if share == "held_share":
        gating.update(experts_held=held, first_expert=first)
    want, _, _ = _layer(
        devices, x, router, *(w[layer].astype(jnp.float32)
                              for w in (wg, wu, wd)), **gating)
    rows = ROWS[counting]
    got, _, _, n_reached = _layer(devices, x, router, wg, wu, wd,
                                  rows=rows, layer=layer,
                                  **gating)
    np.testing.assert_array_equal(got[rows], want[rows])
    if rows.any():  # and the rows are worth comparing
        assert np.abs(want[rows]).max() > 0.01
    # the reached count: distinct held experts among the counting rows'
    # choices, by numpy's own top-k
    idx = _choices(x, router, bias, gate)[rows]
    here = idx[(idx >= first) & (idx < first + held)]
    assert int(n_reached) == len(set(here.tolist()))
    if counting == "all_rows":
        assert int(n_reached) > held // 2
    # an unstacked leaf (``layer`` None) is the same layer
    again, _, _, n2 = _layer(devices, x, router, wg[layer], wu[layer],
                             wd[layer], rows=rows, **gating)
    np.testing.assert_array_equal(again[rows], want[rows])
    assert int(n2) == int(n_reached)


@pytest.mark.parametrize("impl", ["dense", "ll"])
def test_rows_are_refused_where_nothing_loops(devices, impl):
    """``dense`` and ``ll`` (and any layer over an exchange) keep the
    batched GEMMs: their callers hand no rows."""
    x, router, bias, wg, wu, wd = _weights(11, E)
    with pytest.raises(ValueError, match="rows that count"):
        _layer(devices, x, router, wg, wu, wd, impl=impl,
               rows=ROWS["one_row"], layer=2)


# -- through the serving programs ---------------------------------------------

SLOTS, MAX_SEQ = 4, 32
CFG = MoEServeConfig(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                     head_dim=8, moe_experts=4, moe_topk=2, moe_ffn=16,
                     capacity_factor=2.0)


@pytest.fixture(scope="module")
def served(devices):
    srv = MoEServer(CFG, Mesh(np.array(devices[:1]), ("dp",)))
    params = srv.shard_params(init_params(jax.random.PRNGKey(0), CFG))
    return srv, params


def _read_share():
    return (obs.counter("ep_experts_read_total").get(),
            obs.counter("ep_experts_held_total").get())


def test_the_counter_goes_from_nothing_read_to_everything_read(served):
    """No row counting: no expert read. All rows counting, with tokens
    whose routing covers every held expert: every expert read. Between
    them, the engine's own decode steps."""
    srv, params = served
    backend = MoEBackend(srv, params, batch_local=SLOTS, max_seq=MAX_SEQ,
                         decode_impl="sort")
    held = CFG.moe_experts * CFG.n_layers
    assert backend.experts_held == held
    tracer = obs.enable_tracing()
    try:
        r0, h0 = _read_share()
        backend.decode(np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool))
        r1, h1 = _read_share()
        assert (r1 - r0, h1 - h0) == (0, held)
        # four rows x top-2 of four experts a layer: try token sets until
        # one covers every expert of both layers (the first few do)
        full = None
        for seed in range(40):
            tok = np.random.default_rng(seed).integers(0, 64, SLOTS)
            r, _ = _read_share()
            backend.decode(tok.astype(np.int32), np.ones(SLOTS, bool))
            if _read_share()[0] - r == held:
                full = seed
                break
        assert full is not None
        spans = [e for e in tracer.events() if e.name == "ep.experts"]
        assert spans[0].args == {"experts_read": 0, "experts_held": held}
        assert spans[-1].args == {"experts_read": held,
                                  "experts_held": held}
    finally:
        obs.disable_tracing()


def test_an_engine_run_reads_fewer_experts_than_it_holds(served):
    """One request decoding in a pool of four: the other three slots' dummy
    rows reach nothing, so a step reads at most top-k experts a layer."""
    srv, params = served
    backend = MoEBackend(srv, params, batch_local=SLOTS, max_seq=MAX_SEQ,
                         decode_impl="sort")
    eng = ServingEngine(backend, prefill_chunk=4)
    r0, h0 = _read_share()
    eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=6)
    eng.drain()
    r1, h1 = _read_share()
    steps = (h1 - h0) / backend.experts_held
    assert steps >= 5
    assert 0 < r1 - r0 <= steps * CFG.moe_topk * CFG.n_layers


def test_the_ll_decode_reads_every_expert_it_holds(served):
    srv, params = served
    backend = MoEBackend(srv, params, batch_local=SLOTS, max_seq=MAX_SEQ)
    r0, h0 = _read_share()
    backend.decode(np.ones(SLOTS, np.int32), np.zeros(SLOTS, bool))
    r1, h1 = _read_share()
    assert r1 - r0 == h1 - h0 == backend.experts_held


# -- the lowered programs ------------------------------------------------------

@pytest.fixture(scope="module")
def texts(served):
    srv, params = served
    cache = srv.slot_cache(SLOTS, MAX_SEQ)
    act = jnp.ones((1, SLOTS), bool)

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    def prefill(p, tok, lens, mask, start, slots, k, v, ln):
        return srv.prefill_slots(p, tok, lens, mask, MoESlotCache(k, v, ln),
                                 start=start, slots=slots)

    one = jnp.ones((1, 1), jnp.int32)
    one_row = jax.jit(prefill).lower(
        params, jnp.ones((1, 1, 4), jnp.int32), 4 * one,
        jnp.ones((1, 1), bool), 0 * one, 0 * one, *cache)
    return {
        "decode": jax.jit(decode).lower(
            params, jnp.ones((1, SLOTS), jnp.int32), act, *cache).as_text(),
        "prefill_one_row": one_row.as_text(),
        "prefill_one_row_compiled": one_row.compile().as_text(),
    }


def _whole_leaf_ops(text, shape):
    """Lines of a lowered program that convert or copy a value of a whole
    expert leaf's shape (``shape``, any element type)."""
    dims = "x".join(map(str, shape))
    return [ln for ln in text.splitlines()
            if re.search(r"stablehlo\.(convert|copy)\b", ln)
            and re.search(rf"tensor<{dims}x\w+>\s*$", ln)]


def _expert_slices(text, h, f):
    """The loop's mark in a lowered program: dynamic slices that take ONE
    expert's ``[h, f]`` matrix out of a leaf (a trainer's own scans loop
    over other things)."""
    return re.findall(
        rf"stablehlo\.dynamic_slice.*-> tensor<(?:1x)+{h}x{f}x\w+>", text)


def test_the_decode_program_loops_over_experts_and_casts_no_whole_leaf(
        texts):
    text = texts["decode"]
    assert text.count("stablehlo.while") == CFG.n_layers  # one a layer
    # the leaves as placed [L, W, E, ...], as the program sees them
    # [L, E, ...], and one layer's [E, ...]: none is converted or copied
    # whole; what is converted is one expert's slice
    e, h, f, n = CFG.moe_experts, CFG.dim, CFG.moe_ffn, CFG.n_layers
    for a, b in ((h, f), (f, h)):
        for lead in ((n, 1, e), (n, e), (e,)):
            assert not _whole_leaf_ops(text, lead + (a, b)), lead
    assert len(_expert_slices(text, h, f)) == 2 * n  # gate and up, a layer


def test_no_other_program_holds_the_loop(texts, devices):
    """The one-row prefill program and the trainer's step lower without a
    loop from this path: their expert GEMMs are the batched einsum."""
    assert "stablehlo.while" not in texts["prefill_one_row"]
    assert not _expert_slices(texts["prefill_one_row"], CFG.dim, CFG.moe_ffn)
    assert "moe.experts" in texts["prefill_one_row_compiled"]

    from uccl_tpu.models.flagship import (
        FlagshipConfig, init_params as train_init, make_train_step,
        shard_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    tcfg = FlagshipConfig(vocab=64, dim=32, n_layers=1, n_heads=4,
                          n_kv_heads=2, head_dim=8, moe_experts=4,
                          moe_topk=2, moe_ffn=48, capacity_factor=2.0)
    mesh = make_mesh(MeshConfig(dp=2), devices[:2])
    tparams = shard_params(train_init(jax.random.PRNGKey(2), tcfg), mesh,
                           tcfg)
    train_step, init_opt = make_train_step(tcfg, mesh)
    data = jnp.zeros((4, 16), jnp.int32)
    text = jax.jit(train_step).lower(tparams, init_opt(tparams), data,
                                     data).as_text()
    assert not _expert_slices(text, tcfg.dim, tcfg.moe_ffn)
