"""Grouped-query attention over the cache where it lies (ISSUE 37).

``inference._attend_cached`` contracts the queries, grouped by KV head,
against the cache as the pool holds it. Held here: it computes what
repeat-then-attend computes (query head ``j`` reads KV head ``j // n_rep``,
``jnp.repeat``'s order), for one shared length and for per-slot lengths; and
no slot program carries a value ``n_rep`` times the size of the cached rows it
attends over — the repeat cannot come back unseen.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu.models import dense, inference
from uccl_tpu.models.inference import SlotKVCache, _attend_cached

S_MAX = 96
HKV, D = 2, 8


def _repeat_then_attend(q, k_cache, v_cache, length, n_rep):
    """The plain form: every KV head copied ``n_rep`` times, then multi-head
    attention over the cached prefix and the new causal block."""
    sq, smax = q.shape[1], k_cache.shape[1]
    kk = jnp.repeat(k_cache, n_rep, axis=2)
    vv = jnp.repeat(v_cache, n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(q.shape[-1])
    qpos = jnp.reshape(length, (-1, 1, 1)) + jnp.arange(sq)[None, :, None]
    seen = jnp.arange(smax)[None, None, :] <= qpos  # [B | 1, Sq, Smax]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("lengths", ["scalar", "per_slot"])
@pytest.mark.parametrize("sq", [1, 3, 64])
@pytest.mark.parametrize("n_rep", [1, 2, 4])
def test_attend_cached_is_repeat_then_attend(rng, n_rep, sq, lengths):
    b = 3
    q = jnp.asarray(rng.standard_normal((b, sq, HKV * n_rep, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, S_MAX, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, S_MAX, HKV, D)), jnp.float32)
    if lengths == "scalar":
        length = jnp.int32(5)
    else:  # an empty row, a middling one, one whose window ends the cache
        length = jnp.asarray([0, 7, S_MAX - sq], jnp.int32)
    got = _attend_cached(q, k, v, length, SimpleNamespace(n_kv_heads=HKV))
    want = _repeat_then_attend(q, k, v, length, n_rep)
    assert got.shape == want.shape == (b, sq, HKV * n_rep, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# -- no program holds a repeated cache ----------------------------------------

N_REP = 4
SLOTS = 2   # fewer than N_REP: the pool itself stays under every bound below
CHUNK = 4   # narrower than D, so a chunk's scores stay under the bound too


def _values(jaxpr):
    """Every value an equation of ``jaxpr`` or of a jaxpr nested in it (jit,
    shard_map, scan, the branches of a cond) produces."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _values(sub)


def _dense_programs():
    cfg = dense.DenseConfig(vocab=64, dim=32, n_layers=1, n_heads=HKV * N_REP,
                            n_kv_heads=HKV, head_dim=D, ffn=64)
    params = dense.init_params(jax.random.PRNGKey(0), cfg)
    cache = SlotKVCache.empty(cfg, SLOTS, S_MAX)

    def decode(tok, active):
        return inference.decode_step_slots(params, tok, active, cache, cfg)

    def prefill(tok, lens, mask, start, slots):
        return inference.prefill_slots(params, tok, lens, mask, cache, cfg,
                                       start=start, slots=slots)

    return decode, prefill


def _moe_programs():
    from jax.sharding import Mesh

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )

    cfg = MoEServeConfig(vocab=64, dim=32, n_layers=1, n_heads=HKV * N_REP,
                         n_kv_heads=HKV, head_dim=D, moe_experts=2,
                         moe_topk=1, moe_ffn=16, capacity_factor=2.0)
    srv = MoEServer(cfg, Mesh(np.array(jax.devices()[:1]), ("dp",)))
    params = srv.shard_params(init_params(jax.random.PRNGKey(0), cfg))
    cache = srv.slot_cache(SLOTS, S_MAX)

    def decode(tok, active):
        return srv.decode_step_slots(params, tok[None], active[None], cache,
                                     impl="sort")

    def prefill(tok, lens, mask, start, slots):
        return srv.prefill_slots(params, tok[None], lens[None], mask[None],
                                 cache, start=start[None], slots=slots[None])

    return decode, prefill


@pytest.mark.parametrize("program", ["decode", "prefill_one_row"])
@pytest.mark.parametrize("stack", ["dense", "moe"])
def test_no_program_value_is_a_repeated_cache(stack, program):
    decode, prefill = {"dense": _dense_programs, "moe": _moe_programs}[stack]()
    if program == "decode":  # [B, 1] over the whole pool
        rows = SLOTS
        jaxpr = jax.make_jaxpr(decode)(jnp.zeros((SLOTS,), jnp.int32),
                                       jnp.ones((SLOTS,), bool))
    else:  # [1, CHUNK] over one slot's rows
        rows = 1
        one = jnp.ones((1,), jnp.int32)
        jaxpr = jax.make_jaxpr(prefill)(
            jnp.zeros((1, CHUNK), jnp.int32), one * CHUNK, one > 0, one * 0,
            one)
    attended = rows * S_MAX * HKV * D  # one layer's rows the program reads
    sizes = [math.prod(a.shape) for a in _values(jaxpr.jaxpr)
             if hasattr(a, "shape")]
    # the walk reached the layer: the scatter's output is the whole pool
    assert SLOTS * S_MAX * HKV * D in sizes
    largest = max(sizes)
    assert largest < N_REP * attended, (
        f"a value of {largest} numbers in the {stack} {program} program: "
        f"{N_REP} x the {attended} cached numbers it attends over would be "
        f"the GQA repeat")
