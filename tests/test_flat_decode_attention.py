"""A decode step of a description with layer kinds contracts each layer's
cached rows FLAT, ``[B, rows, Hkv * D]`` as the pool holds them
(``inference._grouped_attention`` where ``Sq == 1``); every wider call (a
prefill chunk, a verify window) keeps the grouped form over the rows viewed
``[B, rows, Hkv, D]``. Both descriptions that have layer kinds, at the tiny
sizes of their own test files (MiMo-V2-Flash: keys 12 wide and values 8, a
sink in the window softmax, rings of 16; Trinity: gated heads, QK-norm,
unrotated full layers, rings of 12), each also with ONE KV head.

A step is held to the grouped form over the same rows — a two-wide window
whose first column is the step; what its second column writes lies past the
slot's length and is dead — within ``PATH_TOL``, and to the plain float32
reference within ``LOGIT_TOL`` (tests/test_hybrid_moe_serving.py has what
the tolerances catch). The programs' lowered text says which form each
holds.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from test_afmoe_serving import OVERRIDES, TINY
from test_hybrid_moe_serving import (
    HYBRID, LOGIT_TOL, MAX_SEQ, PATH_TOL, _lowered_programs, _pool_rows,
    _slot_logits, _tokens,
)
from uccl_tpu.models import reference_hybrid_moe as ref
from uccl_tpu.models.inference import kv_row_shapes
from uccl_tpu.models.moe_inference import (
    MoEServeConfig, MoEServer, init_params,
)

DESCRIPTIONS = {
    "mimo": lambda: MoEServeConfig(**HYBRID),
    "mimo_one_kv_head": lambda: MoEServeConfig(
        **{**HYBRID, "n_kv_heads": 1, "window_kv_heads": 1}),
    "afmoe": lambda: MoEServeConfig.from_hf(TINY, **OVERRIDES),
    "afmoe_one_kv_head": lambda: MoEServeConfig.from_hf(
        {**TINY, "num_key_value_heads": 1}, **OVERRIDES),
}
CHUNK = 4
# where the step stands: inside the first window of 8; past it; past a ring
# that has wrapped once (16 rows, 12 rows); wrapped twice and more
POSITIONS = (4, 12, 20, 36)


@pytest.fixture(scope="module")
def models(devices):
    built = {}

    def get(name):
        if name not in built:
            cfg = DESCRIPTIONS[name]()
            params = init_params(jax.random.PRNGKey(17), cfg)
            srv = MoEServer(cfg, Mesh(np.array(devices[:1]), ("dp",)))
            built[name] = cfg, params, srv, srv.shard_params(params)
        return built[name]

    return get


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("neighbour", ["idle", "decoding"])
@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("name", sorted(DESCRIPTIONS))
def test_a_flat_step_is_the_grouped_form_and_the_reference(
        models, name, position, neighbour):
    cfg, params, srv, placed = models(name)
    both = np.stack([_tokens(position + 2, seed=position),
                     _tokens(position + 2, seed=100 + position)])
    live = np.array([True, neighbour == "decoding"])
    cache = srv.slot_cache(2, MAX_SEQ)
    for lo in range(0, position, CHUNK):
        _, cache = _slot_logits(srv, placed, both[:, lo:lo + CHUNK], cache,
                                [lo, lo], np.ones(2, bool))
    start = [position, position]
    before = _pool_rows(cache, 1)
    step, after = _slot_logits(srv, placed, both[:, position:position + 1],
                               cache, start, live)
    grouped, _ = _slot_logits(srv, placed, both[:, position:position + 2],
                              cache, start, live)
    for row in np.flatnonzero(live):
        np.testing.assert_allclose(step[row, 0], grouped[row, 0],
                                   atol=PATH_TOL)
        want = np.asarray(ref.forward_logits(
            params, both[row, :position + 1], cfg))[position]
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(step[row, 0], want, atol=LOGIT_TOL)
    if neighbour == "idle":
        for a, b in zip(_pool_rows(after, 1), before):
            assert np.array_equal(a, b)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("name", sorted(DESCRIPTIONS))
def test_only_the_decode_program_leaves_the_rows_flat(models, name, program):
    """No value of the decode program is a cache group's layer viewed
    ``[B, rows, Hkv, D]`` (on the chip that view, sliced out of a group of
    two or more layers, is a bfloat16 copy of the whole layer in another
    layout: PERF.md section 6, PR 41); the prefill program holds that view
    of every group, keys and values, as it did."""
    cfg, params, srv, placed = models(name)
    text = _lowered_programs(srv, placed, CHUNK)[program].as_text()
    for kind, rows in (("full", MAX_SEQ), ("window", cfg.ring)):
        hkv = cfg.kv_heads(kind)
        for flat in kv_row_shapes(cfg, kind):
            viewed = f"tensor<2x{rows}x{hkv}x{flat[0] // hkv}xf32>"
            assert f"tensor<2x{rows}x{flat[0]}xf32>" in text
            assert (viewed in text) == (program == "prefill"), (
                f"{viewed} in the {program} program of {name}")
