"""What guards the chip path from the CPU: the Pallas kernels cross-lowered
for the TPU, chip_smoke.py's phases at a tiny size on a CPU mesh, its
refusal to run without a chip, and a serving program COMPILED for a v5e that
is described and not attached.

Cross-lowering (``lower(lowering_platforms=("tpu",))``) is the free
pre-flight before chip time: tracing, block specs, scratch shapes, semaphore
plumbing and the Pallas→Mosaic-MLIR stage all run here, and the kernels must
come out as ``tpu_custom_call``s. What only a chip can say — Mosaic's
backend compile (VMEM limit, tiling) and execution — is chip_smoke.py's and
the four-chip session's (PERF.md).

The TPU's compiler is installed here and compiles for a described chip
(``jax.experimental.topologies``; the ``v5e`` fixture): what it makes of a
program — which operations stand at entry level, how many bytes of
temporaries it needs — is read from the compiled program, no chip time. The
fixture is this file's alone and is asked for by name: a process keeps the
TPU's library once it has loaded it, so only the worker that runs this file
does. Nothing runs there: a time is the chip's to say.

The tiny size for chip_smoke's phases is chosen HERE, explicitly — the
script has no small mode and never picks a size from the absence of a chip.
Those two tests run it in a subprocess: its entry points read
``jax.devices()``, and two virtual devices keep the compile cost down.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uccl_tpu.collective import pallas_ccl
from uccl_tpu.ep import pallas_a2a
from uccl_tpu.ops.pallas_attention import flash_attention

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TINY_RUN = """
import json
import chip_smoke

tiny = dict(vocab=128, dim=32, layers=1, heads=2, kv_heads=1, experts=2,
            ffn=64)
phases = chip_smoke.run(tiny, seq=32, batch_per_chip=1, steps=2,
                        slots_per_chip=1, requests=3, prompt_len=4,
                        new_tokens=3, prefill_chunk=2)
print("PHASES " + json.dumps(phases))
"""


def _tpu_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)
    ).as_text()


def test_flash_fwd_bwd_lower_at_flagship_geometry():
    """B=2 S=1024 H=16 KV=4 D=64 bf16, auto-sized (1024) tiles, compiled
    (interpret=False): one custom call forward, three with the backward
    (recomputed forward excluded: dq and dk/dv kernels plus the forward)."""
    q = jax.ShapeDtypeStruct((2, 1024, 16, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 1024, 4, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    assert _tpu_text(fwd, q, kv, kv).count("tpu_custom_call") == 1
    bwd = _tpu_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert bwd.count("tpu_custom_call") == 3


def test_flash_compiled_rejects_untileable_sequence():
    """A sequence Mosaic cannot tile is an error that names the shape when
    the kernel would be compiled — never a quiet switch to another path."""
    q = jnp.zeros((1, 1001, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="q=1001, kv=1001"):
        flash_attention(q, q, q, True, interpret=False)


@pytest.mark.parametrize(
    "name,fn,shape",
    [
        ("ring_all_reduce",
         lambda v: pallas_ccl.ring_all_reduce(v, "x", interpret=False),
         (4, 4096)),
        ("ring_all_gather",
         lambda v: pallas_ccl.ring_all_gather(v, "x", interpret=False),
         (4, 1024)),
        ("ep_all_to_all",
         lambda v: pallas_a2a.all_to_all(v[0], "x", interpret=False)[None],
         (4, 4, 1024)),
    ],
)
def test_remote_dma_kernels_lower(devices, name, fn, shape):
    mesh = Mesh(np.array(devices[:4]), ("x",))
    mapped = shard_map(fn, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                       check_vma=False)
    txt = _tpu_text(mapped, jax.ShapeDtypeStruct(shape, jnp.float32))
    assert "tpu_custom_call" in txt, name


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=_REPO, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
    )


def test_phases_at_tiny_size_on_cpu_mesh():
    r = _run(["-c", _TINY_RUN],
             XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert r.returncode == 0, r.stdout + r.stderr
    line = next(l for l in r.stdout.splitlines() if l.startswith("PHASES "))
    phases = json.loads(line[len("PHASES "):])
    assert set(phases) == {"attention", "serve_whole_prompt",
                           "serve_chunked", "train"}
    cpu2 = {"platform": "cpu", "kind": "cpu", "count": 2}
    # a CPU run says so in every summary: interpreted kernel, XLA attention
    # in the trainer, float32 activations, no device memory statistics
    assert phases["attention"]["interpret"] is True
    train = phases["train"]
    assert train["device"] == cpu2 and train["mesh"]["dp"] == 2
    assert (train["attn_impl"], train["dtype"]) == ("xla", "float32")
    assert train["peak_bytes_in_use"] is None
    assert train["first_step_s"] > 0 and len(train["losses"]) == 2
    for name in ("serve_whole_prompt", "serve_chunked"):
        s = phases[name]
        assert s["device"] == cpu2 and s["devices_used"] == 2
        # world 2 decodes on the packed LL path; XLA:CPU has no ragged wire
        assert (s["decode_impl"], s["ll_wire"]) == ("ll", "dense")
    assert phases["serve_whole_prompt"]["prefill_chunk"] is None
    assert phases["serve_chunked"]["prefill_chunk"] == 2


def test_plain_run_without_a_chip_exits_nonzero():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "platform: cpu" in r.stdout
    assert '"ok"' not in r.stdout  # no result line


# -- compiled for a described v5e ---------------------------------------------

@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip's mesh, or a skip that says why there is none.
    libtpu wants the slice's type and its workers' names from the
    environment where there is no metadata server to ask."""
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        for name, value in (("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                            ("TPU_WORKER_HOSTNAMES", "localhost"),
                            ("TPU_LOG_DIR", "disabled")):
            if name not in os.environ:
                env.setenv(name, value)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is another process's
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield Mesh(np.array(topo.devices[:1]), ("dp",))


def _entry_results(compiled_text: str):
    """(name, opcode, dtype, elements) of every instruction of a compiled
    program's ENTRY computation."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", compiled_text,
                      re.S | re.M).group(1)
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            name, dtype, dims, opcode = m.groups()
            yield name, opcode, dtype, int(np.prod(
                [int(d) for d in dims.split(",") if d]))


def test_decode_program_reads_stacked_cache_groups_where_they_lie(v5e):
    """The decode program of a description with two full and two window
    layers (8 slots of 16,384 rows, keys 4 x 192 and values 4 x 128 wide,
    rings of 256 rows 8 heads wide), compiled for the v5e: beside the
    in-place writes of the pool, no entry-level operation slices or copies a
    whole layer's rows, and the temporaries stay under ONE layer's smallest
    full group in bfloat16. The grouped contraction over a layer sliced out
    of a stacked group of two or more made both: a ``slice`` and a ``copy``
    ``bf16[1, 8, rows, W]`` a layer for keys and for values, 407 MB of
    temporaries at this size (PERF.md section 6, PR 41)."""
    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, MoESlotCache, init_params,
    )

    cfg = MoEServeConfig(
        vocab=1024, dim=512, n_layers=4, n_heads=16, n_kv_heads=4,
        head_dim=192, v_head_dim=128, rope_theta=5e6, norm_eps=1e-5,
        moe_experts=4, moe_topk=2, moe_ffn=256, capacity_factor=2.0,
        layer_kinds=("full", "window", "full", "window"), window=128,
        window_kv_heads=8, window_rope_theta=1e4, rotary_dim=64,
        value_scale=0.707, sink=("window",), gate="sigmoid_bias",
        param_dtype="bfloat16")
    slots, max_seq = 8, 16384
    srv = MoEServer(cfg, v5e)
    chip = NamedSharding(v5e, P())

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    placed = described(jax.eval_shape(
        lambda key: srv.shard_params(init_params(key, cfg)),
        jax.random.PRNGKey(0)))
    pool = described(jax.eval_shape(
        lambda: MoESlotCache.empty(cfg, 1, slots, max_seq)))

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    compiled = jax.jit(decode, donate_argnums=(3, 4, 5)).lower(
        placed, jax.ShapeDtypeStruct((1, slots), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((1, slots), jnp.bool_, sharding=chip),
        *pool).compile()
    # [W, L, B, rows, width] leaves: one layer's rows of the smallest group
    layer = min(int(np.prod(a.shape[2:]))
                for a in jax.tree.leaves((pool.k, pool.v)))
    moved = [(name, opcode, dtype, n)
             for name, opcode, dtype, n in _entry_results(compiled.as_text())
             if n >= layer and (opcode in ("slice", "copy")
                                or re.search("slice.*fusion", name))]
    assert not moved, moved
    full_v = slots * max_seq * cfg.kv_heads("full") * cfg.v_head_dim
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 2 * full_v, (temporaries, 2 * full_v)


def _computations(compiled_text: str) -> dict:
    """{name: body text} of every computation of a compiled program."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", compiled_text,
        re.S | re.M)}


@pytest.mark.parametrize("program,slots,rows", [
    ("decode", 4, None), ("decode", 16, None), ("prefill", 16, None),
    ("prefill", 16, 2), ("prefill", 16, 1)])
def test_slot_programs_advance_a_state_group_in_place(v5e, program, slots,
                                                      rows):
    """The decode and the pool-wide prefill program of a description whose
    layers keep a STATE with no position axis (two retention layers at
    Brumby's head sizes: 8 KV heads of 9,216 features x 128 a slot, 4 and 16
    slots), compiled for the v5e: each layer's state is its own array,
    replaced in the donated buffer it came in, so no operation copies a
    layer's states and the temporaries stay under ONE layer's. One stacked
    array a group made the compiler copy the whole group in and out (4.5 GB
    of temporaries at 8 layers x 16 slots: PERF.md section 6, PR 45).

    And the operator passes over the rows with a real position alone (PR
    46): each layer's state goes from the entry's parameter through ONE
    ``while`` (the row loop, its trip count read from the input) to the
    entry's result — no fusion of the entry computation makes a whole
    layer's ``[slots, 8, 9216, 128]``, which is what read and rewrote every
    slot's state whatever decoded — and the loop's body reads a row's state
    where it lies in the carried array and updates it there: no ``copy``,
    ``slice`` or ``dynamic-slice`` instruction of its own hands on a row's
    37.7 MB (in a fusion the slice is an address, not a pass). The compact
    two-row rung (``rows`` 2 of 16 slots) is held to the same: its rows'
    states are never gathered out of the layer's array, which the loop
    carries whole (gathered, the two rows' 75 MB were moved in and out of
    fast memory every turn of the loop). The compact one-row rung has no
    loop: its row is updated in the layer's array by one in-place
    operation of the entry computation."""
    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, MoESlotCache, init_params,
    )

    cfg = MoEServeConfig(
        vocab=1024, dim=512, n_layers=2, n_heads=40, n_kv_heads=8,
        head_dim=128, rope_theta=1e6, moe_experts=0, moe_topk=0, moe_ffn=0,
        layer_kinds=("retention",) * 2, qk_norm=True, first_k_dense=2,
        dense_ffn=1024, param_dtype="bfloat16")
    srv = MoEServer(cfg, v5e)
    chip = NamedSharding(v5e, P())

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    placed = described(jax.eval_shape(
        lambda key: srv.shard_params(init_params(key, cfg)),
        jax.random.PRNGKey(0)))

    def decode(p, tok, act, k, v, ln):
        return srv.decode_step_slots(p, tok, act, MoESlotCache(k, v, ln),
                                     impl="sort")

    def prefill(p, tok, lens, mask, k, v, ln, *named):
        return srv.prefill_slots(p, tok, lens, mask, MoESlotCache(k, v, ln),
                                 slots=named[0] if named else None)

    pool = described(jax.eval_shape(
        lambda: MoESlotCache.empty(cfg, 1, slots, 32768)))
    row = 8 * 9216 * 128  # one slot's S in one layer, numbers
    layer = slots * row * 4  # one layer's S of every slot, bytes
    n = rows or slots  # the call's rows
    assert [a.shape for a in pool.k["retention"]] \
        == [(1, slots, 8, 9216, 128)] * 2
    if program == "decode":
        compiled = jax.jit(decode, donate_argnums=(3, 4, 5)).lower(
            placed, arg((1, slots), jnp.int32),
            arg((1, slots), jnp.bool_), *pool).compile()
    else:  # [16 | 2 | 1, 128]: pool-wide, or compact over named slots
        compiled = jax.jit(prefill, donate_argnums=(4, 5, 6)).lower(
            placed, arg((1, n, 128), jnp.int32), arg((1, n), jnp.int32),
            arg((1, n), jnp.bool_), *pool,
            *([arg((1, n), jnp.int32)] if rows else [])).compile()
    text = compiled.as_text()
    entry = list(_entry_results(text))
    moved = [(name, opcode, dtype, n) for name, opcode, dtype, n in entry
             if n * 4 >= layer and opcode == "copy"]
    assert not moved, (program, moved)
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < layer, (program, temporaries, layer)
    # a whole layer's S is made by no operation of the entry computation:
    # it is a parameter, what a row loop carries, or a view of either
    computations = _computations(text)
    entry_name = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    whole = f"f32[{slots},8,9216,128]"
    views = ("parameter", "bitcast", "get-tuple-element", "tuple", "while",
             "opt-barrier")
    made = [line.strip()[:160]
            for line in computations[entry_name].splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(",
                              line))
            and whole in m.group(1) and m.group(2) not in views]
    loops = re.findall(r"while\([^\n]*body=%?([\w.\-]+)", text)
    if n == 1:  # one row: no loop, one in-place update a layer and array
        assert not loops and len(made) == 2 and all(
            "dynamic-update-slice" in line for line in made), (loops, made)
        return
    assert not made, (program, made)
    assert len(loops) == 2, loops  # one row loop a retention layer
    state = re.compile(r"f32\[(?:\d+,)?8,9216,128\]")
    for body in loops:
        assert state.search(computations[body]), body  # it carries S
        passes = [line.strip()[:160]
                  for line in computations[body].splitlines()
                  if (m := re.match(
                      r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line))
                  and re.match(r"(copy|slice|dynamic-slice)", m.group(2))
                  and state.search(m.group(1))]
        assert not passes, (program, body, passes)
