"""What guards the chip path from the CPU: the Pallas kernels cross-lowered
for the TPU, chip_smoke.py's phases at a tiny size on a CPU mesh, and its
refusal to run without a chip.

Cross-lowering (``lower(lowering_platforms=("tpu",))``) is the free
pre-flight before chip time: tracing, block specs, scratch shapes, semaphore
plumbing and the Pallas→Mosaic-MLIR stage all run here, and the kernels must
come out as ``tpu_custom_call``s. What only a chip can say — Mosaic's
backend compile (VMEM limit, tiling) and execution — is chip_smoke.py's and
the four-chip session's (PERF.md).

The tiny size for chip_smoke's phases is chosen HERE, explicitly — the
script has no small mode and never picks a size from the absence of a chip.
Those two tests run it in a subprocess: its entry points read
``jax.devices()``, and two virtual devices keep the compile cost down.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

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
