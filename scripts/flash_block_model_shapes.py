"""On-chip: flash block-size sweep at FLAGSHIP shapes (B=16/32, NH=16/KV=4,
S=1024, D=64), fwd and fwd+bwd, vs the XLA attention core.

Timing discipline: iterations are CHAINED (each step's outputs become the
next step's inputs) inside one jitted fori_loop — a loop whose body reads
only loop-invariant inputs gets hoisted out by XLA (LICM) and times an
empty loop; measured here as impossible numbers (fwd+bwd < fwd) before
the chain was added. Sync via a host scalar read inside the timed region.
This script chains FULL tensor state
(outputs feed next inputs) rather than the carry-perturb scheme of
scripts/_timing.chained_timeit — both encode the same discipline; use
the shared helper for scalar-carry probes."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def timeit(name, step, state, iters=20):
    """step(state) -> state (same pytree structure, chained)."""
    run = jax.jit(lambda s, n: lax.fori_loop(0, n, lambda _, t: step(t), s))
    s = run(state, 2)
    float(jax.tree_util.tree_leaves(s)[0].ravel()[0])  # compile+warm sync
    t0 = time.perf_counter()
    s = run(s, iters)
    float(jax.tree_util.tree_leaves(s)[0].ravel()[0])
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:40s} {dt * 1e3:8.3f} ms", flush=True)
    return dt


def main():
    from uccl_tpu.ops.attention import attention_reference
    from uccl_tpu.ops.pallas_attention import flash_attention

    d = jax.devices()[0]
    print(f"device: {d.platform} {d.device_kind}", flush=True)
    B = int(os.environ.get("FB_BATCH", "16"))
    S = int(os.environ.get("FB_SEQ", "1024"))
    NH, KVH, HD = 16, 4, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, NH, HD)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, HD)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, HD)), jnp.bfloat16)

    def chain_fwd(attn):
        # out [B,S,NH,D] feeds the next q; k/v nudged so nothing is invariant
        def step(s):
            q, k, v = s
            o = attn(q, k, v)
            bump = o[:, :1, :1, :1].mean().astype(k.dtype)
            return o.astype(q.dtype), k + bump, v - bump
        return step

    def chain_fwdbwd(attn):
        def step(s):
            q, k, v = s

            def loss(q, k, v):
                return attn(q, k, v).astype(jnp.float32).sum()

            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            # grads have the exact input shapes: perfect chain carriers
            # (tiny scale keeps value drift negligible over the loop; a
            # *0 scale would let XLA DCE that grad entirely)
            return (q + g[0].astype(q.dtype) * 1e-6,
                    k + g[1].astype(k.dtype) * 1e-6,
                    v + g[2].astype(v.dtype) * 1e-6)
        return step

    def try_timeit(name, step, state):
        try:
            return timeit(name, step, state)
        except Exception as e:  # noqa: BLE001 - probe continues past OOM
            print(f"{name:40s} FAILED {type(e).__name__}: {str(e)[:120]}",
                  flush=True)
            return None

    xla = lambda q, k, v: attention_reference(q, k, v, causal=True)
    try_timeit("xla fwd", chain_fwd(xla), (q, k, v))
    try_timeit("xla fwd+bwd", chain_fwdbwd(xla), (q, k, v))

    for blk in (128, 256, 512, 1024):
        fl = lambda q, k, v, blk=blk: flash_attention(q, k, v, True, blk, blk)
        try_timeit(f"flash bq=bk={blk} fwd", chain_fwd(fl), (q, k, v))
        try_timeit(f"flash bq=bk={blk} fwd+bwd", chain_fwdbwd(fl), (q, k, v))


if __name__ == "__main__":
    main()
