"""On-chip decomposition: isolated hot blocks of the flagship train step at
B=32 S=1024 shapes — expert GEMMs, attention core, vocab GEMM, and the MoE
dispatch machinery. (Full-step timing lives in bench.py, whose donated
state chains properly; this script answers "which block eats the step".)

Timing discipline (uccl_tpu/utils/timing.py):
  * CHAINED fori_loop — the carry perturbs the first array input each
    iteration, so the body is not loop-invariant (an unchained body gets
    hoisted out by XLA LICM and times an empty loop);
  * the output is consumed by a full reduction (sum), not a one-element
    read XLA could narrow/DCE through;
  * arrays are jit ARGUMENTS, not closures (constants of this size bloat
    the program);
  * sync via a host scalar read inside the timed region.

Run on the chip from the repo root:
  python scripts/onchip_profile.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from _timing import chained_timeit as timeit


def main():
    d = jax.devices()[0]
    assert d.platform == "tpu", d
    print(f"device: {d.device_kind}", flush=True)

    B, S, H, E, K, F, V = 32, 1024, 1024, 8, 2, 2816, 16384
    NH, KVH, HD = 16, 4, 64
    T = B * S
    cap = int(1.25 * T * K / E)
    rng = np.random.default_rng(0)

    x = jnp.asarray(rng.standard_normal((T, H)), jnp.bfloat16)
    wr = jnp.asarray(rng.standard_normal((H, E)) * 0.02, jnp.bfloat16)

    def dispatch_only(x, wr, c):
        logits = (x @ wr).astype(jnp.float32)
        gates, idx = lax.top_k(jax.nn.softmax(logits), K)
        flat_idx = idx.reshape(-1)
        order = jnp.argsort(flat_idx)
        ranked = jnp.take(x, order // K, axis=0)
        # position within expert via cumsum trick
        onehot = jax.nn.one_hot(flat_idx[order], E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot
        slot = jnp.max(pos, axis=1) - 1
        keep = slot < cap
        dst = jnp.where(keep, flat_idx[order] * cap + slot, E * cap)
        buf = jnp.zeros((E * cap + 1, H), jnp.bfloat16).at[dst].set(ranked)
        return c + buf.astype(jnp.float32).sum() * 1e-6 + gates.sum()

    timeit("moe dispatch machinery", dispatch_only, x, wr)

    w1 = jnp.asarray(rng.standard_normal((E, H, F)) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rng.standard_normal((E, F, H)) * 0.02, jnp.bfloat16)
    w3 = jnp.asarray(rng.standard_normal((E, H, F)) * 0.02, jnp.bfloat16)
    xb = jnp.asarray(rng.standard_normal((E, cap, H)), jnp.bfloat16)

    def expert_gemms(xb, w1, w2, w3, c):
        h1 = jnp.einsum("ech,ehf->ecf", xb, w1)
        h3 = jnp.einsum("ech,ehf->ecf", xb, w3)
        y = jnp.einsum("ecf,efh->ech", jax.nn.silu(h1) * h3, w2)
        return c + y.astype(jnp.float32).sum() * 1e-6

    # one layer's worth; flagship has 4
    t_eg = timeit("expert GEMMs (1 layer)", expert_gemms, xb, w1, w2, w3)

    q = jnp.asarray(rng.standard_normal((B, NH, S, HD)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KVH, S, HD)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, KVH, S, HD)), jnp.bfloat16)
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def attn_core(q, k, v, c):
        kk = jnp.repeat(k, NH // KVH, axis=1)
        vv = jnp.repeat(v, NH // KVH, axis=1)
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(HD)
        p = jax.nn.softmax(jnp.where(mask, s_.astype(jnp.float32), -1e30))
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), vv)
        return c + o.astype(jnp.float32).sum() * 1e-6

    t_at = timeit("attention core (1 layer)", attn_core, q, k, v)

    wv = jnp.asarray(rng.standard_normal((H, V)) * 0.02, jnp.bfloat16)

    def vocab_gemm(x, wv, c):
        return c + (x @ wv).astype(jnp.float32).sum() * 1e-6

    t_vg = timeit("vocab GEMM (fwd once)", vocab_gemm, x, wv)

    print("\nreconstruction (fwd): "
          f"4x experts {4 * t_eg * 1e3:.1f} + 4x attn {4 * t_at * 1e3:.1f} "
          f"+ vocab {t_vg * 1e3:.1f} ms", flush=True)


if __name__ == "__main__":
    main()
