"""Flash-attention microbenchmark: Pallas kernels vs stock XLA attention.

Measures forward and forward+backward wall time and model TFLOP/s on the
local accelerator at a sweep of sequence lengths, causal, GQA-shaped.
This is the recorded evidence VERDICT round-1 item 2 asked for (the
reference's measured-wins culture: README.md:29, ep/bench/test_low_latency.py
metric definitions — report numbers, not vibes).

FLOP accounting (matmuls only): causal attention does ~half the score work,
so fwd = 2 * 2 * B*H*S^2*D * 0.5 (qk^T + p@v), bwd = 2.5x fwd (dq/dk/dv
recompute from LSE included for the flash path so both paths are charged the
same model FLOPs — utilization, not kernel-internal work).

Usage: python benchmarks/attention_bench.py [--seqs 1024,2048,4096,8192]
"""

from __future__ import annotations

import argparse
import functools
import json
import time

from _bootstrap import init_devices


def _ref_attention(q, k, v, causal=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, s, h, d = q.shape
    n_rep = h // k.shape[2]
    kk = jnp.repeat(k, n_rep, axis=2)
    vv = jnp.repeat(v, n_rep, axis=2)
    scores = (
        jnp.einsum(
            "bqhd,bkhd->bhqk",
            q.astype(jnp.float32),
            kk.astype(jnp.float32),
        )
        / np.sqrt(d)
    )
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv.astype(jnp.float32)).astype(
        q.dtype
    )


def _time(step, q, k, v, iters=10, warmup=2):
    """Time `step(q, k, v) -> (q', k', v')` by running `iters` chained
    repetitions inside ONE jitted `lax.fori_loop` dispatch, then forcing a
    host scalar read: the dependency chain keeps XLA from hoisting the body,
    the host read waits for the device, and one dispatch for the whole
    measurement keeps per-dispatch overhead out of a ms-scale kernel time."""
    import jax
    from jax import lax
    import jax.numpy as jnp

    @jax.jit
    def run(state, n):
        return lax.fori_loop(0, n, lambda _, s: step(*s), state)

    state = run((q, k, v), warmup)
    float(jnp.sum(state[0][0, 0, 0]))  # sync the warmup/compile
    t0 = time.perf_counter()
    state = run(state, iters)
    float(jnp.sum(state[0][0, 0, 0]))
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    # Defaults mirror the FLAGSHIP head geometry (B=8, NH=16, KV=4,
    # D=64): the old tiny defaults (B=2, H=8) under-utilized the chip and
    # produced a flash-vs-XLA crossover that did not transfer to the
    # model (PERF.md round-5 "Harness lesson")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--seqs", default="1024,2048,4096,8192")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--block-sweep", action="store_true",
        help="sweep flash (block_q, block_k) tiles at each seq and print "
        "the fastest — the on-chip tuning pass (VERDICT: tune blocks at "
        "long sequence until flash beats XLA in its claimed regime)",
    )
    ap.add_argument(
        "--blocks", default="128,256,512,1024",
        help="candidate tile sizes for --block-sweep (1024 is the "
        "measured v5e optimum at head_dim 64)",
    )
    ap.add_argument(
        "--skip-xla-bwd-at",
        type=int,
        default=16384,
        help="skip the XLA fwd+bwd datapoint at/above this seq (it "
        "materializes [S,S] and OOMs / thrashes first)",
    )
    args = ap.parse_args()

    jax = init_devices(args.devices)
    import jax.numpy as jnp

    from uccl_tpu.ops.pallas_attention import flash_attention

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")
    b, h, kv, d = args.batch, args.heads, args.kv_heads, args.head_dim

    rows = []
    for s in [int(x) for x in args.seqs.split(",")]:
        q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d), jnp.bfloat16)
        fwd_flops = 2 * 2 * b * h * s * s * d * 0.5

        # Each step folds the measured op's output back into q (tiny scaled
        # add — negligible next to attention) so iterations form an on-device
        # dependency chain; see _time.
        def _chain_fwd(attn):
            def step(q, k, v):
                return q + 1e-6 * attn(q, k, v).astype(q.dtype), k, v
            return jax.jit(step)

        def _chain_bwd(attn):
            # grad wrt all three — grad-wrt-q-only would let XLA dead-code
            # the dk/dv kernel and we'd time half the backward.
            def step(q, k, v):
                def loss(q_, k_, v_):
                    o = attn(q_, k_, v_)
                    return jnp.sum(o.astype(jnp.float32) ** 2)
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                return (
                    q + 1e-9 * dq.astype(q.dtype),
                    k + 1e-9 * dk.astype(k.dtype),
                    v + 1e-9 * dv.astype(v.dtype),
                )
            return jax.jit(step)

        if args.block_sweep:
            cands = [int(x) for x in args.blocks.split(",")]
            best = None
            for bq in cands:
                for bk in cands:
                    # a tile larger than S would silently clamp inside the
                    # kernel and re-measure (S, S) under a wrong label
                    if bq > s or bk > s or s % bq or s % bk:
                        continue
                    fn = _chain_fwd(functools.partial(
                        flash_attention, causal=True, block_q=bq, block_k=bk
                    ))
                    try:
                        dt = _time(fn, q, k, v, iters=args.iters)
                    except Exception as e:
                        print(json.dumps({"seq": s, "bq": bq, "bk": bk,
                                          "error": type(e).__name__}))
                        continue
                    rec = {"seq": s, "bq": bq, "bk": bk,
                           "ms": round(dt * 1e3, 3),
                           "tflops": round(fwd_flops / dt / 1e12, 2)}
                    print(json.dumps(rec))
                    if best is None or dt < best[0]:
                        best = (dt, rec)
            if best:
                print(json.dumps({"seq": s, "best": best[1]}))
            continue

        flash = _chain_fwd(functools.partial(flash_attention, causal=True))
        xla = _chain_fwd(functools.partial(_ref_attention, causal=True))
        g_flash = _chain_bwd(functools.partial(flash_attention, causal=True))
        g_xla = _chain_bwd(functools.partial(_ref_attention, causal=True))

        row = {"seq": s}
        for name, fn, flops in (
            ("flash_fwd", flash, fwd_flops),
            ("xla_fwd", xla, fwd_flops),
            ("flash_fwdbwd", g_flash, 3.5 * fwd_flops),
            ("xla_fwdbwd", g_xla, 3.5 * fwd_flops),
        ):
            if name == "xla_fwdbwd" and s >= args.skip_xla_bwd_at:
                row[name] = None
                continue
            try:
                dt = _time(fn, q, k, v, iters=args.iters)
            except Exception as e:  # OOM at long seq is a *result* here
                print(f"  {name} s={s}: {type(e).__name__}")
                row[name] = None
                continue
            row[name] = {"ms": round(dt * 1e3, 3), "tflops": round(flops / dt / 1e12, 2)}
        for k2 in ("fwd", "fwdbwd"):
            fr, xr = row.get(f"flash_{k2}"), row.get(f"xla_{k2}")
            if fr and xr:
                row[f"speedup_{k2}"] = round(xr["ms"] / fr["ms"], 3)
        rows.append(row)
        print(json.dumps(row))

    print(json.dumps({"device": f"{dev.platform} {getattr(dev, 'device_kind', '?')}", "rows": rows}))


if __name__ == "__main__":
    main()
