"""Chained-fori_loop timing harnesses in ONE place, shared by the probe
scripts (via scripts/_timing) and the benchmarks:

  * the loop body must be CHAINED to the carry — a body whose inputs are
    all loop-invariant is hoisted out by XLA's LICM and the loop times
    nothing (measured: "fwd+bwd" 1.6 ms < fwd 3.4 ms);
  * consume outputs with a full reduction, never a one-element read that
    XLA can narrow/DCE through (measured: flattered XLA attention 3x vs
    the un-trimmable pallas kernel);
  * pass arrays as jit ARGUMENTS, not closures — baked-in constants bloat
    the program and re-trace on every new value;
  * sync inside the timed region — dispatch is asynchronous; a host scalar
    read of the result (used here) or block_until_ready both wait for the
    device.

Two estimators:
  chained_timeit — per-iteration time of fn(a0, *rest, c) -> carry; use
    for ms-scale probes where one dispatch's fixed cost amortizes away.
  slope_timeit — per-op = (t(base+n) - t(base)) / n over a pytree of
    args; the differencing cancels the fixed dispatch + host-read cost
    exactly, which µs-scale ops need (a per-call loop measures its own
    dispatch floor).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax


def perturb(a, c):
    """Couple array `a` to the carry so the loop body is not hoistable.
    Float: + c*1e-12 (negligible). Int: + min(|c|, 0) cast — PROVABLY zero
    for any carry value, yet data-dependent, so values are bit-unchanged
    and XLA still cannot prove loop invariance. (The earlier min(c, 0)
    coupling assumed a non-negative carry; a slope carry that drifts
    negative — reductions of signed outputs do — silently mutated every
    int leaf it touched.)"""
    if jnp.issubdtype(a.dtype, jnp.floating):
        return a + (c * 1e-12).astype(a.dtype)
    return a + jnp.minimum(jnp.abs(c), 0.0).astype(a.dtype)


def chained_timeit(name, fn, *args, iters=10, flops=None, width=34):
    """Time fn over `iters` chained iterations in ONE jitted dispatch.
    fn(a0, *rest, c) -> new carry scalar; a0 is perturbed by the carry.
    Returns seconds per iteration; prints `name`, ms, and TF/s if `flops`
    (per-iteration FLOPs) is given."""
    def body(i, state):
        c, arrs = state
        return fn(perturb(arrs[0], c), *arrs[1:], c), arrs

    f = jax.jit(lambda n, c0, *a: lax.fori_loop(0, n, body, (c0, a)))
    c0 = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    float(f(2, c0, *args)[0])  # compile + warm
    tc = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(f(iters, c0, *args)[0])
    dt = (time.perf_counter() - t0) / iters
    tf = f"  {flops / dt / 1e12:6.1f} TF/s" if flops else ""
    print(f"{name:{width}s} {dt * 1e3:8.3f} ms{tf}  (compile {tc:.0f}s)",
          flush=True)
    return dt


def slope_timeit(fn, args, iters, signal_floor=0.02, n_cap=20000):
    """Per-op seconds for fn(*args) via the SLOPE of two chained fori_loop
    runs: (t(base+n) - t(base)) / n, median of 3 pairs. The first leaf of
    `args` (float or int — see perturb) is carry-coupled each iteration;
    every output leaf is consumed by a full reduction. n escalates ×10
    until the differenced signal (slope × n) clears `signal_floor`
    seconds or n reaches `n_cap` — µs-scale ops need thousands of chained
    iterations to rise above run-to-run jitter."""
    flat, treedef = jax.tree.flatten(tuple(args))
    pi = next(
        (i for i, l in enumerate(flat) if hasattr(l, "dtype")), None
    )

    def body(i, state):
        c, leaves = state
        leaves = list(leaves)
        if pi is not None:
            leaves[pi] = perturb(leaves[pi], c)
        out = fn(*jax.tree.unflatten(treedef, leaves))
        s = sum(
            l.astype(jnp.float32).sum()
            for l in jax.tree.leaves(out)
            if hasattr(l, "astype")
        )
        return c + s * 1e-9, tuple(state[1])

    run = jax.jit(
        lambda n, c0, leaves: lax.fori_loop(0, n, body, (c0, leaves))
    )
    c0 = jnp.zeros((), jnp.float32)
    leaves = tuple(flat)
    float(run(2, c0, leaves)[0])  # compile + warm, host-scalar sync

    def timed(n):
        t0 = time.perf_counter()
        float(run(n, c0, leaves)[0])
        return time.perf_counter() - t0

    base, n = 3, max(1, iters)
    while True:
        slopes = sorted(
            (timed(base + n) - timed(base)) / n for _ in range(3)
        )
        if slopes[1] * n > signal_floor or n >= n_cap:
            break
        n = min(n * 10, n_cap)
    return max(slopes[1], 1e-9)  # clamp: noise can make a tiny op negative
