"""Smoke proof for the Pallas EP all-to-all (wire="pallas") on any host.

EXECUTES the kernels under the TPU interpreter on a small virtual CPU mesh
and checks them against the lax wire — the fast fail-first gate for kernel
regressions on CPU runners (scripts/qa.sh and the GitHub workflow run it
with --chunks 2 under a hard timeout). Small shapes on purpose: the whole
smoke must finish in seconds-to-a-minute, not re-prove the full oracle suite
(tests/test_pallas_a2a.py does that). That the kernels LOWER for the TPU
backend is a tier-1 test (tests/test_tpu_bringup.py, cross-lowered from
the CPU); that they compile and run on chips is a chip run (PERF.md).

Prints one line per case; exits nonzero on any failure.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

W, T, H, E, K = 8, 128, 512, 16, 2
CAP = max(1, int(1.25 * T * K / E))


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--chunks", type=int, default=0,
        help="also prove the chunk-pipelined arms at this depth (0 = "
             "unchunked only)",
    )
    ap.add_argument(
        "--wire-dtype", default=None, choices=["fp8", "int8"],
        help="also prove the block-quantized wire (docs/QUANT_WIRE.md): "
             "quantized ring allreduce + EP roundtrip arms — checks "
             "pallas == lax bit-identity on the quantized "
             "path, the documented error bound vs full precision, and "
             "exact zeros on zero input",
    )
    ap.add_argument(
        "--metrics-out", default=None,
        help="dump the Prometheus counter registry here on exit (the "
             "quantized smoke's ep_bytes_total{...,wire_dtype} series — "
             "validated by scripts/check_obs.py --quant)",
    )
    return ap.parse_args(argv)


def _setup_interpret_env():
    """Must run BEFORE jax is imported: the smoke needs virtual devices."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def _interpret_smoke(chunks: int) -> int:
    """Execute small kernel cases under the TPU interpreter and compare to
    the lax wire — worlds 4 (even, real chunked kernels within the interp
    budget) and 5 (odd: pad path + antipodal step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from uccl_tpu.ep import ll as ep_ll
    from uccl_tpu.ep import ops as ep_ops
    from uccl_tpu.ep import pallas_a2a
    from jax import shard_map

    devs = jax.devices()
    rng = np.random.default_rng(0)
    depths = sorted({1, max(1, chunks)})
    failed = 0

    def run(mesh, fn, *args, out_specs=None):
        in_specs = tuple(P("x") for _ in args)
        out_specs = P("x") if out_specs is None else out_specs
        return jax.jit(
            shard_map(fn, mesh, in_specs, out_specs, check_vma=False)
        )(*args)

    def case(name, ok):
        nonlocal failed
        print(f"pallas_a2a_proof[interpret] {name}: "
              f"{'OK' if ok else 'MISMATCH'}")
        failed += 0 if ok else 1

    for n in (4, 5):
        mesh = Mesh(np.array(devs[:n]), ("x",))
        x = jnp.asarray(rng.normal(size=(n, n, 5, 9)), jnp.float32)
        want = np.asarray(run(
            mesh,
            lambda v: jax.lax.all_to_all(v[0], "x", 0, 0, tiled=True)[None],
            x,
        ))
        for nc in depths:
            got = np.asarray(run(
                mesh,
                lambda v, nc=nc: pallas_a2a.all_to_all(
                    v[0], "x", n_chunks=nc, chunk_axis=2
                )[None],
                x,
            ))
            case(f"kernel_w{n}_c{nc}", bool((got == want).all()))

        # one sorted dispatch+combine roundtrip and one LL fp8 roundtrip
        t, h, e, k = 8, 16, 2 * n, 2
        cap = max(1, int(1.25 * t * k / e))
        xs = rng.standard_normal((n, t, h)).astype(np.float32)
        idx = rng.integers(0, e, (n, t, k)).astype(np.int32)
        wts = rng.uniform(0.1, 1.0, (n, t, k)).astype(np.float32)

        def sorted_path(wire, nc):
            def f(xv, iv, wv):
                plan = ep_ops.plan_slots(iv[0], e, cap)
                recv = ep_ops.dispatch_sorted(
                    xv[0], plan, e, cap, "x", wire=wire, n_chunks=nc
                )
                return ep_ops.combine_sorted(
                    recv * 2.0, plan, wv[0], "x", wire=wire, n_chunks=nc
                )[None]

            return np.asarray(run(
                mesh, f, *map(jnp.asarray, (xs, idx, wts))
            ))

        ref = sorted_path("lax", 1)
        for nc in depths:
            case(f"sorted_w{n}_c{nc}",
                 bool((sorted_path("pallas", nc) == ref).all()))

        def ll_path(wire, nc):
            def f(xv, iv, wv):
                r = ep_ll.ll_dispatch(
                    xv[0], iv[0], wv[0], e, "x", wire=wire, wire_fp8=True,
                    n_chunks=nc,
                )
                return r.recv_x[None]

            return np.asarray(run(
                mesh, f, *map(jnp.asarray, (xs, idx, wts))
            ))

        ll_ref = ll_path("dense", 1)
        for nc in depths:
            case(f"ll_fp8_w{n}_c{nc}",
                 bool((ll_path("pallas", nc) == ll_ref).all()))
    return failed


def _interpret_quant_smoke(chunks: int, wire_dtype: str) -> int:
    """Quantized-wire smoke (--wire-dtype): the pallas ring allreduce and
    the sorted EP roundtrip at worlds 4 and 5, asserting (1) the quantized
    pallas path is bit-identical to the quantized lax path (same shared
    codec either wire), (2) error vs full precision sits inside the
    documented per-hop bound (docs/QUANT_WIRE.md), (3) an all-zero payload
    round-trips to EXACT zeros (the codec's scale-guard contract)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from uccl_tpu.collective import pallas_ccl
    from uccl_tpu.ep import ops as ep_ops
    from jax import shard_map

    devs = jax.devices()
    rng = np.random.default_rng(0)
    depths = sorted({1, max(1, chunks)})
    failed = 0
    # two quantize round trips (dispatch + combine, or RS hops + AG) of
    # rel error: half-ulp/QMAX per trip, with headroom for summation
    rel_bound = {"fp8": 0.12, "int8": 0.02}[wire_dtype]

    def case(name, ok):
        nonlocal failed
        print(f"pallas_a2a_proof[interpret,{wire_dtype}] {name}: "
              f"{'OK' if ok else 'MISMATCH'}")
        failed += 0 if ok else 1

    for n in (4, 5):
        mesh = Mesh(np.array(devs[:n]), ("x",))

        def run(fn, *args, n_in=None):
            n_in = len(args) if n_in is None else n_in
            return np.asarray(jax.jit(shard_map(
                fn, mesh, tuple(P("x") for _ in range(n_in)), P("x"),
                check_vma=False,
            ))(*args))

        # -- quantized ring allreduce ---------------------------------
        x = jnp.asarray(rng.normal(size=(n, 3, 200)), jnp.float32)

        def ar(v, wd=None):
            return pallas_ccl.ring_all_reduce(
                v[0], "x", wire_dtype=wd)[None]

        want = run(lambda v: ar(v), x)
        got = run(lambda v: ar(v, wire_dtype), x)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-12)
        case(f"ring_ar_w{n}_err({err:.2e})", bool(err < rel_bound))
        zero = run(lambda v: ar(v, wire_dtype),
                   jnp.zeros((n, 3, 200), jnp.float32))
        case(f"ring_ar_w{n}_zero_exact", bool((zero == 0.0).all()))

        # -- quantized sorted EP roundtrip ----------------------------
        t, h, e, k = 8, 64, 2 * n, 2
        cap = max(1, int(1.25 * t * k / e))
        xs = jnp.asarray(rng.standard_normal((n, t, h)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, e, (n, t, k)), jnp.int32)
        wts = jnp.asarray(rng.uniform(0.1, 1.0, (n, t, k)), jnp.float32)

        def sorted_path(wire, nc, wd):
            def f(xv, iv, wv):
                plan = ep_ops.plan_slots(iv[0], e, cap)
                recv = ep_ops.dispatch_sorted(
                    xv[0], plan, e, cap, "x", wire=wire, n_chunks=nc,
                    wire_dtype=wd,
                )
                return ep_ops.combine_sorted(
                    recv, plan, wv[0], "x", wire=wire, n_chunks=nc,
                    wire_dtype=wd,
                )[None]

            return run(f, xs, idx, wts)

        ref = sorted_path("lax", 1, None)
        lax_q = sorted_path("lax", 1, wire_dtype)
        err = np.abs(lax_q - ref).max() / (np.abs(ref).max() + 1e-12)
        case(f"sorted_w{n}_err({err:.2e})", bool(err < rel_bound))
        for nc in depths:
            case(f"sorted_w{n}_c{nc}_pallas_eq_lax",
                 bool((sorted_path("pallas", nc, wire_dtype)
                       == lax_q).all()))
    return failed


def main():
    args = _parse_args()
    _setup_interpret_env()
    if args.wire_dtype:
        failed = _interpret_quant_smoke(args.chunks, args.wire_dtype)
    else:
        failed = _interpret_smoke(args.chunks)
    if args.metrics_out:
        from uccl_tpu import obs

        obs.write_metrics(args.metrics_out)
        print(f"pallas_a2a_proof: metrics -> {args.metrics_out}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
