"""AllReduce performance sweep — the nccl-tests ``all_reduce_perf`` analog.

The reference's acceptance benchmark is nccl-tests' all_reduce_perf driven over
the UCCL plugin (collective/rdma/run_nccl_test.sh, SURVEY.md §4.5); this sweeps
message sizes over the mesh and prints alg/bus bandwidth per size for both the
XLA-scheduled and the explicit chunk-ring allreduce.

Bus bandwidth uses the standard ring factor 2*(n-1)/n over the data size.

Usage: python benchmarks/all_reduce_perf.py [--devices N] [--algo xla|ring|both]
On a machine without multiple accelerators, pass --devices N to use N virtual
CPU devices.

``--wire-dtype fp8,int8`` adds the quantized-wire arms (pallas ring,
``wire_dtype=`` — docs/QUANT_WIRE.md): per size it prints one JSON line per
arm with the per-shard wire bytes read off the REAL
``ep_bytes_total{verb="ring_all_reduce",...,wire_dtype}`` counter delta
(quantized payload + scale sidecar, counted at trace time by the rings
themselves — never mirrored arithmetic), the effective per-member wire
bandwidth those bytes imply, the wire-byte reduction vs the full-precision
arm, and the max-abs/rel error vs the full-precision result.

``--json`` switches the algo sweep to one ``all_reduce_plan`` JSON line
per size: every arm labeled off the REAL ``collective_plan_total`` counter
delta around its compile (the planner's decision, never the CLI arg
mirrored back) with the cost model's ``modeled_us`` (read off the
``collective_plan_predicted_us`` gauge the planner set) beside the
measured time — the record ``scripts/plan_calibrate.py`` refits the
alpha/beta/gamma constants from. ``--check`` makes every arm's result an
oracle assertion against an independent numpy sum (exit nonzero on mismatch) — the CI
planner smoke rides this. ``--metrics-out`` dumps the Prometheus
snapshot (``scripts/check_obs.py --plan`` validates the plan series
against the emitted JSON); ``--trace-out`` records the ``collective_plan``
decision instants.
"""

from __future__ import annotations

import argparse
import json
import time

from _bootstrap import init_devices


def _ring_bytes_snapshot():
    from uccl_tpu.obs import counters as obsc

    fam = obsc.counter("ep_bytes_total")
    return {tuple(sorted(lb.items())): v for lb, v in fam.samples()
            if lb.get("verb") == "ring_all_reduce"}


def _plan_snapshot():
    from uccl_tpu.obs import counters as obsc

    fam = obsc.counter("collective_plan_total")
    return {tuple(sorted(lb.items())): v for lb, v in fam.samples()}


def _planned_label(before, verb=None):
    """The plan decision an arm ACTUALLY emitted (counter delta around its
    compile) — the real label, never the CLI arg mirrored back. A
    ``fallback`` delta (the planned kernel degraded to its lax mirror at
    trace time) wins over the decision delta: the arm's timings are the
    mirror's, and plan_calibrate must be able to exclude them. Otherwise
    the largest plan delta; None if nothing moved. ``verb`` restricts to
    one verb's series (broadcast/all_gather carry a verb= label; the
    allreduce series has none — verb=None)."""
    deltas = []
    for k, v in _plan_snapshot().items():
        d = v - before.get(k, 0)
        lb = dict(k)
        if d > 0 and lb.get("algo") != "ep_a2a" and lb.get("verb") == verb:
            deltas.append((d, lb))
    if not deltas:
        return None
    for _, lb in deltas:
        if lb.get("outcome") == "fallback":
            return lb
    return max(deltas, key=lambda t: t[0])[1]


def _modeled_us(label):
    """The cost model's prediction for a plan label, read off the gauge the
    planner set at decision time (shared arithmetic, not mirrored)."""
    from uccl_tpu.obs import counters as obsc

    extra = {"verb": label["verb"]} if label.get("verb") else {}
    return obsc.gauge("collective_plan_predicted_us").get(
        algo=label["algo"], chunks=label["chunks"],
        wire_dtype=label["wire_dtype"], **extra,
    )


def _ring_bytes_delta(before):
    out = {}
    for kk, v in _ring_bytes_snapshot().items():
        d = v - before.get(kk, 0)
        if d > 0:
            out[dict(kk)["wire_dtype"]] = out.get(
                dict(kk)["wire_dtype"], 0) + int(d)
    return out


def quant_sweep(jax, n, wire_dtypes, args):
    """Quantized-wire arms: per (size, wire_dtype) one JSON line — wire
    bytes off the counter delta around the compiling call, effective
    per-member wire bandwidth, wire-byte reduction and error vs the
    full-precision pallas arm."""
    import numpy as np
    from jax.sharding import Mesh

    from uccl_tpu import obs
    from uccl_tpu.collective import Communicator

    # 1-axis mesh — same choice as ep_bench's pallas arm
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    comm = Communicator(mesh, "dp")

    size = args.min_bytes
    while size <= args.max_bytes:
        elems = size // 4
        x = comm.device_put(
            np.random.default_rng(0)
            .standard_normal((n, elems))
            .astype(np.float32)
        )
        arms = []
        ref = None
        ref_bytes = None
        for wd in [None] + list(wire_dtypes):
            before = _ring_bytes_snapshot()
            out = comm.all_reduce(x, algo="pallas", wire_dtype=wd)
            got = np.asarray(out)  # compile + host sync
            wire_bytes = _ring_bytes_delta(before).get(wd or "none", 0)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = comm.all_reduce(x, algo="pallas", wire_dtype=wd)
            np.asarray(out)
            dt = (time.perf_counter() - t0) / args.iters
            if wd is None:
                ref, ref_bytes = got, wire_bytes
                err_abs = err_rel = 0.0
            else:
                err_abs = float(np.abs(got - ref).max())
                err_rel = float(err_abs / (np.abs(ref).max() + 1e-12))
            arms.append({
                "wire_dtype": wd or "none",
                "time_us": round(dt * 1e6, 1),
                "wire_bytes_per_shard": wire_bytes,
                "wire_gbps_per_member": round(wire_bytes / dt / 1e9, 3),
                "wire_byte_reduction": round(
                    ref_bytes / wire_bytes, 2) if wire_bytes else None,
                "max_abs_err": err_abs,
                "max_rel_err": err_rel,
            })
        print(json.dumps({
            "bench": "all_reduce_quant",
            "schema_version": obs.SCHEMA_VERSION,
            "bytes": size, "world": n,
            "substrate": jax.default_backend(),
            "arms": arms,
        }))
        size *= 4


def _bcast_bytes_snapshot():
    from uccl_tpu.obs import counters as obsc

    fam = obsc.counter("ep_bytes_total")
    return {tuple(sorted(lb.items())): v for lb, v in fam.samples()
            if lb.get("verb") == "bcast"}


def verb_sweep(jax, n, verb, args):
    """The --bench bcast|ag arms: per size one ``collective_plan`` JSON
    line whose arms are labeled off the REAL
    ``collective_plan_total{verb=...}`` counter delta (the new verbs'
    decisions — docs/PLAN_BENCH.md round-9) with the gauge-read
    modeled_us beside the measured time; broadcast arms additionally
    carry the counter-audited per-member wire bytes (``ep_bytes_total
    {verb="bcast"}`` delta) so the psum-baseline reduction is a recorded
    counter fact. ``--check`` asserts every arm bit-exact against the
    root row / input (broadcast and all-gather are pure data movement at
    full precision)."""
    import numpy as np
    from jax.sharding import Mesh

    from uccl_tpu import obs
    from uccl_tpu.collective import Communicator

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    comm = Communicator(mesh, "dp")
    algos = (["psum", "xla", "tree", "scatter_ag", "auto"]
             if verb == "bcast" else ["xla", "ring", "bidir", "auto"])
    plan_verb = "broadcast" if verb == "bcast" else "all_gather"
    root = 1 % n
    failed = 0

    size = args.min_bytes
    while size <= args.max_bytes:
        elems = size // 4
        x = np.random.default_rng(0).standard_normal(
            (n, elems)).astype(np.float32)
        gx = comm.device_put(x)
        ref = np.tile(x[root], (n, 1)) if verb == "bcast" else x
        arms = []
        for algo in algos:
            before = _plan_snapshot()
            bbytes = _bcast_bytes_snapshot() if verb == "bcast" else {}
            if verb == "bcast":
                out = comm.broadcast(gx, root, algo=algo)
            else:
                out = comm.all_gather(gx, algo=algo)
            got = np.asarray(out)  # compile + host sync
            label = _planned_label(before, plan_verb) or {
                "algo": algo, "chunks": "1", "wire_dtype": "none",
                "verb": plan_verb}
            wire_delta = None
            if verb == "bcast":
                wire_delta = sum(
                    int(v - bbytes.get(k, 0))
                    for k, v in _bcast_bytes_snapshot().items()
                    if v - bbytes.get(k, 0) > 0
                ) or None
            t0 = time.perf_counter()
            for _ in range(args.iters):
                if verb == "bcast":
                    out = comm.broadcast(gx, root, algo=algo)
                else:
                    out = comm.all_gather(gx, algo=algo)
            np.asarray(out)
            dt = (time.perf_counter() - t0) / args.iters
            ok = bool(np.array_equal(got, ref))
            if args.check and not ok:
                print(f"all_reduce_perf: CHECK FAILED {verb}/{algo} @ "
                      f"{size}B (planned {label['algo']})", flush=True)
                failed = 1
            arms.append({
                "requested": algo,
                "algo": label["algo"],
                "chunks": int(label["chunks"]),
                "outcome": label.get("outcome", "explicit"),
                "time_us": round(dt * 1e6, 1),
                "modeled_us": round(_modeled_us(label), 2),
                "wire_bytes_per_member": wire_delta,
                "oracle_ok": ok,
            })
        print(json.dumps({
            "bench": "collective_plan",
            "verb": plan_verb,
            "schema_version": obs.SCHEMA_VERSION,
            "bytes": size, "world": n, "root": root, "n_axes": 1,
            "mesh2d": None,
            "substrate": jax.default_backend(),
            "arms": arms,
        }), flush=True)
        size *= 4
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual CPU devices (0 = use real devices)")
    ap.add_argument(
        "--algo", default="both",
        choices=["xla", "ring", "hd", "torus", "pallas", "bidir", "auto",
                 "both", "all"]
    )
    ap.add_argument(
        "--mesh2d", default="", metavar="AxB",
        help="use a 2D mesh (e.g. 2x4) — enables the torus algo",
    )
    ap.add_argument("--min-bytes", type=int, default=1 << 12)
    ap.add_argument("--max-bytes", type=int, default=1 << 26)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--wire-dtype", default="",
        help="comma list of quantized pallas-ring arms to sweep "
             "(e.g. 'fp8,int8'): JSON line per size with counter-derived "
             "wire bytes, effective bandwidth, and error vs full precision",
    )
    ap.add_argument("--json", action="store_true",
                    help="emit one all_reduce_plan JSON line per size: arms "
                         "labeled off the real collective_plan_total delta "
                         "with modeled_us beside measured (the record "
                         "plan_calibrate.py refits from)")
    ap.add_argument("--bench", default="ar",
                    help="comma list of verbs to sweep: ar (the allreduce "
                         "sweep, default) and/or bcast,ag — the broadcast/"
                         "all-gather arms emit collective_plan JSON lines "
                         "labeled off the verb-labeled plan counter "
                         "(plan_calibrate.py fits the new verbs from them)")
    ap.add_argument("--check", action="store_true",
                    help="oracle mode: every arm must match the numpy sum oracle "
                         "(exit nonzero on mismatch) — the planner smoke")
    from uccl_tpu import obs  # safe pre-device-forcing: jax-free surfaces

    obs.add_cli_args(ap)
    args = ap.parse_args()

    jax = init_devices(args.devices)

    import numpy as np

    from uccl_tpu.collective import Communicator
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    obs.setup_from_args(args)

    n = len(jax.devices())
    benches = [b for b in args.bench.split(",") if b]
    for b in benches:
        if b not in ("ar", "bcast", "ag"):
            ap.error(f"unknown --bench verb {b!r} (want ar/bcast/ag)")
    if benches != ["ar"]:
        if args.mesh2d or args.wire_dtype:
            ap.error("--bench bcast/ag rides the single-axis sweep; drop "
                     "--mesh2d/--wire-dtype")
        failed = 0
        for b in benches:
            if b == "ar":
                ap.error("--bench ar composes with bcast/ag only when "
                         "listed alone (the ar sweep has its own flags)")
            failed |= verb_sweep(jax, n, b, args)
        obs.dump_from_args(args)
        if failed:
            raise SystemExit(failed)
        return
    if args.wire_dtype:
        # quant_sweep builds its own raw single-axis mesh — dispatch
        # before constructing one here
        if args.mesh2d:
            ap.error("--wire-dtype rides the single-axis pallas ring; "
                     "drop --mesh2d")
        wire_dtypes = [w for w in args.wire_dtype.split(",") if w]
        for w in wire_dtypes:
            if w not in ("fp8", "int8"):
                ap.error(f"unknown --wire-dtype arm {w!r} (want fp8/int8)")
        quant_sweep(jax, n, wire_dtypes, args)
        obs.dump_from_args(args)
        return
    if args.mesh2d:
        a, b = (int(v) for v in args.mesh2d.lower().split("x"))
        assert a * b == n, f"mesh {a}x{b} != {n} devices"
        mesh = make_mesh(MeshConfig(dp=a, tp=b))
        comm = Communicator(mesh, ("dp", "tp"))
    else:
        # raw single-axis mesh: the same choice as quant_sweep (the ring
        # kernels drive one named axis), so auto may plan pallas/bidir
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()), ("dp",))
        comm = Communicator(mesh, "dp")

    if args.algo == "both":
        algos = ["xla", "ring"]
    elif args.algo == "all":
        algos = ["xla", "ring", "hd", "pallas", "bidir", "auto"] + (
            ["torus"] if args.mesh2d else [])
    else:
        algos = [args.algo]

    failed = 0
    if not args.json:
        print(f"# all_reduce_perf  world={n}  "
              f"devices={jax.devices()[0].platform}")
        print(f"# {'bytes':>12} {'algo':>8} {'planned':>8} {'time_us':>10}"
              f" {'model_us':>10} {'algbw_GB/s':>10} {'busbw_GB/s':>10}")
    size = args.min_bytes
    while size <= args.max_bytes:
        elems = size // 4
        x = comm.device_put(
            np.random.default_rng(0).standard_normal((n, elems)).astype(np.float32)
        )
        # the --check oracle: an independent numpy sum, NOT comm.all_reduce
        # — the comm memoizes plan resolutions per request, so going through
        # it here would consume the xla arm's counter delta before the arm
        # could label itself off it
        ref = np.tile(np.asarray(x).sum(0), (n, 1))
        arms = []
        for algo in algos:
            if algo == "hd" and n & (n - 1):
                # hd falls back to ring off power-of-two worlds; skip rather
                # than record ring timings under the hd label
                continue
            if algo in ("pallas", "bidir") and args.mesh2d:
                continue  # the ring kernels drive a single mesh axis
            before = _plan_snapshot()
            out = comm.all_reduce(x, algo=algo)  # compile + warmup (+ plan)
            got = np.asarray(out)
            label = _planned_label(before) or {
                "algo": algo, "chunks": "1", "wire_dtype": "none"}
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = comm.all_reduce(x, algo=algo)
            np.asarray(out)  # host read waits for the device
            dt = (time.perf_counter() - t0) / args.iters
            err = float(np.abs(got - ref).max())
            ok = err <= 1e-4 * max(1.0, float(np.abs(ref).max()))
            if args.check and not ok:
                print(f"all_reduce_perf: CHECK FAILED {algo} @ {size}B "
                      f"(planned {label['algo']}): max abs err {err}",
                      flush=True)
                failed = 1
            algbw = size / dt / 1e9
            busbw = algbw * 2 * (n - 1) / n
            modeled = _modeled_us(label)
            arms.append({
                "requested": algo,
                "algo": label["algo"],  # the REAL plan label (counter)
                "chunks": int(label["chunks"]),
                # "fallback" = the planned kernel ran as its lax mirror —
                # plan_calibrate excludes those rows from the fit
                "outcome": label.get("outcome", "explicit"),
                "time_us": round(dt * 1e6, 1),
                "modeled_us": round(modeled, 2),
                "algbw_gbps": round(algbw, 3),
                "busbw_gbps": round(busbw, 3),
                "max_abs_err": err,
                "oracle_ok": ok,
            })
            if not args.json:
                print(f"  {size:>12} {algo:>8} {label['algo']:>8} "
                      f"{dt * 1e6:>10.1f} {modeled:>10.1f} {algbw:>10.3f} "
                      f"{busbw:>10.3f}")
        if args.json:
            print(json.dumps({
                "bench": "all_reduce_plan",
                "schema_version": obs.SCHEMA_VERSION,
                "bytes": size, "world": n,
                "n_axes": 2 if args.mesh2d else 1,
                "mesh2d": args.mesh2d or None,
                "substrate": jax.default_backend(),
                "arms": arms,
            }), flush=True)
        size *= 4
    obs.dump_from_args(args)
    if failed:
        raise SystemExit(failed)


if __name__ == "__main__":
    main()
