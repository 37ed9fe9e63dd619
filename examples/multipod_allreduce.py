"""Cross-pod hierarchical allreduce: ICI inside each pod, DCN between pods.

Two processes each own a "pod" (a 4-device mesh); gradients reduce-scatter
over the pod's ICI, the shards allreduce across pods through the transfer
engine (ring over multipath channels), and the result redistributes — the
reference's cross-rack story (README.md:29 "cross-rack AllReduce beats NCCL")
re-expressed for TPU pods.

Usage: python examples/multipod_allreduce.py [--pods 2] [--elems 4096]
Runs on CPU (each process forces a virtual 4-device mesh) so it works anywhere.
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LOCAL_DEVICES = 4


def pod_main(rank, world, store_port, elems, result_q):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={LOCAL_DEVICES}"
    ).strip()
    import jax

    # each pod is an OS process with its own virtual CPU mesh; pods talk over
    # the host DCN wire, and several processes cannot share a chip
    jax.config.update("jax_platforms", "cpu")
    if rank == 0:
        print(f"device: {jax.devices()[0].platform} x{LOCAL_DEVICES} per pod",
              flush=True)
    import numpy as np

    from uccl_tpu.collective import Communicator
    from uccl_tpu.collective.hierarchical import DcnGroup, hierarchical_all_reduce
    from uccl_tpu.p2p.store import StoreClient
    from uccl_tpu.parallel.distributed import Session
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=LOCAL_DEVICES))
    comm = Communicator(mesh, "dp")
    sess = Session(rank=rank, world=world, store=StoreClient("127.0.0.1", store_port))
    dcn = DcnGroup(sess, n_paths=2)

    # every mesh member of every pod contributes a distinct buffer
    rng = np.random.default_rng(rank)
    x = rng.standard_normal((LOCAL_DEVICES, elems)).astype(np.float32)
    out = np.asarray(hierarchical_all_reduce(comm, dcn, comm.device_put(x)))

    result_q.put((rank, x, out[0]))  # row 0 == every row (replicated result)
    dcn.close()
    sess.store.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--elems", type=int, default=4096)
    args = ap.parse_args()

    from uccl_tpu.p2p.store import StoreServer

    server = StoreServer()
    ctx = mp.get_context("spawn")
    result_q = ctx.Queue()
    procs = [
        ctx.Process(
            target=pod_main,
            args=(r, args.pods, server.port, args.elems, result_q),
        )
        for r in range(args.pods)
    ]
    [p.start() for p in procs]
    results = [result_q.get(timeout=300) for _ in procs]
    [p.join(timeout=60) for p in procs]
    server.close()

    import numpy as np

    want = np.sum([x for _, x, _ in results], axis=0).sum(axis=0)  # global sum
    ok = all(np.allclose(out, want, rtol=1e-4, atol=1e-5) for _, _, out in results)
    print(
        f"hierarchical allreduce across {args.pods} pods x {LOCAL_DEVICES} devices: "
        f"{'OK' if ok else 'MISMATCH'}"
    )
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
