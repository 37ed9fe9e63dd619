#!/usr/bin/env python3
"""Multi-process / multi-host job launcher — the torchrun-shaped entry.

The analog of the reference's cluster launch tooling (scripts/ rsync fan-out
+ hostfiles + torchrun in every bench, SURVEY.md §2.5). Spawns ``--nproc``
worker processes on this node with the session environment set
(UCCL_TPU_COORD/RANK/WORLD — workers call
``uccl_tpu.parallel.distributed.initialize_from_env()``), streams their
output with rank prefixes, and propagates the first failure.

Chips: this launcher does NOT divide a host's chips among its children. A
chip belongs to one process, and one process drives all the chips of its
host — so on chips run ``--nproc 1`` per host. ``--nproc > 1`` is for CPU
ranks and is REFUSED unless the children are pinned to the CPU: either
``JAX_PLATFORMS=cpu`` in the environment or a ``--devices N`` argument to
the script (the entry points' virtual-CPU-mesh flag). Otherwise N children
would each reach for every chip of the host and all but one would fail or
hang.

Single node, CPU ranks 0..N-1:
    python scripts/launch.py --nproc 4 train.py --devices 2 --epochs 3

Multi-host on chips (run once per node, one process each; rank 0 must live
on the coordinator node):
    python scripts/launch.py --nnodes 2 --node-rank 0 \\
        --coordinator 10.0.0.1:9333 --nproc 1 train.py
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time


def _stream(prefix: str, pipe, out):
    for line in iter(pipe.readline, ""):
        out.write(f"[{prefix}] {line}")
        out.flush()
    pipe.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--nproc", type=int, default=1, help="ranks on this node")
    ap.add_argument("--nnodes", type=int, default=1)
    ap.add_argument("--node-rank", type=int, default=0)
    ap.add_argument(
        "--coordinator", default="127.0.0.1:9333",
        help="rank 0's ip:port (must be reachable from every node)",
    )
    ap.add_argument(
        "--no-jax-dist", action="store_true",
        help="skip jax.distributed.initialize in workers (DCN-only jobs)",
    )
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    opts = ap.parse_args()

    if (opts.nproc > 1 and os.environ.get("JAX_PLATFORMS") != "cpu"
            and "--devices" not in opts.args):
        sys.exit(
            f"launch.py: refusing --nproc {opts.nproc}: the launcher does "
            "not divide a host's chips among processes, and a chip belongs "
            "to one process. On chips run one process per host (--nproc 1; "
            "it drives all of them). For CPU ranks set JAX_PLATFORMS=cpu or "
            "pass the script --devices N."
        )

    world = opts.nnodes * opts.nproc
    base_rank = opts.node_rank * opts.nproc
    procs = []
    streams = []
    for local in range(opts.nproc):
        env = dict(os.environ)
        env["UCCL_TPU_COORD"] = opts.coordinator
        env["UCCL_TPU_RANK"] = str(base_rank + local)
        env["UCCL_TPU_WORLD"] = str(world)
        env["UCCL_TPU_LOCAL_RANK"] = str(local)
        if opts.no_jax_dist:
            env["UCCL_TPU_INIT_JAX"] = "0"
        p = subprocess.Popen(
            [sys.executable, opts.script, *opts.args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        procs.append(p)
        t = threading.Thread(
            target=_stream, args=(f"rank {base_rank + local}", p.stdout, sys.stdout),
            daemon=True,
        )
        t.start()
        streams.append(t)

    rc = 0
    try:
        # Poll ALL workers: a crash in any rank (not just the lowest) must
        # tear the job down even while earlier ranks block in collectives.
        live = set(range(len(procs)))
        while live and rc == 0:
            for i in sorted(live):
                code = procs[i].poll()
                if code is None:
                    continue
                live.discard(i)
                if code != 0 and rc == 0:
                    rc = code
            if rc != 0:
                for q in procs:  # first failure tears the job down
                    if q.poll() is None:
                        q.send_signal(signal.SIGTERM)
            time.sleep(0.05)
        # SIGTERM -> grace -> SIGKILL: a worker wedged in native code must
        # not hang the launcher (torchrun discipline)
        deadline = time.time() + 10
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
    except KeyboardInterrupt:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:  # same TERM -> grace -> KILL discipline on Ctrl-C
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        rc = 130
    for t in streams:
        t.join(timeout=5)
    return rc


if __name__ == "__main__":
    sys.exit(main())
