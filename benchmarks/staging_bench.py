"""HBM↔host↔wire staging pipeline benchmark (send_jax/recv_jax).

Measures end-to-end tensor hand-off latency over TCP loopback: monolithic
(stage the WHOLE tensor to host, then send — the round-2 serial path) vs
pipelined (chunked D2H overlapped with wire TX and chunked H2D on receive,
SURVEY §7 hard-part 3; the reference hides staging with GPUDirect/bounce-pool
pipelining, p2p/engine.cc staged paths). Prints one JSON line per size.

On a real TPU the D2H/H2D legs are genuine DMAs and the overlap is larger;
on CPU-jax the staging legs are memcpys, so the measured win here is the
wire/copy overlap only (a lower bound).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from uccl_tpu.p2p import Endpoint  # noqa: E402


def _xfer(server, client, conn_s, conn_c, x, shape, dtype, chunk_bytes):
    box = {}

    def rx():
        y = server.recv_jax(conn_s, shape, dtype, timeout_ms=120000)
        np.asarray(y).reshape(-1)[:1]  # host read: the tensor is really there
        box["y"] = y

    t = threading.Thread(target=rx)
    t.start()
    t0 = time.perf_counter()
    client.send_jax(conn_c, x, chunk_bytes=chunk_bytes)
    t.join()
    return time.perf_counter() - t0


def run(sizes=(16 << 20, 64 << 20, 256 << 20), iters=5, chunk=8 << 20):
    import jax.numpy as jnp

    results = []
    with Endpoint(n_engines=2) as server, Endpoint(n_engines=2) as client:
        conn_c = client.connect("127.0.0.1", server.port)
        conn_s = server.accept()
        for size in sizes:
            elems = size // 4
            x = jnp.arange(elems, dtype=jnp.float32)
            shape, dtype = (elems,), np.float32
            for mode, cb in (("serial", 1 << 62), ("pipelined", chunk)):
                _xfer(server, client, conn_s, conn_c, x, shape, dtype, cb)
                ts = [
                    _xfer(server, client, conn_s, conn_c, x, shape, dtype, cb)
                    for _ in range(iters)
                ]
                best = min(ts)
                results.append(
                    {
                        "size": size,
                        "mode": mode,
                        "ms": round(best * 1e3, 2),
                        "GB/s": round(size / best / 1e9, 3),
                    }
                )
                print(json.dumps(results[-1]))
            s = next(r for r in results if r["size"] == size and r["mode"] == "serial")
            p = next(r for r in results if r["size"] == size and r["mode"] == "pipelined")
            print(json.dumps({"size": size, "pipelined_vs_serial": round(p["ms"] / s["ms"], 3)}))
    return results


if __name__ == "__main__":
    # This measures host wire/staging overlap — pinned to the CPU so it
    # never claims a chip another process owns.
    from uccl_tpu.utils.device import describe, pin_cpu

    pin_cpu()
    print(f"device: {describe()}", flush=True)
    run()
