"""Disaggregated serving PROXY: the vLLM-style prefill/decode router.

The reference ships an HTTP proxy that (1) sends each request to the
prefill deployment with max_tokens=1, (2) lifts ``kv_transfer_params``
out of the prefill response, (3) forwards the request plus those params
to the decode deployment, whose NIXL connector pulls the KV cache over
RDMA (ep/bench/vllm/disagg_proxy.py:13-15,64-67). This example is that
router over this framework's stack:

* prefill worker — runs the prompt, registers the KV cache through
  ``XferEndpoint.register_memory`` and answers with kv_transfer_params =
  {endpoint metadata, serialized descriptors, length, first token},
* decode worker — one-sided READs the cache windows (the NIXL-pull
  analog), then generates,
* proxy — plain stdlib HTTP front doing the two-step routing; the client
  sees one /v1/completions-shaped call.

The run asserts the disaggregated tokens match single-worker generation
exactly. Usage: python examples/disagg_proxy.py [--new-tokens 12]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# one shared disaggregation fixture: model config, prompt shape, and the
# CPU-forcing gate live in disagg_kv so the two exact-match demos can
# never drift apart
from examples.disagg_kv import BATCH, MAX_SEQ, _make, _pin_cpu


def _model():
    return _make(seed=0)


def _post(url: str, payload: dict, timeout: float = 120.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        # workers reply 500 with a JSON {"error": ...} body — pass it
        # through so the proxy (and the client) see the cause, mirroring
        # the reference proxy's error forwarding (disagg_proxy.py:56-59)
        try:
            return json.loads(e.read().decode())
        except Exception:
            return {"error": f"HTTP {e.code}"}


def _serve(app, port_q):
    """Bind an ephemeral JSON HTTP server around ``app(path, payload)``,
    report the port, serve forever (shared by all three workers)."""
    srv = HTTPServer(("127.0.0.1", 0), _JsonHandler)
    srv.app = app  # type: ignore[attr-defined]
    port_q.put(srv.server_address[1])
    srv.serve_forever()


class _JsonHandler(BaseHTTPRequestHandler):
    def log_message(self, *a):  # quiet
        pass

    def _reply(self, obj: dict, code: int = 200):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802 (stdlib naming)
        n = int(self.headers.get("Content-Length", "0"))
        payload = json.loads(self.rfile.read(n).decode() or "{}")
        try:
            self._reply(self.server.app(self.path, payload))  # type: ignore
        except Exception as e:  # surface worker errors to the proxy
            self._reply({"error": repr(e)}, code=500)


def prefill_worker(port_q):
    """POST /prefill {"prompt_ids"} -> kv_transfer_params (the reference's
    max_tokens=1 leg: populate the cache, describe how to pull it)."""
    _pin_cpu()
    import jax.numpy as jnp

    from uccl_tpu.models.inference import prefill
    from uccl_tpu.p2p import XferEndpoint

    cfg, params = _model()
    xp = XferEndpoint(n_engines=1)

    def app(path, payload):
        assert path == "/prefill", path
        prompt = np.asarray(payload["prompt_ids"], np.int32)
        logits, cache = prefill(params, jnp.asarray(prompt), cfg, MAX_SEQ)
        first = np.asarray(
            jnp.argmax(logits, axis=-1), np.int32
        )
        k_host = np.ascontiguousarray(np.asarray(cache.k, np.float32))
        v_host = np.ascontiguousarray(np.asarray(cache.v, np.float32))
        # register + advertise; the endpoint's registry pins the arrays
        # for the worker's lifetime (a production server would
        # deregister_memory once the decode side confirms the pull)
        descs = xp.register_memory([k_host, v_host])
        return {
            "first_token": first.tolist(),
            "kv_transfer_params": {
                "metadata": xp.get_metadata().decode(),
                "descs": xp.get_serialized_descs(descs).decode(),
                "kv_shape": list(k_host.shape),
                "length": int(cache.length),
            },
        }

    def accept_loop():  # serve decode-worker connections as they dial in
        while True:
            try:
                xp.accept(timeout_ms=1000)
            except TimeoutError:
                continue

    threading.Thread(target=accept_loop, daemon=True).start()

    _serve(app, port_q)


def decode_worker(port_q):
    """POST /decode {"max_tokens", "first_token", "kv_transfer_params"} ->
    generated tokens. Pulls the KV cache with one-sided READs (the NIXL
    do_remote_prefill pull, reference :64-67)."""
    _pin_cpu()
    import jax.numpy as jnp

    from uccl_tpu.models.inference import KVCache
    from uccl_tpu.p2p import XferEndpoint
    from uccl_tpu.serving.disagg import decode_continue

    cfg, params = _model()
    xp = XferEndpoint(n_engines=1)
    conns = {}  # prefill metadata -> conn id (dial once, reuse)

    def app(path, payload):
        assert path == "/decode", path
        ktp = payload["kv_transfer_params"]
        md = ktp["metadata"].encode()
        if md not in conns:
            ok, cid = xp.add_remote_endpoint(md)
            assert ok, "dial prefill failed"
            conns[md] = cid
        cid = conns[md]
        shape = tuple(ktp["kv_shape"])
        k_host = np.zeros(shape, np.float32)
        v_host = np.zeros(shape, np.float32)
        remote = XferEndpoint.deserialize_descs(ktp["descs"].encode())
        xids = xp.transfer(cid, "READ", [k_host, v_host], remote)
        assert xp.wait(xids), "KV pull failed"
        cache = KVCache(
            jnp.asarray(k_host), jnp.asarray(v_host),
            jnp.int32(ktp["length"]),
        )
        tok = jnp.asarray(np.asarray(payload["first_token"], np.int32))
        toks = decode_continue(
            params, cfg, cache, tok, int(payload["max_tokens"])
        )
        return {"tokens": toks.tolist()}

    _serve(app, port_q)


def proxy_worker(port_q, prefill_port, decode_port):
    """The router itself — the reference proxy's two-step flow."""

    def app(path, payload):
        assert path == "/v1/completions", path
        # Step 1: prefill leg (max_tokens=1 equivalent: the prompt pass)
        pre = _post(
            f"http://127.0.0.1:{prefill_port}/prefill",
            {"prompt_ids": payload["prompt_ids"]},
        )
        if "error" in pre:
            return pre
        # Step 2: decode leg with the lifted kv_transfer_params
        dec = _post(
            f"http://127.0.0.1:{decode_port}/decode",
            {
                "max_tokens": payload.get("max_tokens", 8),
                "first_token": pre["first_token"],
                "kv_transfer_params": pre["kv_transfer_params"],
            },
        )
        return dec

    _serve(app, port_q)


def _single_worker_reference(prompt, new_tokens):
    _pin_cpu()
    from uccl_tpu.serving.disagg import oneshot_reference

    cfg, params = _model()
    return oneshot_reference(params, cfg, prompt, new_tokens, MAX_SEQ)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    qs = [mp.Queue() for _ in range(3)]
    pre = mp.Process(target=prefill_worker, args=(qs[0],), daemon=True)
    dec = mp.Process(target=decode_worker, args=(qs[1],), daemon=True)
    pre.start()
    dec.start()
    pre_port = qs[0].get(timeout=60)
    dec_port = qs[1].get(timeout=60)
    prox = mp.Process(
        target=proxy_worker, args=(qs[2], pre_port, dec_port), daemon=True
    )
    prox.start()
    proxy_port = qs[2].get(timeout=60)

    prompt = np.random.default_rng(7).integers(
        0, 128, (BATCH, 8)
    ).astype(np.int32)
    out = _post(
        f"http://127.0.0.1:{proxy_port}/v1/completions",
        {"prompt_ids": prompt.tolist(), "max_tokens": args.new_tokens},
        timeout=300.0,
    )
    if "error" in out:
        print("worker error:", out["error"])
        return 1
    got = np.asarray(out["tokens"], np.int32)
    want = _single_worker_reference(prompt, args.new_tokens)
    ok = np.array_equal(got, want)
    print(f"disagg proxy: {got.shape[1]} tokens/seq via prefill->decode "
          f"routing; exact match vs single worker: {ok}")
    for p in (pre, dec, prox):
        p.terminate()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
