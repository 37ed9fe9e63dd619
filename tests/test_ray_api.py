"""NIXL/Ray-facing tensor-transfer API (uccl_tpu.p2p.XferEndpoint) — the
adapter surface the reference validates in p2p/tests/test_ray_api.py
(register_memory descriptor structure, serialize/deserialize roundtrip,
metadata exchange + add_remote_endpoint, WRITE/READ transfers over
descriptor lists)."""

import multiprocessing as mp

import numpy as np
import pytest

from uccl_tpu.p2p import XferEndpoint


class TestDescriptors:
    def test_register_memory_fields(self):
        xp = XferEndpoint(n_engines=1)
        try:
            arrs = [np.ones(1024, np.float32), np.zeros(512, np.float32)]
            descs = xp.register_memory(arrs)
            assert len(descs) == 2
            for arr, d in zip(arrs, descs):
                assert d["addr"] == arr.ctypes.data
                assert d["size"] == arr.nbytes
                assert d["mr_id"] > 0
                assert len(bytes.fromhex(d["fifo"])) == 64
        finally:
            xp.close()

    def test_serialize_roundtrip(self):
        xp = XferEndpoint(n_engines=1)
        try:
            arrs = [
                np.ones(1024, np.float32),
                np.zeros(512, np.float16),
                np.ones(256, np.int32),
            ]
            descs = xp.register_memory(arrs)
            blob = xp.get_serialized_descs(descs)
            back = XferEndpoint.deserialize_descs(blob)
            assert back == descs
        finally:
            xp.close()

    def test_non_numpy_rejected(self):
        xp = XferEndpoint(n_engines=1)
        try:
            with pytest.raises(TypeError, match="numpy"):
                xp.register_memory([[1, 2, 3]])
        finally:
            xp.close()

    def test_transfer_validation(self):
        xp = XferEndpoint(n_engines=1)
        try:
            with pytest.raises(ValueError, match="WRITE or READ"):
                xp.transfer(1, "PUT", [], [])
            with pytest.raises(ValueError, match="local arrays"):
                xp.transfer(1, "WRITE", [np.ones(4, np.float32)], [])
        finally:
            xp.close()


def _server(q):
    xp = XferEndpoint(n_engines=1)
    dst = [np.zeros(4096, np.float32), np.zeros(100, np.float32)]
    descs = xp.register_memory(dst)
    q.put((xp.get_metadata(), xp.get_serialized_descs(descs)))
    assert xp.accept() >= 0
    import time

    for _ in range(400):
        if any(p == b"DONE" for _, p in xp.get_notifs()):
            break
        time.sleep(0.05)
    q.put([float(d.sum()) for d in dst])
    # serve the client's READ-back before closing
    for _ in range(400):
        if any(p == b"READ_DONE" for _, p in xp.get_notifs()):
            break
        time.sleep(0.05)
    xp.close()


class TestTwoProcessTransfer:
    def test_write_then_read(self):
        """The reference's client/server flow (test_ray_api.py:442-600):
        metadata + descs out-of-band, WRITE local->remote, verify, then
        READ the same windows back and verify bit-exactness."""
        q = mp.Queue()
        proc = mp.Process(target=_server, args=(q,))
        proc.start()
        try:
            metadata, blob = q.get(timeout=30)
            xp = XferEndpoint(n_engines=1)
            ok, conn = xp.add_remote_endpoint(metadata)
            assert ok and conn >= 0
            remote = XferEndpoint.deserialize_descs(blob)
            rng = np.random.default_rng(5)
            src = [
                rng.standard_normal(4096).astype(np.float32),
                rng.standard_normal(100).astype(np.float32),
            ]
            xids = xp.transfer(conn, "WRITE", src, remote)
            assert xp.wait(xids)
            xp.send_notif(conn, b"DONE")
            sums = q.get(timeout=60)
            np.testing.assert_allclose(
                sums, [float(s.sum()) for s in src], rtol=1e-5
            )
            # READ the windows back: must be bit-exact with what we wrote
            back = [np.zeros(4096, np.float32), np.zeros(100, np.float32)]
            xids = xp.transfer(conn, "READ", back, remote)
            assert xp.wait(xids)
            for b, s in zip(back, src):
                np.testing.assert_array_equal(b, s)
            xp.send_notif(conn, b"READ_DONE")
            xp.close()
        finally:
            proc.join(timeout=30)
            if proc.is_alive():
                proc.terminate()
        assert proc.exitcode == 0


class TestExampleRuns:
    @pytest.mark.slow
    def test_disagg_proxy_example(self):
        # slow: ~16 s subprocess example run; qa.sh executes the proxy
        # example directly and its unfiltered pytest tier keeps this —
        # moved out of tier-1 to stay under the 870 s cap
        """The vLLM-style prefill/decode router end-to-end: HTTP two-step
        routing, KV pulled by one-sided READ, exact-match generation."""
        import subprocess
        import sys
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "examples",
                                          "disagg_proxy.py"),
             "--new-tokens", "8"],
            capture_output=True, text=True, timeout=300, cwd=repo, env=env,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "exact match vs single worker: True" in r.stdout

    def test_weight_transfer_example(self):
        """The Ray-actor example end-to-end (multiprocessing fallback in
        this image; identical transfer path under real Ray)."""
        import subprocess
        import sys
        import os

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "examples",
                                          "ray_weight_transfer.py")],
            capture_output=True, text=True, timeout=120, cwd=repo,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout
