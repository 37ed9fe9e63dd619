"""Elastic hybrid device/host buffers: placement, spill, and decode parity.

The reference backs EP windows with host memory when device memory is short
(lite-ep ElasticBuffer, csrc/elastic/buffer.hpp; README.md:35 "elastic
hybrid GPU/CPU buffers"); the TPU analog offloads via XLA memory kinds.
These tests run on the CPU backend, which exposes the same
device/pinned_host memory spaces as TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from uccl_tpu.ep.elastic import ElasticBuffer, ElasticKVCache
from uccl_tpu.models import dense
from uccl_tpu.models.inference import (
    KVCache,
    decode_step,
    decode_step_elastic,
    prefill,
)


def _f32(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


class TestElasticBuffer:
    def test_budget_placement_and_spill(self):
        buf = ElasticBuffer(hbm_budget_bytes=3 * 1024)
        a = _f32((16, 16))  # 1 KiB
        b = _f32((16, 16), 1)
        c = _f32((16, 16), 2)
        d = _f32((16, 16), 3)
        buf.put("a", a)
        buf.put("b", b)
        buf.put("c", c)
        buf.put("d", d)  # over budget -> host
        assert buf.placement("a") == "device"
        assert buf.placement("c") == "device"
        if buf.has_host:
            assert buf.placement("d") == "host"
            assert buf.device_bytes <= 3 * 1024
            assert buf.host_bytes == 1024
        got = buf.get("d")
        # "device" on TPU/GPU-shaped backends; CPU backends may name their
        # only (device-resident) space differently, e.g. "unpinned_host"
        dev_kind = jax.devices()[0].default_memory().kind
        assert got.sharding.memory_kind in (None, "device", dev_kind)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(d))
        # the durable placement is unchanged by a read
        if buf.has_host:
            assert buf.placement("d") == "host"

    def test_pin_overrides_budget(self):
        buf = ElasticBuffer(hbm_budget_bytes=0)
        buf.put("w", _f32((8, 8)), pin=True)
        assert buf.placement("w") == "device"

    def test_offload_and_delete(self):
        buf = ElasticBuffer(hbm_budget_bytes=1 << 20)
        buf.put("x", _f32((8, 8)))
        assert buf.placement("x") == "device"
        buf.offload("x")
        if buf.has_host:
            assert buf.placement("x") == "host"
            assert buf._store["x"].sharding.memory_kind == "pinned_host"
        np.testing.assert_array_equal(
            np.asarray(buf.get("x")), np.asarray(_f32((8, 8)))
        )
        buf.delete("x")
        assert "x" not in buf.names()


class TestElasticKVCache:
    def _mk(self, **kw):
        base = dict(
            n_layers=2, batch=2, n_kv_heads=2, head_dim=4,
            block_tokens=8, hot_blocks=2,
        )
        base.update(kw)
        return ElasticKVCache(**base)

    def test_append_and_gather_roundtrip(self):
        ekv = self._mk()
        k = _f32((2, 2, 37, 2, 4), 0)  # 4 full blocks + partial 5
        v = _f32((2, 2, 37, 2, 4), 1)
        ekv.append_tokens(k, v)
        assert ekv.length == 37
        # 4 sealed blocks, hot keeps 2, so 2 went cold
        assert ekv.cold_blocks == 2
        kk, vv, length = ekv.kv()
        assert length == 37
        np.testing.assert_allclose(
            np.asarray(kk[:, :, :37]), np.asarray(k), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(vv[:, :, :37]), np.asarray(v), rtol=1e-6
        )

    def test_cold_blocks_live_in_host_memory(self):
        ekv = self._mk()
        if not ekv.has_host:
            pytest.skip("backend has no host memory space")
        k = _f32((2, 2, 40, 2, 4))
        ekv.append_tokens(k, k)
        assert ekv.cold_blocks == 3
        for ck, cv in ekv._cold:
            assert ck.sharding.memory_kind == "pinned_host"
            assert cv.sharding.memory_kind == "pinned_host"
        for hk, hv in ekv._hot:
            assert hk.sharding.memory_kind == "device"

    def test_device_committed_is_bounded(self):
        """Growing the context grows host bytes, not committed HBM."""
        ekv = self._mk(hot_blocks=2)
        committed = []
        for _ in range(6):
            ekv.append_tokens(_f32((2, 2, 8, 2, 4)), _f32((2, 2, 8, 2, 4)))
            committed.append(ekv.device_committed_bytes())
        if ekv.has_host:
            # after the hot ring fills, committed HBM stops growing
            assert committed[-1] == committed[2]
        assert ekv.cold_blocks == 4


class TestElasticDecodeParity:
    def test_matches_dense_decode(self, rng):
        """Greedy decode over the elastic cache (with forced cold spills)
        must produce exactly the dense decode_step logits."""
        cfg = dense.DenseConfig(
            vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8,
            ffn=64,
        )
        params = dense.init_params(jax.random.PRNGKey(0), cfg)
        prompt = jnp.asarray(
            rng.integers(0, cfg.vocab, (2, 21)), jnp.int32
        )
        max_seq = 64
        logits_d, cache = prefill(params, prompt, cfg, max_seq)
        # block_tokens=8, hot_blocks=1: the 21-token prompt spills cold
        ekv = ElasticKVCache.from_cache(
            cache, block_tokens=8, hot_blocks=1
        )
        assert ekv.cold_blocks >= 1
        logits_e = logits_d
        tok_d = tok_e = None
        for step in range(6):
            tok_d = jnp.argmax(logits_d, axis=-1).astype(jnp.int32)
            tok_e = jnp.argmax(logits_e, axis=-1).astype(jnp.int32)
            np.testing.assert_array_equal(np.asarray(tok_d), np.asarray(tok_e))
            logits_d, cache = decode_step(params, tok_d, cache, cfg)
            logits_e = decode_step_elastic(params, tok_e, ekv, cfg)
            np.testing.assert_allclose(
                np.asarray(logits_e), np.asarray(logits_d), rtol=2e-4, atol=2e-5
            )
        assert ekv.length == 21 + 6


@pytest.mark.slow
class TestWarmReplicaAdmission:
    def test_admit_warm_replica_attaches_and_serves(self, rng):
        """The elastic up-scale twin of Router.detach: a warm spare built
        off the prototype (shared compile caches) joins the live router
        mid-run, is routable, serves bit-exact, and can be detached
        again with nothing leaked."""
        from uccl_tpu.ep.elastic import admit_warm_replica
        from uccl_tpu.models.inference import generate
        from uccl_tpu.serving import Router, ServingEngine
        from uccl_tpu.serving.engine import DenseBackend

        cfg = dense.DenseConfig(vocab=64, dim=32, n_layers=1, n_heads=2,
                                n_kv_heads=1, head_dim=16, ffn=64)
        params = dense.init_params(jax.random.PRNGKey(0), cfg)
        proto = DenseBackend(params, cfg, n_slots=2, max_seq=16)
        eng0 = ServingEngine(proto, prefill_chunk=4)
        r = Router([eng0])
        r.enable_health(suspect_after_s=5, dead_after_s=10)
        spare = admit_warm_replica(
            r, proto, engine_kw={"prefill_chunk": 4})
        assert len(r.replicas) == 2
        assert spare.backend.programs is proto.programs, \
            "compiles must share"
        assert spare.backend.cache is not proto.cache
        # load the original so the spare wins the route
        eng0.submit(list(range(8)), max_new_tokens=4)
        prompt = np.arange(1, 7, dtype=np.int32)
        req = r.submit(prompt, max_new_tokens=4)
        assert any(q is req for q in spare.sched.queued_requests())
        done = r.drain()
        want = np.asarray(generate(params, jnp.asarray(prompt)[None],
                                   cfg, max_new_tokens=4, max_seq=16))[0]
        got = [q for q in done if q is req][0]
        np.testing.assert_array_equal(np.asarray(got.out_tokens), want)
        finished = r.detach(1)
        assert len(r.replicas) == 1 and not finished
        assert r.leaked() == 0 and spare.pool.leaked() == 0
        r.close()
