"""Ring attention / Ulysses vs the single-shard reference — exact-math checks of
the sequence/context-parallel layer on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from uccl_tpu.ops.attention import (
    attention_reference,
    ring_attention,
    ulysses_attention,
)


def _qkv(rng, b=2, s=32, h=4, hkv=None, d=16, dtype=np.float32):
    hkv = hkv or h
    q = rng.standard_normal((b, s, h, d)).astype(dtype)
    k = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    v = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    return q, k, v


@pytest.fixture(scope="module")
def cp_mesh(devices):
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(cp=4, dp=2), devices)


def _run_cp(mesh, fn, arrays, n_cp=4):
    """Run per-shard fn over the cp axis with sequence (dim 1) sharded."""
    spec = P(None, "cp", None, None)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * len(arrays), out_specs=spec, check_vma=False
    )
    return np.asarray(jax.jit(mapped)(*arrays))


class TestReference:
    def test_causal_masking(self, rng):
        q, k, v = _qkv(rng, s=8)
        out = attention_reference(q, k, v, causal=True)
        # last position attends to everything; first position only to itself
        out_nc = attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(out[:, -1], out_nc[:, -1], rtol=1e-5)
        assert not np.allclose(out[:, 0], out_nc[:, 0])

    def test_gqa(self, rng):
        q, k, v = _qkv(rng, h=8, hkv=2)
        out = attention_reference(q, k, v)
        # manual repeat must match
        k_rep = np.repeat(k, 4, axis=2)
        v_rep = np.repeat(v, 4, axis=2)
        want = attention_reference(q, jnp.asarray(k_rep), jnp.asarray(v_rep))
        np.testing.assert_allclose(out, want, rtol=1e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, cp_mesh, rng, causal):
        q, k, v = _qkv(rng, s=32)
        want = np.asarray(attention_reference(q, k, v, causal=causal))
        got = _run_cp(
            cp_mesh, lambda a, b, c: ring_attention(a, b, c, "cp", causal=causal), (q, k, v)
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_gqa_ring(self, cp_mesh, rng):
        q, k, v = _qkv(rng, s=32, h=8, hkv=2)
        want = np.asarray(attention_reference(q, k, v))
        got = _run_cp(cp_mesh, lambda a, b, c: ring_attention(a, b, c, "cp"), (q, k, v))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_bf16(self, cp_mesh, rng):
        q, k, v = _qkv(rng, s=32)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        want = np.asarray(
            attention_reference(jnp.asarray(qb), jnp.asarray(kb), jnp.asarray(vb))
        ).astype(np.float32)
        got = _run_cp(
            cp_mesh, lambda a, b, c: ring_attention(a, b, c, "cp"), (qb, kb, vb)
        ).astype(np.float32)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, cp_mesh, rng, causal):
        q, k, v = _qkv(rng, s=32, h=8, hkv=4)
        want = np.asarray(attention_reference(q, k, v, causal=causal))
        got = _run_cp(
            cp_mesh,
            lambda a, b, c: ulysses_attention(a, b, c, "cp", causal=causal),
            (q, k, v),
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_indivisible_heads_raises(self, cp_mesh, rng):
        q, k, v = _qkv(rng, s=32, h=6)
        with pytest.raises(ValueError):
            _run_cp(cp_mesh, lambda a, b, c: ulysses_attention(a, b, c, "cp"), (q, k, v))


class TestRingFlash:
    """ring_attention(impl='flash'): Pallas blocks + LSE merge, fwd and bwd."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, cp_mesh, rng, causal):
        q, k, v = _qkv(rng, s=128, d=32)  # s_loc=32 >= min block 8
        want = np.asarray(attention_reference(q, k, v, causal=causal))
        got = _run_cp(
            cp_mesh,
            lambda a, b, c: ring_attention(a, b, c, "cp", causal=causal, impl="flash"),
            (q, k, v),
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_gqa(self, cp_mesh, rng):
        q, k, v = _qkv(rng, s=128, h=8, hkv=2, d=32)
        want = np.asarray(attention_reference(q, k, v))
        got = _run_cp(
            cp_mesh,
            lambda a, b, c: ring_attention(a, b, c, "cp", impl="flash"),
            (q, k, v),
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_grads_match_xla_ring(self, cp_mesh, rng):
        q, k, v = _qkv(rng, b=1, s=128, h=2, d=32)

        def make(impl):
            spec = P(None, "cp", None, None)
            fn = jax.shard_map(
                lambda a, b, c: ring_attention(a, b, c, "cp", impl=impl),
                mesh=cp_mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False,
            )
            return jax.grad(
                lambda a, b, c: jnp.sum(jnp.sin(fn(a, b, c))), argnums=(0, 1, 2)
            )

        gf = make("flash")(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        gx = make("xla")(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(gf, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5
            )

    def test_small_seq_interprets(self, cp_mesh, rng):
        """s_loc below Mosaic's minimum tile still runs the kernel under the
        interpreter (any tile works there); compiled, it is an error
        (test_pallas_attention) — never a quiet switch to the XLA path."""
        q, k, v = _qkv(rng, s=8)  # s_loc = 2
        want = np.asarray(attention_reference(q, k, v))
        got = _run_cp(
            cp_mesh,
            lambda a, b, c: ring_attention(a, b, c, "cp", impl="flash"),
            (q, k, v),
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


class TestUlyssesFlash:
    def test_matches_reference(self, cp_mesh, rng):
        q, k, v = _qkv(rng, s=128, h=8, hkv=4, d=32)
        want = np.asarray(attention_reference(q, k, v, causal=True))
        got = _run_cp(
            cp_mesh,
            lambda a, b, c: ulysses_attention(a, b, c, "cp", impl="flash"),
            (q, k, v),
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
