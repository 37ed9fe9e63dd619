"""Pallas remote-DMA ring collectives, run under the TPU interpreter on the
virtual 8-device mesh (remote DMAs + semaphores simulated faithfully).

Numerics oracle: numpy / lax.psum. Schedule oracle: the lax.ppermute plan
lowering of the same ring schedules (uccl_tpu.collective.plan)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu.collective import pallas_ccl, plan
from uccl_tpu.parallel.mesh import MeshConfig, make_mesh
from jax import shard_map


@pytest.fixture(scope="module")
def mesh(devices):
    return make_mesh(MeshConfig(dp=8), devices)


@pytest.fixture(scope="module")
def mesh2d(devices):
    return make_mesh(MeshConfig(dp=2, tp=4), devices)


def _run(mesh, fn, x, in_spec, out_spec):
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
        check_vma=False,
    )
    return np.asarray(jax.jit(mapped)(x))


class TestAllGather:
    @pytest.mark.parametrize("direction", [1, -1])
    def test_matches_tile(self, mesh, rng, direction):
        x = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_gather(
                v, "dp", direction=direction, interpret=True
            ),
            x, P("dp"), P("dp", None),
        )
        # every member outputs the full gather; out_spec stacks all 8 copies
        np.testing.assert_array_equal(got, np.tile(np.asarray(x), (8, 1)))

    def test_matches_plan_lowering(self, mesh, rng):
        x = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_gather(v, "dp", interpret=True),
            x, P("dp"), P("dp", None),
        )
        want = _run(
            mesh, lambda v: plan.ring_all_gather(v, "dp"),
            x, P("dp"), P("dp", None),
        )
        np.testing.assert_array_equal(got, want)

    def test_subaxis_ring(self, mesh2d, rng):
        """Ring over tp inside a dp×tp mesh: MESH device addressing keeps
        the dp coordinate fixed."""
        x = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
        got = _run(
            mesh2d,
            lambda v: pallas_ccl.ring_all_gather(v, "tp", interpret=True),
            x, P(("dp", "tp")), P(("dp", "tp"), None),
        )
        xs = np.asarray(x)
        want = np.concatenate(
            [np.tile(xs[g * 4: (g + 1) * 4], (4, 1)) for g in range(2)]
        )
        np.testing.assert_array_equal(got, want)


class TestReduceScatter:
    @pytest.mark.parametrize("direction", [1, -1])
    def test_matches_numpy(self, mesh, rng, direction):
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_reduce_scatter(
                v.reshape(16), "dp", direction=direction, interpret=True
            ).reshape(1, 2),
            x, P("dp"), P("dp", None),
        )
        full = np.asarray(x).sum(axis=0)  # [16]; member r keeps slot r
        want = full.reshape(8, 2)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_indivisible_raises(self, mesh):
        x = jnp.ones((8, 9), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            _run(
                mesh,
                lambda v: pallas_ccl.ring_reduce_scatter(
                    v.reshape(9), "dp", interpret=True
                ),
                x, P("dp"), P("dp"),
            )


class TestAllReduce:
    @pytest.mark.parametrize("bidi", [False, True])
    @pytest.mark.parametrize("payload", [64, 257])  # 257: padding path
    def test_matches_psum(self, mesh, rng, bidi, payload):
        x = jnp.asarray(rng.normal(size=(8, payload)), jnp.float32)

        def f(v):
            return pallas_ccl.ring_all_reduce(
                v, "dp", bidirectional=bidi, interpret=True
            )

        got = _run(mesh, f, x, P("dp"), P("dp", None))
        want = np.tile(np.asarray(x).sum(0), (8, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_bf16(self, mesh, rng):
        x = jnp.asarray(rng.normal(size=(8, 128)), jnp.bfloat16)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(v, "dp", interpret=True),
            x, P("dp"), P("dp", None),
        )
        want = _run(
            mesh, lambda v: jax.lax.psum(v, "dp"), x, P("dp"), P("dp", None)
        )
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32),
            rtol=5e-2, atol=5e-2,
        )

    def test_world2_subaxis(self, mesh2d, rng):
        """n=2 ring (left == right) over the dp axis of the 2D mesh."""
        x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
        got = _run(
            mesh2d,
            lambda v: pallas_ccl.ring_all_reduce(v, "dp", interpret=True),
            x, P(("dp", "tp")), P(("dp", "tp"), None),
        )
        xs = np.asarray(x)
        # dp pairs: shard (g, t) pairs with (1-g, t); shards are row groups
        want = np.empty_like(xs)
        for g in range(2):
            for t in range(4):
                a, b = g * 4 + t, (1 - g) * 4 + t
                want[a] = xs[a] + xs[b]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_nd_payload(self, mesh, rng):
        x = jnp.asarray(rng.normal(size=(8, 3, 5)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(v, "dp", interpret=True),
            x, P("dp"), P("dp", None, None),
        )
        want = np.tile(np.asarray(x).sum(0), (8, 1, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_communicator_algo(self, mesh, rng):
        """algo="pallas" through the public Communicator API == psum."""
        from uccl_tpu.collective import Communicator

        comm = Communicator(mesh, "dp")
        x = comm.device_put(
            np.asarray(rng.normal(size=(8, 32)), np.float32)
        )
        got = np.asarray(comm.all_reduce(x, algo="pallas"))
        want = np.asarray(comm.all_reduce(x, algo="xla"))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_vmem_budget_fallback(self, mesh, rng, monkeypatch):
        """Over-budget payloads take the ppermute plan path (still correct)."""
        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
            got = _run(
                mesh,
                lambda v: pallas_ccl.ring_all_reduce(v, "dp", interpret=True),
                x, P("dp"), P("dp", None),
            )
            want = np.tile(np.asarray(x).sum(0), (8, 1))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()


class TestBidir:
    """The paired counter-rotating ring kernels (round 8, the FlexLink
    pair): two unidirectional kernels on paired collective ids, each
    carrying half the payload. 1-axis meshes; worlds 4/8/5 — the odd world
    is what catches the credit fenceposts, exactly like TestOddWorlds."""

    @staticmethod
    def _mesh(devices, n):
        return Mesh(np.array(devices[:n]), ("dp",))

    @staticmethod
    def _mirror_fn(n, wire_dtype=None):
        """The directed lax mirror pair — the exact code the pair-level
        budget fallback runs, so kernel == this pins kernel == fallback."""

        def f(v):
            flat = v.reshape(-1)
            half = flat.size // 2
            fwd = pallas_ccl._directed_ar_mirror(flat[:half], "dp", n, 1,
                                                 wire_dtype)
            bwd = pallas_ccl._directed_ar_mirror(flat[half:], "dp", n, -1,
                                                 wire_dtype)
            return jnp.concatenate([fwd, bwd]).reshape(v.shape)

        return f

    def test_matches_sum_and_mirror(self, devices, rng):
        """World 4, f32 (the tier-1 arm): oracle-exact vs the sum AND
        bit-identical to the directed lax mirror pair."""
        n = 4
        mesh = self._mesh(devices, n)
        x = jnp.asarray(rng.normal(size=(n, 41)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.bidir_all_reduce(v, "dp", interpret=True),
            x, P("dp"), P("dp", None),
        )
        want = np.tile(np.asarray(x).sum(0), (n, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        mirror = _run(mesh, self._mirror_fn(n), x, P("dp"), P("dp", None))
        np.testing.assert_array_equal(got, mirror)

    def test_budget_fallback_counted(self, devices, rng, monkeypatch):
        """The pair-level budget gate degrades BOTH rings to the mirror as
        a unit — counted on ep_wire_fallback_total{what="all_reduce_bidir"}
        AND collective_plan_total{algo="bidir", outcome="fallback"}, and
        still numerically correct."""
        from uccl_tpu.collective import dma, plan as plan_mod

        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            n = 4
            mesh = self._mesh(devices, n)
            x = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
            fb = {tuple(sorted(lb.items())): v
                  for lb, v in dma.WIRE_FALLBACK.samples()}
            pk = (("algo", "bidir"), ("chunks", "2"),
                  ("outcome", "fallback"), ("wire_dtype", "none"))
            pl = {tuple(sorted(lb.items())): v
                  for lb, v in plan_mod.PLAN_TOTAL.samples()}
            got = _run(
                mesh,
                lambda v: pallas_ccl.bidir_all_reduce(v, "dp",
                                                      interpret=True),
                x, P("dp"), P("dp", None),
            )
            want = np.tile(np.asarray(x).sum(0), (n, 1))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
            fb2 = {tuple(sorted(lb.items())): v
                   for lb, v in dma.WIRE_FALLBACK.samples()}
            hit = [k for k, v in fb2.items()
                   if v > fb.get(k, 0)
                   and dict(k)["what"] == "all_reduce_bidir"]
            assert hit, f"no counted all_reduce_bidir downgrade in {fb2}"
            pl2 = {tuple(sorted(lb.items())): v
                   for lb, v in plan_mod.PLAN_TOTAL.samples()}
            assert pl2.get(pk, 0) == pl.get(pk, 0) + 1
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [8, 5])
    def test_oracle_worlds(self, devices, rng, n):
        x = jnp.asarray(rng.normal(size=(n, 72)), jnp.float32)
        got = _run(
            self._mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_reduce(v, "dp", interpret=True),
            x, P("dp"), P("dp", None),
        )
        want = np.tile(np.asarray(x).sum(0), (n, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        mirror = _run(self._mesh(devices, n), self._mirror_fn(n), x,
                      P("dp"), P("dp", None))
        np.testing.assert_array_equal(got, mirror)

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [4, 8, 5])
    def test_bf16(self, devices, rng, n):
        x = jnp.asarray(rng.normal(size=(n, 64)), jnp.bfloat16)
        got = _run(
            self._mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_reduce(v, "dp", interpret=True),
            x, P("dp"), P("dp", None),
        ).astype(np.float32)
        want = np.tile(
            np.asarray(x, np.float32).sum(0, keepdims=True), (n, 1)
        )
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [4, 8, 5])
    def test_fp8_wire(self, devices, rng, n):
        """fp8 wire: tolerance-exact vs the f32 oracle AND bit-identical to
        the quantized directed mirror pair (the counted fallback path)."""
        mesh = self._mesh(devices, n)
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.bidir_all_reduce(v, "dp", interpret=True,
                                                  wire_dtype="fp8"),
            x, P("dp"), P("dp", None),
        )
        want = np.tile(np.asarray(x).sum(0), (n, 1))
        # one quantize round trip per RS hop + one on the gathered copy
        # (docs/QUANT_WIRE.md error model)
        np.testing.assert_allclose(got, want, rtol=0.2, atol=0.6)
        mirror = _run(mesh, self._mirror_fn(n, "fp8"), x, P("dp"),
                      P("dp", None))
        np.testing.assert_array_equal(got, mirror)


class TestOddWorlds:
    """Rings at n ∈ {3, 5} on 1-axis meshes: odd n is exactly what catches
    the ``s <= n - 4`` credit-window arithmetic (n=5 has ONE credited step
    per direction, n=3 none — a fencepost slip deadlocks or unbalances the
    semaphores)."""

    @staticmethod
    def _mesh(devices, n):
        return Mesh(np.array(devices[:n]), ("dp",))

    @pytest.mark.parametrize("n", [3, 5])
    @pytest.mark.parametrize("bidi", [False, True])
    def test_allreduce_matches_sum(self, devices, rng, n, bidi):
        mesh = self._mesh(devices, n)
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", bidirectional=bidi, interpret=True
            ),
            x, P("dp"), P("dp", None),
        )
        want = np.tile(np.asarray(x).sum(0), (n, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n", [3, 5])
    @pytest.mark.parametrize("direction", [1, -1])
    def test_allgather_matches_tile(self, devices, rng, n, direction):
        mesh = self._mesh(devices, n)
        x = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_gather(
                v, "dp", direction=direction, interpret=True
            ),
            x, P("dp"), P("dp", None),
        )
        np.testing.assert_array_equal(got, np.tile(np.asarray(x), (n, 1)))

    @pytest.mark.parametrize("n", [3, 5])
    def test_reduce_scatter_matches_numpy(self, devices, rng, n):
        mesh = self._mesh(devices, n)
        # payload divisible by n: n rows of 2n elements
        x = jnp.asarray(rng.normal(size=(n, 2 * n)), jnp.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_reduce_scatter(
                v.reshape(2 * n), "dp", interpret=True
            ).reshape(1, 2),
            x, P("dp"), P("dp", None),
        )
        want = np.asarray(x).sum(axis=0).reshape(n, 2)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
