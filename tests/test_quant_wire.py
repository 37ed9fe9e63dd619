"""Quantized wire collectives vs the full-precision oracle.

The EQuARX-shaped contract (docs/QUANT_WIRE.md): ``wire_dtype="fp8"|"int8"``
quantizes only what crosses the wire — reduce-scatter dequantizes before
accumulating in the input precision, all-gather quantizes once and forwards
verbatim — so the end-to-end error of a world-n allreduce is bounded by n
per-block quantize round trips (each ``<= amax / QERR``), regardless of how
partial sums grow. This suite pins:

* that bound, elementwise, at worlds 4 / 8 / 5 (odd world 5 = pad path +
  the one-credit ring schedule; marked ``slow`` per the tier-1 budget);
* exact zeros on zero input (the codec's scale-guard contract);
* outlier isolation (a huge value only pollutes its own 128-lane block);
* bit-identity between the Pallas kernel and its pure-lax mirror (the
  budget/addressability fallback MUST be the same math);
* counted-not-silent downgrades (non-float payload rides the
  full-precision wire, visible on ``ep_wire_fallback_total``);
* the Buffer-level EP arms — dispatch/combine under ``wire_dtype`` against
  the full-precision result, chunked ``n_chunks>1`` composing
  bit-identically, and ``ep_bytes_total`` carrying the quantized wire-byte
  arithmetic (payload + scale sidecar) under the ``wire_dtype`` label.

All meshes here are single-named-axis (same choice as test_pallas_ccl's odd
worlds).

Tier-1 time budget: the suite sits at the 870s cap (ROADMAP), so tier-1
keeps only a representative core — the world-4 fp8 bound arms of each
collective, the quantized Buffer round trip, and the wire-byte counter
contract (~9s) — and every other arm (world 8/5, int8, bf16, zero-exact
kernels, outlier, the kernel==mirror double-compile, the chunked
composition, counted downgrades, the moe_ffn knob) is marked ``slow``:
they run in qa.sh / ci.yml's unfiltered pytest, and the CI fail-fast
quantized smoke (pallas_a2a_proof --wire-dtype) re-proves
zero-exactness, the error bound, and pallas==lax bit-identity at worlds
4/5 per push anyway.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu.collective import dma, pallas_ccl
from uccl_tpu.ep import ops as ep_ops
from jax import shard_map

# per-round-trip error divisors of the codec (uccl_tpu.ops.quant module
# docstring): fp8 half-ulp at 448 + f16 double-rounding slack, int8 half a
# 1/127 step
QERR = {"fp8": 448.0 / 16.125, "int8": 254.0}

WORLDS = [4, pytest.param(8, marks=pytest.mark.slow),
          pytest.param(5, marks=pytest.mark.slow)]
WIRE_DTYPES = ["fp8", pytest.param("int8", marks=pytest.mark.slow)]


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("dp",))


def _run(mesh, fn, *args, out_specs=P("dp")):
    mapped = shard_map(
        fn, mesh=mesh, in_specs=tuple(P("dp") for _ in args),
        out_specs=out_specs, check_vma=False,
    )
    return np.asarray(jax.jit(mapped)(*args))


def _fb_snapshot():
    return {tuple(sorted(lb.items())): v
            for lb, v in dma.WIRE_FALLBACK.samples()}


def _fb_reasons(before):
    out = {}
    for k, v in _fb_snapshot().items():
        d = v - before.get(k, 0)
        if d > 0:
            lb = dict(k)
            out[(lb["what"], lb["reason"])] = int(d)
    return out


def _allreduce_bound(xs, n, wd):
    """Elementwise error budget of a quantized world-n allreduce: n block
    round trips (n-1 RS hops + the quantize-once AG), each bounded by its
    block amax / QERR; every partial sum's amax is bounded by the
    elementwise sum of absolutes."""
    return n * np.abs(xs).sum(axis=0).max() / QERR[wd] * 1.05


class TestQuantRings:
    @pytest.mark.parametrize("n", WORLDS)
    @pytest.mark.parametrize("wd", WIRE_DTYPES)
    def test_allreduce_within_bound(self, devices, rng, n, wd):
        mesh = _mesh(devices, n)
        xs = rng.normal(size=(n, 6, 100)).astype(np.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype=wd
            ),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        want = np.tile(xs.sum(0), (n, 1, 1))
        assert np.abs(got - want).max() <= _allreduce_bound(xs, n, wd)
        # every member dequantizes the same wire bytes -> identical copies
        per = got.reshape(n, 6, 100)
        assert (per == per[0]).all()

    @pytest.mark.slow
    @pytest.mark.parametrize("bidi", [False, True])
    def test_allreduce_unidirectional_and_nondividing(self, devices, rng,
                                                      bidi):
        """257-element payload: the pad path, both ring layouts."""
        n = 4
        mesh = _mesh(devices, n)
        xs = rng.normal(size=(n, 257)).astype(np.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", bidirectional=bidi, interpret=True,
                wire_dtype="fp8",
            ),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        want = np.tile(xs.sum(0), (n, 1))
        assert np.abs(got - want).max() <= _allreduce_bound(xs, n, "fp8")

    @pytest.mark.parametrize("wd", WIRE_DTYPES)
    @pytest.mark.slow
    def test_allreduce_zero_exact(self, devices, wd):
        n = 4
        mesh = _mesh(devices, n)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype=wd
            ),
            jnp.zeros((n, 3, 64), jnp.float32), out_specs=P("dp", None),
        )
        np.testing.assert_array_equal(got, 0.0)

    @pytest.mark.slow
    def test_allreduce_outlier_isolated_to_block(self, devices, rng):
        """A 1e4 outlier saturates its own 128-lane block's scale but must
        not degrade blocks it does not live in."""
        n = 4
        mesh = _mesh(devices, n)
        # exactly 2 wire rows per stream chunk: flat[0:128] is one block
        xs = rng.normal(size=(n, n * 2 * 2 * 128)).astype(np.float32)
        xs[0, 0] = 1e4
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype="fp8"
            ),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        want = np.tile(xs.sum(0), (n, 1))
        # the outlier's own value still lands within its (huge-amax) bound
        assert abs(got[0, 0] - want[0, 0]) <= _allreduce_bound(xs, n, "fp8")
        # all other blocks obey the bound computed WITHOUT the outlier
        clean = xs.copy()
        clean[0, 0] = 0.0
        bound = _allreduce_bound(clean, n, "fp8")
        assert np.abs(got[:, 128:] - want[:, 128:]).max() <= bound

    @pytest.mark.parametrize("wd", WIRE_DTYPES)
    def test_allgather_bounded_and_identical(self, devices, rng, wd):
        n = 4
        mesh = _mesh(devices, n)
        xs = rng.normal(size=(n, 4, 50)).astype(np.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_gather(
                v, "dp", interpret=True, wire_dtype=wd
            ),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        want = np.tile(xs.reshape(n * 4, 50), (n, 1)).reshape(got.shape)
        # one quantize round trip from the input, identical on all members
        assert np.abs(got - want).max() <= np.abs(xs).max() / QERR[wd] * 1.05
        per = got.reshape(n, n * 4, 50)
        assert (per == per[0]).all()

    @pytest.mark.parametrize("n", WORLDS)
    def test_reduce_scatter_within_bound(self, devices, rng, n):
        mesh = _mesh(devices, n)
        xs = rng.normal(size=(n, n * 6)).astype(np.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_reduce_scatter(
                v.reshape(n * 6), "dp", interpret=True, wire_dtype="fp8"
            ).reshape(1, 6),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        want = xs.sum(axis=0).reshape(n, 6)
        # n-1 hops of one round trip each
        bound = (n - 1) * np.abs(xs).sum(axis=0).max() / QERR["fp8"] * 1.05
        assert np.abs(got - want).max() <= bound

    @pytest.mark.slow
    def test_bf16_payload(self, devices, rng):
        n = 4
        mesh = _mesh(devices, n)
        xs = rng.normal(size=(n, 256)).astype(np.float32)
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype="fp8"
            ),
            jnp.asarray(xs, jnp.bfloat16), out_specs=P("dp", None),
        ).astype(np.float32)
        want = np.tile(xs.sum(0), (n, 1))
        # quant round trips + bf16 accumulation noise
        bound = _allreduce_bound(xs, n, "fp8") + 0.1 * np.abs(want).max()
        assert np.abs(got - want).max() <= bound

    @pytest.mark.parametrize("wd", WIRE_DTYPES)
    @pytest.mark.slow
    def test_kernel_bit_identical_to_lax_mirror(self, devices, rng,
                                                monkeypatch, wd):
        """The budget fallback of the quantized rings is a pure-lax mirror
        of the SAME per-hop math — forcing it must change nothing, bit for
        bit (the fallback is a transport decision, never a numerics one)."""
        n = 4
        mesh = _mesh(devices, n)
        xs = jnp.asarray(rng.normal(size=(n, 3, 70)).astype(np.float32))

        def ar(v):
            return pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype=wd
            )

        kernel = _run(mesh, ar, xs, out_specs=P("dp", None))
        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            mirror = _run(mesh, ar, xs, out_specs=P("dp", None))
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()
        np.testing.assert_array_equal(kernel, mirror)

    @pytest.mark.slow
    def test_int_payload_downgrades_counted(self, devices):
        """wire_dtype on a non-float payload ships full precision (exact
        result) and counts the downgrade — never silent."""
        n = 4
        mesh = _mesh(devices, n)
        xs = np.arange(n * 32, dtype=np.int32).reshape(n, 32)
        before = _fb_snapshot()
        got = _run(
            mesh,
            lambda v: pallas_ccl.ring_all_reduce(
                v, "dp", interpret=True, wire_dtype="fp8"
            ),
            jnp.asarray(xs), out_specs=P("dp", None),
        )
        np.testing.assert_array_equal(got, np.tile(xs.sum(0), (n, 1)))
        assert _fb_reasons(before).get(("all_reduce", "quant_dtype"), 0) >= 1


class TestQuantBufferA2A:
    """Buffer-level EP arms under ``wire_dtype``."""

    def _data(self, rng, n, t=16, h=64, e_per=2, k=2):
        e = e_per * n
        xs = rng.standard_normal((n, t, h)).astype(np.float32)
        idx = rng.integers(0, e, (n, t, k)).astype(np.int32)
        wts = rng.uniform(0.1, 1.0, (n, t, k)).astype(np.float32)
        return e, xs, idx, wts

    def _roundtrip(self, buf, xs, idx, wts, **kw):
        recv, handle = buf.dispatch(
            jnp.asarray(xs), jnp.asarray(idx), jnp.asarray(wts), **kw
        )
        return np.asarray(recv), np.asarray(
            buf.combine(recv, handle, **kw)
        )

    @pytest.mark.parametrize("n", WORLDS)
    @pytest.mark.parametrize("wd", WIRE_DTYPES)
    def test_dispatch_combine_within_bound(self, devices, rng, n, wd):
        from uccl_tpu.ep import Buffer

        mesh = _mesh(devices, n)
        e, xs, idx, wts = self._data(rng, n)
        full = Buffer(mesh, "dp", num_experts=e, num_selected=2)
        quant = Buffer(mesh, "dp", num_experts=e, num_selected=2,
                       wire_dtype=wd)
        recv_f, out_f = self._roundtrip(full, xs, idx, wts)
        recv_q, out_q = self._roundtrip(quant, xs, idx, wts)
        # dispatch: one round trip per row, block amax <= row amax
        bound = np.abs(xs).max() / QERR[wd] * 1.05
        assert np.abs(recv_q - recv_f).max() <= bound
        # combine adds a second round trip; gate weights sum to <= k
        bound = 2 * 2 * np.abs(recv_f).max() / QERR[wd] * 1.1
        assert np.abs(out_q - out_f).max() <= bound

    @pytest.mark.slow
    def test_chunked_composes_bit_identically(self, devices, rng):
        """wire_dtype x n_chunks>1: blocks run along the hidden dim, the
        chunk split along capacity — quantize-then-chunk must equal the
        unchunked quantized exchange bit for bit."""
        from uccl_tpu.ep import Buffer

        n = 4
        mesh = _mesh(devices, n)
        e, xs, idx, wts = self._data(rng, n)
        one = Buffer(mesh, "dp", num_experts=e, num_selected=2,
                     wire="pallas", n_chunks=1, wire_dtype="fp8")
        two = Buffer(mesh, "dp", num_experts=e, num_selected=2,
                     wire="pallas", n_chunks=2, wire_dtype="fp8")
        recv1, out1 = self._roundtrip(one, xs, idx, wts)
        recv2, out2 = self._roundtrip(two, xs, idx, wts)
        np.testing.assert_array_equal(recv1, recv2)
        np.testing.assert_array_equal(out1, out2)

    def test_wire_bytes_counted_with_label(self, devices, rng):
        """ep_bytes_total must carry the QUANTIZED wire arithmetic
        (1 byte/elem + f32 scale sidecar) under the wire_dtype label, not
        logical element bytes."""
        from uccl_tpu.ep import Buffer
        from uccl_tpu.ep.buffer import EP_BYTES

        n = 4
        mesh = _mesh(devices, n)
        e, xs, idx, wts = self._data(rng, n)

        def snap():
            return {tuple(sorted(lb.items())): v
                    for lb, v in EP_BYTES.samples()}

        buf = Buffer(mesh, "dp", num_experts=e, num_selected=2,
                     wire_dtype="int8")
        before = snap()
        buf.dispatch(jnp.asarray(xs), jnp.asarray(idx), jnp.asarray(wts))
        deltas = {k: v - before.get(k, 0) for k, v in snap().items()
                  if v > before.get(k, 0)}
        (key, got), = deltas.items()
        lb = dict(key)
        assert lb["verb"] == "dispatch" and lb["wire_dtype"] == "int8"
        assert got == ep_ops.wire_bytes_of(xs.shape, xs.dtype, "int8")
        # and that is strictly less than the logical f32 bytes
        assert got < xs.size * 4

    @pytest.mark.slow
    def test_nonfloat_payload_downgrades_counted(self, devices, rng):
        """An integer payload under wire_dtype ships full precision —
        bit-exact vs the unquantized Buffer — and counts the downgrade on
        ep_wire_fallback_total{what=ep_wire_quant,reason=quant_dtype},
        the same rule the rings enforce. wire_bytes_of charges raw bytes
        for it (the counter must match what actually moved)."""
        from uccl_tpu.ep import Buffer

        n = 4
        mesh = _mesh(devices, n)
        e, _, idx, wts = self._data(rng, n)
        xs = rng.integers(-1000, 1000, (n, 16, 64)).astype(np.int32)
        full = Buffer(mesh, "dp", num_experts=e, num_selected=2)
        quant = Buffer(mesh, "dp", num_experts=e, num_selected=2,
                       wire_dtype="fp8")
        recv_f, _ = full.dispatch(
            jnp.asarray(xs), jnp.asarray(idx), jnp.asarray(wts))
        before = _fb_snapshot()
        recv_q, _ = quant.dispatch(
            jnp.asarray(xs), jnp.asarray(idx), jnp.asarray(wts))
        assert _fb_reasons(before).get(
            ("ep_wire_quant", "quant_dtype"), 0) >= 1
        np.testing.assert_array_equal(np.asarray(recv_q),
                                      np.asarray(recv_f))
        assert ep_ops.wire_bytes_of(xs.shape, xs.dtype, "fp8") == \
            xs.size * 4

    @pytest.mark.slow
    def test_moe_ffn_quantized_matches_full_precision(self, devices, rng):
        """The model-layer knob: moe_ffn(wire_dtype=) stays within a loose
        tolerance of the full-precision layer (2 wire round trips deep
        inside a SwiGLU stack — this is the flagship/moe_inference path)."""
        n = 4
        mesh = _mesh(devices, n)
        t, h, f, e, k = 8, 64, 32, 8, 2
        xs = rng.standard_normal((n, t, h)).astype(np.float32)
        logits = rng.standard_normal((n, t, e)).astype(np.float32)
        s = 1.0 / np.sqrt(h)
        wg = (rng.standard_normal((e, h, f)) * s).astype(np.float32)
        wu = (rng.standard_normal((e, h, f)) * s).astype(np.float32)
        wdn = (rng.standard_normal((e, f, h)) * s).astype(np.float32)

        def layer(wd):
            def f_(xv, lv, g, u, d):
                out, _, _ = ep_ops.moe_ffn(
                    xv[0], lv[0], g, u, d, "dp", num_selected=k,
                    capacity_factor=1.25, impl="sort", wire_dtype=wd,
                )
                return out[None]

            mapped = shard_map(
                f_, mesh=mesh,
                in_specs=tuple(P("dp") for _ in range(5)),
                out_specs=P("dp"), check_vma=False,
            )
            return np.asarray(jax.jit(mapped)(
                *map(jnp.asarray, (xs, logits, wg, wu, wdn))
            ))

        ref = layer(None)
        for wd in ("fp8", "int8"):
            got = layer(wd)
            err = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12)
            assert err < {"fp8": 0.15, "int8": 0.03}[wd], (wd, err)
