"""Bandwidth-optimal broadcast / all-gather (ISSUE 14).

Kernel oracle: broadcast and all-gather are PURE DATA MOVEMENT at full
precision, so the scatter-allgather kernels must equal the root row /
input tile EXACTLY (array_equal, not allclose); quantized wires pay one
documented codec round trip and every member dequantizes the same bytes.
Planner: the new verbs' decisions land on
``collective_plan_total{verb=...}`` and their crossovers shift with
quantized wire bytes (the PR 7 rule, via the budget probe).
Wire audit: the psum-baseline reduction is a COUNTER delta on
``ep_bytes_total{verb="bcast"}``, never model math.

Worlds 4/8/5 on 1-axis meshes (like TestBidir); heavy arms are ``slow`` — tier-1 keeps the
world-4 kernel core + the world-8 counter regressions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu.collective import Communicator, dma, pallas_ccl, plan
from jax import shard_map


def _run(mesh, fn, x, in_spec=P("dp"), out_spec=P("dp", None)):
    mapped = shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_vma=False)
    return np.asarray(jax.jit(mapped)(x))


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("dp",))


def _fb_snap():
    return {tuple(sorted(lb.items())): v
            for lb, v in dma.WIRE_FALLBACK.samples()}


def _plan_snap():
    return {tuple(sorted(lb.items())): v
            for lb, v in plan.PLAN_TOTAL.samples()}


def _bytes_snap(verb="bcast"):
    from uccl_tpu.obs import counters as obsc

    return {tuple(sorted(lb.items())): v
            for lb, v in obsc.counter("ep_bytes_total").samples()
            if lb.get("verb") == verb}


def _bytes_delta(before, verb="bcast"):
    return sum(int(v - before.get(k, 0))
               for k, v in _bytes_snap(verb).items()
               if v - before.get(k, 0) > 0)


class TestScatterAgBroadcast:
    """The kernel pair: root scatters S/n chunks, the counter-rotating
    all-gather pair completes — bit-exact at full precision."""

    @pytest.mark.parametrize("root", [0, 2])
    def test_matches_root_exact(self, devices, rng, root):
        n = 4
        x = jnp.asarray(rng.normal(size=(n, 41)), jnp.float32)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.scatter_ag_broadcast(
                v, "dp", root, interpret=True),
            x,
        )
        np.testing.assert_array_equal(
            got, np.tile(np.asarray(x)[root], (n, 1)))

    def test_budget_fallback_counted(self, devices, rng, monkeypatch):
        """Over-budget: the whole decomposition rides the bit-identical
        lax mirror, counted on ep_wire_fallback_total{what="broadcast"}
        AND collective_plan_total{verb="broadcast", outcome="fallback"}
        — and stays exact (pure movement either way)."""
        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            n = 4
            x = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
            fb, pl = _fb_snap(), _plan_snap()
            pk = (("algo", "scatter_ag"), ("chunks", "2"),
                  ("outcome", "fallback"), ("verb", "broadcast"),
                  ("wire_dtype", "none"))
            got = _run(
                _mesh(devices, n),
                lambda v: pallas_ccl.scatter_ag_broadcast(
                    v, "dp", 1, interpret=True),
                x,
            )
            np.testing.assert_array_equal(
                got, np.tile(np.asarray(x)[1], (n, 1)))
            fb2 = _fb_snap()
            hit = [k for k, v in fb2.items()
                   if v > fb.get(k, 0) and dict(k)["what"] == "broadcast"]
            assert hit, f"no counted broadcast downgrade in {fb2}"
            assert _plan_snap().get(pk, 0) == pl.get(pk, 0) + 1
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [4, 8, 5])
    def test_every_root_worlds(self, devices, rng, n):
        """The acceptance sweep: exact at EVERY root, worlds 4/8/5."""
        x = jnp.asarray(rng.normal(size=(n, 72)), jnp.float32)
        for root in range(n):
            got = _run(
                _mesh(devices, n),
                lambda v, r=root: pallas_ccl.scatter_ag_broadcast(
                    v, "dp", r, interpret=True),
                x,
            )
            np.testing.assert_array_equal(
                got, np.tile(np.asarray(x)[root], (n, 1)))

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [4, 8, 5])
    def test_fp8_wire(self, devices, rng, n):
        """fp8 wire: one quantize round trip of error vs the root row,
        every member identical, and bit-identical to the lax mirror (the
        counted fallback path)."""
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.scatter_ag_broadcast(
                v, "dp", 1, interpret=True, wire_dtype="fp8"),
            x,
        )
        want = np.tile(np.asarray(x)[1], (n, 1))
        # one e4m3 round trip (+ XLA's double-rounding slack,
        # docs/QUANT_WIRE.md)
        np.testing.assert_allclose(got, want, rtol=0.15, atol=0.1)
        for r in range(1, n):
            np.testing.assert_array_equal(got[0], got[r])

    @pytest.mark.slow
    def test_int8_wire(self, devices, rng):
        n = 4
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.scatter_ag_broadcast(
                v, "dp", 3, interpret=True, wire_dtype="int8"),
            x,
        )
        want = np.tile(np.asarray(x)[3], (n, 1))
        np.testing.assert_allclose(got, want, rtol=0.02, atol=0.02)

    @pytest.mark.slow
    def test_bf16_exact(self, devices, rng):
        """Full-precision movement is dtype-agnostic: bf16 stays exact."""
        n = 4
        x = jnp.asarray(rng.normal(size=(n, 64)), jnp.bfloat16)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.scatter_ag_broadcast(
                v, "dp", 2, interpret=True),
            x,
        )
        np.testing.assert_array_equal(
            got.astype(np.float32),
            np.tile(np.asarray(x[2], np.float32), (n, 1)))

    @pytest.mark.slow
    def test_mirror_bit_identity_fp8(self, devices, rng, monkeypatch):
        """kernel == counted lax fallback, bit for bit, on the quantized
        wire (quantize-once + verbatim forwarding on both paths)."""
        n = 4
        x = jnp.asarray(rng.normal(size=(n, 40)), jnp.float32)
        kern = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.scatter_ag_broadcast(
                v, "dp", 0, interpret=True, wire_dtype="fp8"),
            x,
        )
        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            mirror = _run(
                _mesh(devices, n),
                lambda v: pallas_ccl.scatter_ag_broadcast(
                    v, "dp", 0, interpret=True, wire_dtype="fp8"),
                x,
            )
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()
        np.testing.assert_array_equal(kern, mirror)


class TestBidirAllGather:
    """The counter-rotating AG pair: write-once forwarding, exact at full
    precision, one round trip quantized."""

    def test_matches_tile_exact(self, devices, rng):
        n = 4
        x = jnp.asarray(rng.normal(size=(n, 41)), jnp.float32)  # odd split
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_gather(v, "dp", interpret=True),
            x,
        )
        np.testing.assert_array_equal(got, np.tile(np.asarray(x), (n, 1)))

    def test_budget_fallback_counted(self, devices, rng, monkeypatch):
        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        pallas_ccl._MAX_VMEM_BYTES.reset()
        try:
            n = 4
            x = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
            fb, pl = _fb_snap(), _plan_snap()
            pk = (("algo", "bidir"), ("chunks", "2"),
                  ("outcome", "fallback"), ("verb", "all_gather"),
                  ("wire_dtype", "none"))
            got = _run(
                _mesh(devices, n),
                lambda v: pallas_ccl.bidir_all_gather(v, "dp",
                                                      interpret=True),
                x,
            )
            np.testing.assert_array_equal(
                got, np.tile(np.asarray(x), (n, 1)))
            hit = [k for k, v in _fb_snap().items()
                   if v > fb.get(k, 0)
                   and dict(k)["what"] == "all_gather_bidir"]
            assert hit
            assert _plan_snap().get(pk, 0) == pl.get(pk, 0) + 1
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            pallas_ccl._MAX_VMEM_BYTES.reset()

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [8, 5])
    def test_oracle_worlds(self, devices, rng, n):
        x = jnp.asarray(rng.normal(size=(n, 27)), jnp.float32)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_gather(v, "dp", interpret=True),
            x,
        )
        np.testing.assert_array_equal(got, np.tile(np.asarray(x), (n, 1)))

    @pytest.mark.slow
    @pytest.mark.parametrize("n", [4, 8, 5])
    @pytest.mark.parametrize("wd", ["fp8", "int8"])
    def test_quant_wire(self, devices, rng, n, wd):
        """Quantized gather: every gathered row one codec round trip from
        its contributor, all members dequantizing the same bytes."""
        x = jnp.asarray(rng.normal(size=(n, 24)), jnp.float32)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_gather(
                v, "dp", interpret=True, wire_dtype=wd),
            x,
        )
        tol = dict(fp8=(0.15, 0.1), int8=(0.02, 0.02))[wd]
        got = got.reshape(n, n, 24)  # [member, gathered row, payload]
        np.testing.assert_allclose(got[0], np.asarray(x),
                                   rtol=tol[0], atol=tol[1])
        for r in range(1, n):  # every member dequantizes the same bytes
            np.testing.assert_array_equal(got[0], got[r])

    @pytest.mark.slow
    def test_bf16_exact(self, devices, rng):
        n = 5
        x = jnp.asarray(rng.normal(size=(n, 16)), jnp.bfloat16)
        got = _run(
            _mesh(devices, n),
            lambda v: pallas_ccl.bidir_all_gather(v, "dp", interpret=True),
            x,
        )
        np.testing.assert_array_equal(
            got.astype(np.float32),
            np.tile(np.asarray(x, np.float32), (n, 1)))


class TestCommunicatorVerbs:
    """The planned Communicator surface + the counter-audited wire-byte
    regressions (the ISSUE's acceptance numbers)."""

    @pytest.fixture(scope="class")
    def comm8(self, devices):
        return Communicator(_mesh(devices, 8), "dp")

    def test_xla_scatter_gather_beats_psum_wire_bytes(self, comm8, rng):
        """The satellite regression: the re-lowered xla broadcast
        (ppermute scatter + ring gather) halves the counted wire bytes of
        the legacy masked psum — a counter delta, not model math."""
        n = 8
        x = rng.standard_normal((n, 16384)).astype(np.float32)  # 64 KiB
        gx = comm8.device_put(x)
        b = _bytes_snap()
        out = np.asarray(comm8.broadcast(gx, 3, algo="psum"))
        np.testing.assert_array_equal(out, np.tile(x[3], (n, 1)))
        psum_bytes = _bytes_delta(b)
        b = _bytes_snap()
        out = np.asarray(comm8.broadcast(gx, 3, algo="xla"))
        np.testing.assert_array_equal(out, np.tile(x[3], (n, 1)))
        xla_bytes = _bytes_delta(b)
        assert psum_bytes > 0 and xla_bytes > 0
        assert psum_bytes / xla_bytes >= 2.0, (psum_bytes, xla_bytes)

    def test_pallas_bcast_beats_psum_wire_bytes(self, comm8, rng):
        """Acceptance: the planned pallas broadcast's counter-audited
        wire bytes are >= ~2x below the masked-psum baseline at world 8
        (and the result stays bit-exact for every member)."""
        n = 8
        x = rng.standard_normal((n, 16384)).astype(np.float32)
        gx = comm8.device_put(x)
        b = _bytes_snap()
        out = np.asarray(comm8.broadcast(gx, 5, algo="psum"))
        psum_bytes = _bytes_delta(b)
        b = _bytes_snap()
        out = np.asarray(comm8.broadcast(gx, 5, algo="scatter_ag"))
        np.testing.assert_array_equal(out, np.tile(x[5], (n, 1)))
        pallas_bytes = _bytes_delta(b)
        assert pallas_bytes > 0
        assert psum_bytes / pallas_bytes >= 2.0, (psum_bytes, pallas_bytes)

    @pytest.mark.slow
    def test_fp8_bcast_wire_bytes(self, comm8, rng):
        """...and more with an fp8 wire (acceptance): >= 4x below the
        baseline, within the codec's round-trip bound."""
        n = 8
        x = rng.standard_normal((n, 16384)).astype(np.float32)
        gx = comm8.device_put(x)
        b = _bytes_snap()
        np.asarray(comm8.broadcast(gx, 0, algo="psum"))
        psum_bytes = _bytes_delta(b)
        b = _bytes_snap()
        out = np.asarray(comm8.broadcast(gx, 0, algo="scatter_ag",
                                         wire_dtype="fp8"))
        fp8_bytes = _bytes_delta(b)
        ref = np.tile(x[0], (n, 1))
        np.testing.assert_allclose(out, ref, rtol=0.15, atol=0.1)
        assert fp8_bytes > 0
        assert psum_bytes / fp8_bytes >= 4.0, (psum_bytes, fp8_bytes)

    def test_tree_and_auto_match(self, comm8, rng):
        x = rng.standard_normal((8, 96)).astype(np.float32)
        gx = comm8.device_put(x)
        want = np.tile(x[2], (8, 1))
        np.testing.assert_array_equal(
            np.asarray(comm8.broadcast(gx, 2, algo="tree")), want)
        np.testing.assert_array_equal(
            np.asarray(comm8.broadcast(gx, 2, algo="auto")), want)

    def test_bad_args(self, comm8):
        x = comm8.device_put(np.zeros((8, 4), np.float32))
        with pytest.raises(ValueError, match="root"):
            comm8.broadcast(x, 9)
        with pytest.raises(ValueError, match="wire_dtype"):
            comm8.broadcast(x, 0, algo="tree", wire_dtype="fp8")
        with pytest.raises(ValueError, match="wire_dtype"):
            comm8.all_gather(x, algo="xla", wire_dtype="fp8")
        with pytest.raises(ValueError, match="unknown broadcast"):
            comm8.broadcast(x, 0, algo="nope")
        with pytest.raises(ValueError, match="unknown all_gather"):
            comm8.all_gather(x, algo="nope")

    @pytest.mark.slow
    def test_all_gather_algos_match(self, comm8, rng):
        x = rng.standard_normal((8, 128)).astype(np.float32)
        gx = comm8.device_put(x)
        for algo in ("xla", "ring", "bidir", "auto"):
            np.testing.assert_array_equal(
                np.asarray(comm8.all_gather(gx, algo=algo)), x,
                err_msg=algo)


class TestPlannerVerbs:
    """Pure planner decisions (no mesh): candidates, budget-probe
    crossovers, verb-labeled emission."""

    def test_broadcast_tiny_prefers_tree(self):
        p = plan.get_planner().plan_broadcast((64,), jnp.float32, 8,
                                              pallas_ok=True)
        assert p.algo == "tree" and p.verb == "broadcast"

    def test_broadcast_bandwidth_range_prefers_scatter_ag(self):
        p = plan.get_planner().plan_broadcast((16384,), jnp.float32, 8,
                                              pallas_ok=True)
        assert p.algo == "scatter_ag" and p.chunks == 2

    def test_fp8_shifts_the_budget_crossover(self):
        """The PR 7 rule at the new verb: a payload whose f32 kernel pair
        overflows the (interpreter) budget plans xla, but its QUANTIZED
        wire fits — fp8 flips the decision to the kernel."""
        pl = plan.get_planner()
        shape = (8 * 8192,)  # f32 pair charge > interpret budget
        assert pl.plan_broadcast(shape, jnp.float32, 8,
                                 pallas_ok=True).algo == "xla"
        p = pl.plan_broadcast(shape, jnp.float32, 8, pallas_ok=True,
                              wire_dtype="fp8")
        assert p.algo == "scatter_ag" and p.wire_dtype == "fp8"

    def test_quant_relabel_on_non_kernel_winner(self):
        """A quantized request whose winner can't carry the wire is
        emitted at full precision (the caller counts the downgrade)."""
        p = plan.get_planner().plan_broadcast((64,), jnp.float32, 8,
                                              pallas_ok=True,
                                              wire_dtype="fp8")
        assert p.algo == "tree" and p.wire_dtype is None

    def test_all_gather_candidates(self):
        pl = plan.get_planner()
        p = pl.plan_all_gather((2048,), jnp.float32, 8, pallas_ok=True)
        assert p.algo == "bidir" and p.verb == "all_gather"
        # over the quiet budget probe: kernels drop out
        p2 = pl.plan_all_gather((1 << 20,), jnp.float32, 8,
                                pallas_ok=True)
        assert p2.algo == "xla"
        # no kernel addressability: xla is the only candidate
        p3 = pl.plan_all_gather((2048,), jnp.float32, 8, pallas_ok=False)
        assert p3.algo == "xla"

    def test_verb_emission_labels(self):
        before = _plan_snap()
        p = plan.get_planner().plan_broadcast((256,), jnp.float32, 8,
                                              pallas_ok=False)
        key = (("algo", p.algo), ("chunks", str(p.chunks)),
               ("outcome", "model"), ("verb", "broadcast"),
               ("wire_dtype", "none"))
        assert _plan_snap().get(key, 0) == before.get(key, 0) + 1
        from uccl_tpu.obs import counters as obsc

        g = obsc.gauge("collective_plan_predicted_us")
        assert g.get(algo=p.algo, chunks=str(p.chunks), wire_dtype="none",
                     verb="broadcast") == pytest.approx(p.predicted_us)

    def test_verb_cost_features(self):
        hops, vol, launches = plan.verb_cost_features(
            "broadcast", "scatter_ag", 8, 1000)
        assert hops == 14 and launches == 2
        assert vol == pytest.approx(1.5 * 7 / 8 * 1000)
        th, tvol, _ = plan.verb_cost_features("broadcast", "tree", 8, 1000)
        assert th == 3 and tvol == pytest.approx(3000)
        rh, rvol, rl = plan.verb_cost_features("all_gather", "ring", 8,
                                               1000)
        bh, bvol, bl = plan.verb_cost_features("all_gather", "bidir", 8,
                                               1000)
        assert rvol == pytest.approx(2 * bvol) and (rl, bl) == (1, 2)
        assert plan.xla_wire_volume("all_gather", 8, 1000) == 7000
        assert plan.xla_wire_volume("broadcast", 8, 1000) == 1000
        with pytest.raises(ValueError):
            plan.verb_cost_features("broadcast", "nope", 8, 1000)

    def test_tree_rounds_schedule(self):
        """The shared binomial schedule (utils.topology.bcast_tree_rounds
        — the dedupe target of plan.tree_broadcast and
        DcnGroup.broadcast): every non-root member receives exactly once,
        from a member that already holds the value."""
        from uccl_tpu.utils.topology import bcast_tree_rounds

        for n in (2, 3, 5, 8):
            for root in (0, n - 1):
                rounds = bcast_tree_rounds(n, root)
                holders = {root}
                seen = set()
                for pairs in rounds:
                    new = set()
                    for s, d in pairs:
                        assert s in holders, (n, root, s)
                        assert d not in holders and d not in seen
                        new.add(d)
                        seen.add(d)
                    holders |= new
                assert holders == set(range(n))
                assert len(rounds) == max(1, (n - 1).bit_length())


class TestCalibrateVerbs:
    """plan_calibrate fits the SAME alpha/beta/gamma from synthetic
    broadcast/all-gather arms (collective_plan lines) — one calibration
    repricing every verb."""

    @staticmethod
    def _calibrate_mod():
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "plan_calibrate.py")
        spec = importlib.util.spec_from_file_location("plan_calibrate",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_fit_recovers_constants_from_verb_arms(self):
        import json

        pc = self._calibrate_mod()
        model = plan.CostModel(
            alpha_us=3.0, beta_us_per_byte=2e-3, gamma_us=7.0,
            xla_alpha_us=55.0, xla_beta_us_per_byte=1.1e-3, xla_snake=2.0,
        )
        lines = []
        for nbytes in (4096, 65536, 1 << 20):
            for verb, algos in (("broadcast",
                                 ("xla", "tree", "scatter_ag")),
                                ("all_gather", ("xla", "ring", "bidir"))):
                arms = [
                    {"algo": a,
                     "time_us": model.predict_verb(verb, a, 8, nbytes),
                     "modeled_us": 0.0}
                    for a in algos
                ]
                lines.append(json.dumps({
                    "bench": "collective_plan", "verb": verb,
                    "bytes": nbytes, "world": 8, "n_axes": 1,
                    "mesh2d": None, "arms": arms,
                }))
        rows = pc._rows(lines)
        assert rows and all(r[0] in ("broadcast", "all_gather")
                            for r in rows)
        fitted = pc.fit(rows)
        assert fitted["PLAN_ALPHA_US"] == pytest.approx(3.0, rel=1e-3)
        assert fitted["PLAN_BETA_US_PER_BYTE"] == pytest.approx(2e-3,
                                                                rel=1e-3)
        assert fitted["PLAN_GAMMA_US"] == pytest.approx(7.0, rel=1e-3)
        assert fitted["PLAN_XLA_ALPHA_US"] == pytest.approx(55.0, rel=1e-3)
        assert fitted["PLAN_XLA_BETA_US_PER_BYTE"] == pytest.approx(
            1.1e-3, rel=1e-3)
