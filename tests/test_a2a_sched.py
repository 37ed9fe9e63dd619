"""Contention-aware scheduled EP all-to-all + planned reduce_scatter.

Two layers of oracle discipline:

* HOST — the schedule builder (uccl_tpu.ep.a2a_sched) is pure numpy, so its
  contract is property-tested exhaustively: every decomposition round is a
  partial matching (no port contention), the rounds cover the traffic matrix
  exactly, the round count respects the greedy edge-coloring bound
  ``2Δ − 1``, and the heaviest rounds go first. ``wire_schedule`` then
  completes that to FULL permutations + the designated-round matrix K the
  device driver consumes.

* DEVICE — the scheduled kernel (pallas_a2a.scheduled_all_to_all), the
  sorted dispatch/combine path and the Buffer verbs are a pure reordering
  of the same write-once per-pair DMAs, so every arm is pinned
  bit-identical to the unscheduled wire / ``lax.all_to_all`` — including
  the fp8+scales wire format and ``n_chunks`` pipelining. Heavy worlds
  (8, 5) ride ``slow`` per the tier-1 budget convention of
  tests/test_pallas_a2a.py.

The planner arbitration (``ep_sched`` vs ``ep_streams`` under the one
alpha-beta-gamma model) and the fourth planned verb
(``Communicator.reduce_scatter``) are covered at the bottom.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu.ep import Buffer, a2a_sched, pallas_a2a
from uccl_tpu.ep import ops as ep_ops
from jax import shard_map

WORLDS_T1 = (4,
             pytest.param(8, marks=pytest.mark.slow),
             pytest.param(5, marks=pytest.mark.slow))


def _mesh(devices, n):
    return Mesh(np.array(devices[:n]), ("ep",))


def _run(mesh, fn, *args, out_specs=None):
    in_specs = tuple(P("ep") for _ in args)
    out_specs = P("ep") if out_specs is None else out_specs
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    )(*args)


def _by_labels(samples):
    """counter.samples() → {sorted-label-items: value} (dicts unhashable)."""
    return {tuple(sorted(d.items())): v for d, v in samples}


def _skewed(rng, w, hot_scale=8.0):
    """A hot-row + hot-column traffic matrix (the MoE skew shape)."""
    m = rng.uniform(0.5, 2.0, (w, w))
    m[0] *= hot_scale       # member 0 sends a lot
    m[:, w - 1] *= hot_scale  # member w-1 hosts hot experts
    np.fill_diagonal(m, 0.0)
    return m


# ---------------------------------------------------------------------------
# host: the decomposition properties
# ---------------------------------------------------------------------------


class TestDecompose:
    @pytest.mark.parametrize("w", [2, 3, 4, 5, 8])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_rounds_are_matchings(self, w, seed):
        rng = np.random.default_rng(seed)
        m = _skewed(rng, w)
        # sparsify some entries so partial rounds appear
        m[rng.uniform(size=m.shape) < 0.3] = 0.0
        np.fill_diagonal(m, 0.0)
        for r in a2a_sched.decompose(m):
            dsts = [d for d in r.perm if d >= 0]
            assert len(dsts) == len(set(dsts)), r  # receive ports
            # send ports are unique by construction (perm indexed by src);
            # no self-loops in a decomposition round
            assert all(r.perm[s] != s for s in range(w) if r.perm[s] >= 0)

    @pytest.mark.parametrize("w", [3, 4, 5, 8])
    def test_exact_cover(self, w):
        rng = np.random.default_rng(w)
        m = _skewed(rng, w)
        m[rng.uniform(size=m.shape) < 0.25] = 0.0
        np.fill_diagonal(m, 0.0)
        rounds = a2a_sched.decompose(m)
        got = np.zeros_like(m)
        for r in rounds:
            for s, d in enumerate(r.perm):
                if d >= 0:
                    assert got[s, d] == 0.0, f"pair ({s},{d}) in two rounds"
                    got[s, d] = m[s, d]
        np.testing.assert_array_equal(got, m)
        # per-round loads are the carried weights
        for r in rounds:
            want = sum(m[s, d] for s, d in enumerate(r.perm) if d >= 0)
            assert r.load == pytest.approx(want)

    @pytest.mark.parametrize("w", [3, 4, 5, 8])
    @pytest.mark.parametrize("seed", [0, 7])
    def test_round_bound_and_order(self, w, seed):
        rng = np.random.default_rng(seed)
        m = _skewed(rng, w)
        m[rng.uniform(size=m.shape) < 0.4] = 0.0
        np.fill_diagonal(m, 0.0)
        rounds = a2a_sched.decompose(m)
        delta = a2a_sched.max_degree(m)
        if delta:
            assert len(rounds) <= 2 * delta - 1
        loads = [r.load for r in rounds]
        assert loads == sorted(loads, reverse=True)  # heaviest first

    def test_degenerates(self):
        w = 4
        assert a2a_sched.decompose(np.zeros((w, w))) == []
        # single hot column: every member sends to member 0 — w-1 rounds of
        # one edge each (receive port 0 serializes, degree w-1)
        m = np.zeros((w, w))
        m[1:, 0] = 1.0
        rounds = a2a_sched.decompose(m)
        assert len(rounds) == w - 1
        assert all(r.n_edges == 1 for r in rounds)
        # uniform all-pairs: covers with a port-disjoint round set
        u = np.ones((w, w))
        np.fill_diagonal(u, 0.0)
        got = sum(r.n_edges for r in a2a_sched.decompose(u))
        assert got == w * (w - 1)

    def test_rejects_bad_matrices(self):
        with pytest.raises(ValueError, match="square"):
            a2a_sched.decompose(np.ones((2, 3)))
        with pytest.raises(ValueError, match="non-negative"):
            a2a_sched.decompose(np.array([[0.0, -1.0], [1.0, 0.0]]))

    def test_skew_feature(self):
        u = np.ones((4, 4))
        assert a2a_sched.skew(u) == pytest.approx(1.0)  # diag ignored
        assert a2a_sched.skew(np.zeros((4, 4))) == 1.0
        m = np.zeros((4, 4))
        m[0, 1:] = 1.0  # one member does all the talking
        assert a2a_sched.skew(m) == pytest.approx(4.0)


class TestWireSchedule:
    @pytest.mark.parametrize("w", [2, 3, 4, 5, 8])
    @pytest.mark.parametrize("seed", [0, 3])
    def test_full_permutations_and_k(self, w, seed):
        rng = np.random.default_rng(seed)
        m = _skewed(rng, w)
        m[rng.uniform(size=m.shape) < 0.3] = 0.0
        np.fill_diagonal(m, 0.0)
        rounds, k = a2a_sched.wire_schedule(m, w)
        for r in rounds:
            assert sorted(r.perm) == list(range(w))  # total permutation
        assert k.shape == (w, w) and k.dtype == np.int32
        for s in range(w):
            for d in range(w):
                if s != d:
                    assert rounds[k[s, d]].perm[s] == d, (s, d)

    def test_empty_matrix_is_the_rotation_wire(self):
        """Zero traffic completes to exactly the W−1 rotations the fixed
        streams would drive — no extra rounds from a ragged packing."""
        w = 5
        rounds, k = a2a_sched.wire_schedule(np.zeros((w, w)), w)
        assert len(rounds) == w - 1
        perms = {r.perm for r in rounds}
        want = {tuple((s + h) % w for s in range(w)) for h in range(1, w)}
        assert perms == want

    def test_heavy_prefix_preserved(self):
        """Completion only touches free ports: the decomposition's heavy
        rounds keep their designated edges and their order."""
        rng = np.random.default_rng(5)
        m = _skewed(rng, 4)
        base = a2a_sched.decompose(m)
        rounds, k = a2a_sched.wire_schedule(m, 4)
        assert len(rounds) >= len(base)
        for i, r in enumerate(base):
            for s, d in enumerate(r.perm):
                if d >= 0:
                    assert rounds[i].perm[s] == d
                    assert k[s, d] == i
            assert rounds[i].load == pytest.approx(r.load)

    def test_world_mismatch_raises(self):
        with pytest.raises(ValueError, match="world"):
            a2a_sched.wire_schedule(np.zeros((3, 3)), 4)


class TestTrafficHelpers:
    def test_traffic_from_topk_matches_drop_semantics(self):
        w, t, k, e, cap = 4, 16, 2, 8, 3
        rng = np.random.default_rng(2)
        idx = rng.integers(0, e, (w, t, k)).astype(np.int32)
        got = a2a_sched.traffic_from_topk(idx, e, cap, w)
        epp = e // w
        want = np.zeros((w, w), np.int64)
        for s in range(w):
            for ee in range(e):
                cnt = min(int((idx[s] == ee).sum()), cap)
                want[s, ee // epp] += cnt
        np.testing.assert_array_equal(got, want)

    def test_traffic_from_topk_rejects(self):
        with pytest.raises(ValueError, match="topk_idx"):
            a2a_sched.traffic_from_topk(np.zeros((3, 4), np.int32), 8, 2, 3)
        with pytest.raises(ValueError, match="divisible"):
            a2a_sched.traffic_from_topk(
                np.zeros((3, 4, 2), np.int32), 7, 2, 3
            )

    def test_zipf_topk_shapes_and_skew(self):
        rng = np.random.default_rng(0)
        idx = a2a_sched.zipf_topk(rng, 4, 256, 2, 8, alpha=1.2)
        assert idx.shape == (4, 256, 2) and idx.dtype == np.int32
        assert idx.min() >= 0 and idx.max() < 8
        hot = a2a_sched.traffic_from_topk(idx, 8, 10 ** 6, 4)
        uni = a2a_sched.traffic_from_topk(
            a2a_sched.zipf_topk(rng, 4, 256, 2, 8, alpha=0.0), 8, 10 ** 6, 4
        )
        assert a2a_sched.skew(hot) > a2a_sched.skew(uni)


# ---------------------------------------------------------------------------
# device: the scheduled kernel vs the lax contract
# ---------------------------------------------------------------------------


class TestScheduledKernel:
    @pytest.mark.parametrize("n", WORLDS_T1)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_lax(self, devices, rng, n, dtype):
        mesh = _mesh(devices, n)
        sched = a2a_sched.wire_schedule(_skewed(rng, n), n)
        # 5x9 trailing block keeps the per-chunk padding path hot
        x = jnp.asarray(rng.normal(size=(n, n, 5, 9)), dtype)
        got = np.asarray(_run(
            mesh,
            lambda v: pallas_a2a.scheduled_all_to_all(v[0], "ep", sched)[None],
            x,
        ))
        want = np.asarray(_run(
            mesh,
            lambda v: jax.lax.all_to_all(v[0], "ep", 0, 0, tiled=True)[None],
            x,
        ))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("chunks", [2, 3])
    def test_chunked_matches_lax(self, devices, rng, chunks):
        n = 4
        mesh = _mesh(devices, n)
        sched = a2a_sched.wire_schedule(_skewed(rng, n), n)
        x = jnp.asarray(rng.normal(size=(n, n, 5, 9)), jnp.float32)
        got = np.asarray(_run(
            mesh,
            lambda v: pallas_a2a.scheduled_all_to_all(
                v[0], "ep", sched, n_chunks=chunks, chunk_axis=2
            )[None],
            x,
        ))
        want = np.asarray(_run(
            mesh,
            lambda v: jax.lax.all_to_all(v[0], "ep", 0, 0, tiled=True)[None],
            x,
        ))
        np.testing.assert_array_equal(got, want)

    def test_zero_matrix_schedule_matches(self, devices, rng):
        """The rotation-completed empty schedule still ships every pair."""
        n = 4
        mesh = _mesh(devices, n)
        sched = a2a_sched.wire_schedule(np.zeros((n, n)), n)
        x = jnp.asarray(rng.normal(size=(n, n, 3, 5)), jnp.float32)
        got = np.asarray(_run(
            mesh,
            lambda v: pallas_a2a.scheduled_all_to_all(v[0], "ep", sched)[None],
            x,
        ))
        want = np.asarray(_run(
            mesh,
            lambda v: jax.lax.all_to_all(v[0], "ep", 0, 0, tiled=True)[None],
            x,
        ))
        np.testing.assert_array_equal(got, want)

    def test_budget_fallback_matches(self, devices, rng, monkeypatch):
        """Past the VMEM budget the scheduled call degrades to the
        unscheduled kernel and transitively to lax — same numbers."""
        from uccl_tpu.collective import dma

        monkeypatch.setenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES", "64")
        dma.MAX_VMEM_BYTES.reset()
        try:
            n = 4
            mesh = _mesh(devices, n)
            sched = a2a_sched.wire_schedule(_skewed(rng, n), n)
            x = jnp.asarray(rng.normal(size=(n, n, 8, 16)), jnp.float32)
            got = np.asarray(_run(
                mesh,
                lambda v: pallas_a2a.scheduled_all_to_all(
                    v[0], "ep", sched
                )[None],
                x,
            ))
            want = np.asarray(_run(
                mesh,
                lambda v: jax.lax.all_to_all(
                    v[0], "ep", 0, 0, tiled=True
                )[None],
                x,
            ))
            np.testing.assert_array_equal(got, want)
        finally:
            monkeypatch.delenv("UCCL_TPU_PALLAS_CCL_MAX_BYTES")
            dma.MAX_VMEM_BYTES.reset()

    def test_bad_schedules_rejected(self, devices, rng):
        n = 4
        mesh = _mesh(devices, n)
        x = jnp.zeros((n, n, 4, 4), jnp.float32)
        rounds, k = a2a_sched.wire_schedule(_skewed(rng, n), n)

        def call(sched):
            _run(
                mesh,
                lambda v: pallas_a2a.scheduled_all_to_all(
                    v[0], "ep", sched
                )[None],
                x,
            )

        with pytest.raises(ValueError, match="not a permutation"):
            call(([(0, 0, 1, 2)], k))
        with pytest.raises(ValueError, match="designated-round"):
            call((rounds, np.zeros((3, 3), np.int32)))
        with pytest.raises(ValueError, match="at least one round"):
            call(([], np.zeros((n, n), np.int32)))
        bad_k = np.asarray(k).copy()
        bad_k[0, 1] = (bad_k[0, 1] + 1) % len(rounds)
        with pytest.raises(ValueError, match="does not carry"):
            call((rounds, bad_k))


class TestScheduledSortedPath:
    """dispatch_sorted/combine_sorted with a schedule vs the plain pallas
    wire — the scale exchange rides the same rounds under fp8."""

    @pytest.mark.parametrize("n", WORLDS_T1)
    @pytest.mark.parametrize("fp8", [False, True])
    def test_roundtrip_matches_unscheduled(self, devices, rng, n, fp8):
        mesh = _mesh(devices, n)
        t, h, e, k = 12, 24, 2 * n, 2
        cap = max(1, int(1.25 * t * k / e))
        x = rng.standard_normal((n, t, h)).astype(np.float32)
        idx = rng.integers(0, e, (n, t, k)).astype(np.int32)
        wts = rng.uniform(0.1, 1.0, (n, t, k)).astype(np.float32)
        mat = a2a_sched.traffic_from_topk(idx, e, cap, n).astype(float)
        sched = a2a_sched.wire_schedule(mat, n)
        sched_t = a2a_sched.wire_schedule(mat.T, n)

        def path(schedule, schedule_back):
            def f(xv, iv, wv):
                tfs, slot, _ = ep_ops.sorted_from_topk(iv[0], e, cap)
                recv = ep_ops.dispatch_sorted(
                    xv[0], tfs, e, cap, "ep", wire="pallas",
                    wire_fp8=fp8, schedule=schedule,
                )
                out = ep_ops.combine_sorted(
                    recv * 2.0, slot, wv[0], "ep", wire="pallas",
                    wire_fp8=fp8, schedule=schedule_back,
                )
                return recv[None], out[None]

            return _run(
                mesh, f, jnp.asarray(x), jnp.asarray(idx), jnp.asarray(wts),
                out_specs=(P("ep"), P("ep")),
            )

        recv_s, out_s = map(np.asarray, path(sched, sched_t))
        recv_u, out_u = map(np.asarray, path(None, None))
        np.testing.assert_array_equal(recv_s, recv_u)
        np.testing.assert_array_equal(out_s, out_u)


class TestBufferSched:
    """Buffer(a2a_sched=...): the knob surface — on/auto/off bit-identical,
    handles record the choice, combine rides the transposed matrix, and
    the decisions land on the obs pair."""

    def _case(self, rng, w, t, h, e, k, alpha=1.2):
        x = jnp.asarray(rng.standard_normal((w, t, h)), jnp.float32)
        idx = jnp.asarray(a2a_sched.zipf_topk(rng, w, t, k, e, alpha))
        traffic = a2a_sched.traffic_from_topk(np.asarray(idx), e, 8, w)
        return x, idx, traffic

    @pytest.mark.parametrize("n", WORLDS_T1)
    def test_modes_identical(self, devices, rng, n):
        mesh = _mesh(devices, n)
        e = 2 * n
        x, idx, traffic = self._case(rng, n, 16, 64, e, 2)
        outs = {}
        for mode in ("off", "on", "auto"):
            buf = Buffer(mesh, "ep", num_experts=e, wire="pallas",
                         a2a_sched=mode, a2a_traffic=traffic)
            recv, h = buf.dispatch(x, idx)
            out = buf.combine(recv * 2.0, h)
            outs[mode] = (np.asarray(recv), np.asarray(out), h.a2a_sched)
        assert outs["on"][2] is True and outs["off"][2] is False
        for mode in ("on", "auto"):
            np.testing.assert_array_equal(outs[mode][0], outs["off"][0])
            np.testing.assert_array_equal(outs[mode][1], outs["off"][1])

    @pytest.mark.slow
    def test_fp8_chunked_composition(self, devices, rng):
        mesh = _mesh(devices, 4)
        x, idx, traffic = self._case(rng, 4, 16, 64, 8, 2)
        outs = {}
        for mode in ("off", "on"):
            buf = Buffer(mesh, "ep", num_experts=8, wire="pallas",
                         a2a_sched=mode, a2a_traffic=traffic, n_chunks=2)
            recv, h = buf.dispatch(x, idx, wire_dtype="fp8")
            out = buf.combine(recv * 2.0, h, wire_dtype="fp8")
            outs[mode] = (np.asarray(recv), np.asarray(out))
        np.testing.assert_array_equal(outs["on"][0], outs["off"][0])
        np.testing.assert_array_equal(outs["on"][1], outs["off"][1])

    def test_auto_uniform_keeps_streams(self, devices, rng):
        mesh = _mesh(devices, 4)
        x, idx, _ = self._case(rng, 4, 16, 32, 8, 2, alpha=0.0)
        buf = Buffer(mesh, "ep", num_experts=8, wire="pallas",
                     a2a_sched="auto")  # no matrix: uniform default
        _, h = buf.dispatch(x, idx)
        assert h.a2a_sched is False

    def test_counters_fire(self, devices, rng):
        from uccl_tpu.collective import plan as _plan

        mesh = _mesh(devices, 4)
        x, idx, traffic = self._case(rng, 4, 16, 32, 8, 2)
        rounds_before = _by_labels(a2a_sched.ROUNDS_TOTAL.samples())
        plans_before = _by_labels(_plan.PLAN_TOTAL.samples())
        buf = Buffer(mesh, "ep", num_experts=8, wire="pallas",
                     a2a_sched="on", a2a_traffic=traffic)
        recv, h = buf.dispatch(x, idx)
        buf.combine(recv, h)
        rounds = _by_labels(a2a_sched.ROUNDS_TOTAL.samples())
        key = next(
            (k for k in rounds if dict(k)["algo"] == "ep_sched"), None
        )
        assert key is not None
        assert rounds[key] > rounds_before.get(key, 0)
        plans = _by_labels(_plan.PLAN_TOTAL.samples())
        ep_keys = [
            k for k in plans
            if dict(k).get("verb") == "ep_a2a"
            and dict(k)["algo"] == "ep_sched"
            and plans[k] > plans_before.get(k, 0)
        ]
        assert ep_keys, plans
        # the gauge saw the matrix (combine's transposed view lands last)
        [(_, sk)] = a2a_sched.SKEW_GAUGE.samples()
        assert sk >= 1.0

    def test_bad_mode_rejected(self, devices):
        mesh = _mesh(devices, 4)
        with pytest.raises(ValueError, match="a2a_sched"):
            Buffer(mesh, "ep", num_experts=8, a2a_sched="sometimes")


# ---------------------------------------------------------------------------
# the planner: ep_sched vs ep_streams, and the fourth verb
# ---------------------------------------------------------------------------


class TestPlanEpA2a:
    def _planner(self):
        from uccl_tpu.collective.plan import CollectivePlanner

        return CollectivePlanner()

    def test_skew_crossover(self):
        """Small payload (under the interpret budget): uniform keeps the
        streams, heavy skew flips to the schedule — the (skew−1)·β·B vs
        (rounds−1)·γ crossover of the one cost model."""
        p = self._planner()
        shape, dt, w = (4, 8, 128), jnp.float32, 4
        uni = p.plan_ep_a2a(shape, dt, w, skew=1.0, emit=False)
        assert uni.algo == "ep_streams" and uni.verb == "ep_a2a"
        hot = p.plan_ep_a2a(shape, dt, w, skew=6.0, n_rounds=3, emit=False)
        assert hot.algo == "ep_sched"
        assert hot.chunks == 3  # chunks field carries the round count

    def test_budget_gates_sched(self):
        """A payload past the kernel budget never plans ep_sched, however
        skewed — auto must not pick rounds whose first act is a counted
        fallback."""
        p = self._planner()
        big = p.plan_ep_a2a((8, 64, 512), jnp.bfloat16, 8, skew=6.0,
                            emit=False)
        assert big.algo == "ep_streams"

    def test_world1_degenerate(self):
        p = self._planner()
        one = p.plan_ep_a2a((1, 8), jnp.float32, 1, skew=9.0, emit=False)
        assert one.algo == "ep_streams" and one.predicted_us == 0.0


class TestPlannedReduceScatter:
    def _comm(self, devices, n=4):
        # single-named-axis mesh, like the other ring-kernel tests
        from uccl_tpu.collective import Communicator

        return Communicator(
            Mesh(np.array(devices[:n]), ("dp",)), "dp"
        )

    @pytest.mark.parametrize("algo", ["auto", "ring", "xla"])
    def test_matches_numpy(self, devices, rng, algo):
        comm = self._comm(devices)
        x = rng.standard_normal((4, 8, 16)).astype(np.float32)
        out = np.asarray(
            comm.reduce_scatter(comm.device_put(x), algo=algo)
        )
        want = x.sum(0).reshape(4, 2, 16)
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def test_fp8_wire_bounded_error(self, devices, rng):
        comm = self._comm(devices)
        x = rng.standard_normal((4, 8, 16)).astype(np.float32)
        out = np.asarray(comm.reduce_scatter(
            comm.device_put(x), algo="ring", wire_dtype="fp8"
        ))
        want = x.sum(0).reshape(4, 2, 16)
        err = np.abs(out - want) / (np.abs(want) + 1e-3)
        assert float(np.median(err)) < 0.15

    def test_plan_counter_rows(self, devices, rng):
        from uccl_tpu.collective import plan as _plan

        before = _by_labels(_plan.PLAN_TOTAL.samples())
        comm = self._comm(devices)
        x = rng.standard_normal((4, 8, 16)).astype(np.float32)
        comm.reduce_scatter(comm.device_put(x), algo="ring")
        comm.reduce_scatter(comm.device_put(x), algo="auto")
        after = _by_labels(_plan.PLAN_TOTAL.samples())
        rows = [
            dict(k) for k in after
            if dict(k).get("verb") == "reduce_scatter"
            and after[k] > before.get(k, 0)
        ]
        assert any(r["outcome"] == "explicit" for r in rows), rows
        assert any(r["outcome"] in ("model", "fallback") for r in rows), rows

    def test_planner_unit(self):
        from uccl_tpu.collective.plan import CollectivePlanner

        p = CollectivePlanner()
        auto = p.plan_reduce_scatter((4, 8, 16), jnp.float32, 4,
                                     pallas_ok=True, emit=False)
        assert auto.verb == "reduce_scatter"
        assert auto.algo in ("ring", "xla")
        no_pallas = p.plan_reduce_scatter((4, 8, 16), jnp.float32, 4,
                                          pallas_ok=False, emit=False)
        assert no_pallas.algo == "xla"
        one = p.plan_reduce_scatter((4, 8), jnp.float32, 1, emit=False)
        assert one.algo == "xla"


# ---------------------------------------------------------------------------
# cross-pod: scheduled rounds on the DCN wire
# ---------------------------------------------------------------------------


def _run_dcn_group(world, fn, tag):
    import threading

    from uccl_tpu.collective.hierarchical import DcnGroup
    from uccl_tpu.p2p.store import StoreClient, StoreServer
    from uccl_tpu.parallel.distributed import Session

    server = StoreServer()
    results = [None] * world
    errors = []

    def rank_main(r):
        try:
            client = StoreClient("127.0.0.1", server.port)
            sess = Session(rank=r, world=world, store=client)
            g = DcnGroup(sess, n_paths=2, tag=tag)
            try:
                results[r] = fn(g, r)
            finally:
                g.close()
                client.close()
        except Exception as e:  # pragma: no cover
            import traceback

            errors.append((r, traceback.format_exc()))

    ts = [threading.Thread(target=rank_main, args=(r,))
          for r in range(world)]
    [t.start() for t in ts]
    [t.join(timeout=120) for t in ts]
    server.close()
    assert not errors, errors[0][1]
    return results


class TestDcnScheduled:
    @pytest.mark.parametrize("world", [3, 4])
    def test_matches_unscheduled(self, rng, world):
        mat = _skewed(np.random.default_rng(world), world)
        sched = a2a_sched.wire_schedule(mat, world)
        xs = [rng.standard_normal((world, 7)).astype(np.float32)
              for _ in range(world)]
        outs_s = _run_dcn_group(
            world, lambda g, r: g.all_to_all(xs[r], schedule=sched),
            tag=f"sched{world}",
        )
        outs_u = _run_dcn_group(
            world, lambda g, r: g.all_to_all(xs[r]), tag=f"unsched{world}"
        )
        for a, b in zip(outs_s, outs_u):
            np.testing.assert_array_equal(a, b)

    def test_incomplete_schedule_rejected(self, rng):
        """A K matrix that misses a pair must fail fast on every rank, not
        deadlock the exchange."""
        world = 3
        rounds, k = a2a_sched.wire_schedule(np.zeros((world, world)), world)
        bad_k = np.asarray(k).copy()
        bad_k[0, 1] = (bad_k[0, 1] + 1) % len(rounds)

        def body(g, r):
            with pytest.raises(ValueError, match="does not carry"):
                g.all_to_all(np.zeros((world, 4), np.float32),
                             schedule=(rounds, bad_k))
            return True

        assert all(_run_dcn_group(world, body, tag="badk"))

    @pytest.mark.slow
    def test_mixed_with_unscheduled_and_broadcast(self):
        """Scheduled and unscheduled exchanges interleave on one group
        without poisoning the license/parity protocol."""
        world = 3
        mat = np.ones((world, world))
        np.fill_diagonal(mat, 0.0)
        sched = a2a_sched.wire_schedule(mat, world)

        def body(g, r):
            a1 = g.all_to_all(
                np.full((world, 4), float(10 * r), np.float32),
                schedule=sched,
            )
            a2 = g.all_to_all(
                np.full((world, 4), float(10 * r + 1), np.float32)
            )
            b = g.broadcast(np.full(8, float(r), np.float32), root=2)
            return ([a1[j][0] for j in range(world)],
                    [a2[j][0] for j in range(world)], b[0])

        for r, (a1, a2, b) in enumerate(_run_dcn_group(world, body, "mix")):
            assert a1 == [0.0, 10.0, 20.0]
            assert a2 == [1.0, 11.0, 21.0]
            assert b == 2.0


@pytest.mark.slow
class TestCrossPodScheduled:
    def test_two_pods_sched_matches_off(self, devices, rng):
        import threading

        from uccl_tpu.collective.hierarchical import DcnGroup
        from uccl_tpu.ep.cross_pod import CrossPodMoE
        from uccl_tpu.p2p.store import StoreClient, StoreServer
        from uccl_tpu.parallel.distributed import Session
        from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

        P_pods, E, T, H, F, K = 2, 8, 24, 16, 32, 2
        epp = E // P_pods
        wg = (rng.standard_normal((E, H, F)) * 0.2).astype(np.float32)
        wd = (rng.standard_normal((E, F, H)) * 0.2).astype(np.float32)
        x = rng.standard_normal((P_pods, T, H)).astype(np.float32)
        logits = rng.standard_normal((P_pods, T, E)).astype(np.float32)
        gates = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        ti = np.argsort(-gates, axis=-1)[..., :K].astype(np.int32)
        tv = np.take_along_axis(gates, ti, -1)
        tv = (tv / tv.sum(-1, keepdims=True)).astype(np.float32)
        skewed = np.array([[0.0, 5.0], [1.0, 0.0]])

        def expert_fn(buf, w):
            hmid = jnp.maximum(
                jnp.einsum("ech,ehf->ecf", buf, w["wg"]), 0.0
            )
            return jnp.einsum("ecf,efh->ech", hmid, w["wd"])

        def run(mode, traffic, tag):
            server = StoreServer()
            results, errors = {}, []

            def pod_main(p):
                try:
                    client = StoreClient("127.0.0.1", server.port)
                    sess = Session(rank=p, world=P_pods, store=client)
                    dcn = DcnGroup(sess, n_paths=2, tag=tag)
                    mesh = make_mesh(
                        MeshConfig(dp=4), devices[p * 4:(p + 1) * 4]
                    )
                    moe = CrossPodMoE(
                        dcn, mesh, num_global_experts=E, num_selected=K,
                        capacity_factor=float(E), a2a_sched=mode,
                        a2a_traffic=traffic,
                    )
                    results[p] = moe.forward(x[p], ti[p], tv[p], {
                        "fn": expert_fn,
                        "wg": jnp.asarray(wg[p * epp:(p + 1) * epp]),
                        "wd": jnp.asarray(wd[p * epp:(p + 1) * epp]),
                    })
                    dcn.close()
                    client.close()
                except Exception as e:  # pragma: no cover
                    import traceback

                    errors.append((p, traceback.format_exc()))

            ts = [threading.Thread(target=pod_main, args=(p,))
                  for p in range(P_pods)]
            [t.start() for t in ts]
            [t.join(timeout=180) for t in ts]
            server.close()
            assert not errors, errors[0][1]
            return results

        off = run("off", None, "xs_off")
        on = run("on", skewed, "xs_on")
        for p in range(P_pods):
            np.testing.assert_array_equal(
                np.asarray(on[p]), np.asarray(off[p])
            )
