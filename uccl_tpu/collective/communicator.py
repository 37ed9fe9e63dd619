"""Host-side NCCL-shaped Communicator over a mesh axis.

The analog of the reference's NCCL-plugin surface (collective/rdma/nccl_plugin.cc:
pluginIsend/pluginIrecv + the ncclAllReduce/... family the plugin serves): a host
object with the familiar collective verbs, executing compiled XLA collectives over
the ICI mesh.

Buffer model: NCCL ranks each own a local buffer; the global-array analog here is a
leading **rank dimension** of size ``world`` sharded over the communicator's mesh
axes. ``all_reduce(x)[i] == sum_j x[j]`` etc. Each distinct (op, shape, dtype,
kwargs) compiles once and is cached — the moral equivalent of the reference's
per-comm setup cost, after which calls are hot-path only.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from uccl_tpu.parallel.mesh import AXIS, get_mesh, mesh_axis_size
from uccl_tpu.utils.logging import get_logger
from uccl_tpu.utils.topology import ppermute_pairs

_log = get_logger("COLL")

Axis = Union[str, Tuple[str, ...]]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    AVG = "mean"
    PROD = "prod"


def _as_tuple(axis: Axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


class Communicator:
    """Collective communicator over one (or a tuple of) mesh axes.

    Equivalent role to an ``ncclComm_t`` bound to the reference's transport
    (RDMAEndpoint + engines); here `mesh axes` + cached compiled collectives.
    """

    def __init__(self, mesh: Optional[Mesh] = None, axis: Axis = AXIS.DP):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.axes = _as_tuple(axis)
        for a in self.axes:
            if a not in self.mesh.shape:
                raise ValueError(f"axis {a!r} not in mesh axes {tuple(self.mesh.shape)}")
        self.world = mesh_axis_size(self.mesh, self.axes)
        self._cache = {}
        # request → resolved (algo, chunks, wire_dtype): planner emission
        # happens ONCE per distinct resolution (per-compile semantics, the
        # repo's counter idiom) — hot-path/timed-loop calls skip straight
        # to the compiled-fn cache with no obs work in the measured time
        self._plan_memo = {}

    # -- internals ---------------------------------------------------------

    def _axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def _ranked(self, extra_dims: int = 0) -> P:
        """PartitionSpec sharding the leading rank dim over the comm axes."""
        return P(self.axes, *([None] * extra_dims))

    def _compiled(self, key, build):
        fn = self._cache.get(key)
        if fn is None:
            fn = build()
            self._cache[key] = fn
        return fn

    def _shard_jit(self, fn, in_spec: P, out_spec: P):
        mapped = shard_map(
            fn, mesh=self.mesh, in_specs=(in_spec,), out_specs=out_spec, check_vma=False
        )
        return jax.jit(mapped)

    def _check(self, x: jax.Array):
        if x.ndim < 1 or x.shape[0] != self.world:
            raise ValueError(
                f"expected leading rank dim of size {self.world}, got shape {x.shape}"
            )

    def device_put(self, x) -> jax.Array:
        """Lay a host array with a leading rank dim out across the comm axes."""
        x = jnp.asarray(x)
        self._check(x)
        spec = self._ranked(x.ndim - 1)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # -- collectives -------------------------------------------------------

    def _payload_shape(self, x: jax.Array) -> Tuple[int, ...]:
        """One member's payload shape (the rank dim stripped) — what the
        planner's wire-byte arithmetic sees."""
        return tuple(x.shape[1:]) if x.ndim > 1 else (1,)

    def _pallas_ok(self) -> bool:
        """Can the device-kernel candidates (bidir) address this mesh? They
        take a single comm axis."""
        return len(self.axes) == 1

    def _resolve_ar_plan(self, x, op, algo, wire_dtype):
        """Resolve one all_reduce request to (algo, chunks, wire_dtype),
        emitting the planner decision and counting any quant downgrade —
        called once per distinct request (the _plan_memo guard)."""
        from uccl_tpu.collective import plan as _plan

        planner = _plan.get_planner()
        payload_shape = self._payload_shape(x)
        worlds = tuple(self.mesh.shape[a] for a in self.axes)
        plan_ = None
        if algo == "auto":
            if op != ReduceOp.SUM:
                algo = "xla"  # the explicit plans are sum-only
                if wire_dtype is not None:
                    # counted, never silent: the xla lowering of a non-sum
                    # op cannot carry a quantized wire
                    from uccl_tpu.collective import dma as _dma

                    _dma.record_fallback(
                        "all_reduce_plan", "quant_algo", detail="xla",
                        msg=f"non-sum all_reduce ({op!r}) plans xla, which "
                            f"cannot carry a quantized wire; shipping full "
                            f"precision",
                    )
                    wire_dtype = None
            else:
                plan_ = planner.plan_all_reduce(
                    payload_shape, x.dtype, self.world,
                    n_axes=len(self.axes), worlds=worlds,
                    wire_dtype=wire_dtype, pallas_ok=self._pallas_ok(),
                )
                algo = plan_.algo
                if wire_dtype is not None and algo not in ("pallas",
                                                           "bidir"):
                    from uccl_tpu.collective import dma as _dma

                    _dma.record_fallback(
                        "all_reduce_plan", "quant_algo", detail=algo,
                        msg=f"all_reduce plan {algo!r} cannot carry a "
                            f"quantized wire; shipping full precision",
                    )
                    wire_dtype = None
        if algo not in ("xla", "ring", "hd", "torus", "pallas", "bidir"):
            raise ValueError(f"unknown all_reduce algo {algo!r}")
        if plan_ is None:
            plan_ = planner.plan_explicit(
                algo, payload_shape, x.dtype, self.world,
                n_axes=len(self.axes), worlds=worlds, wire_dtype=wire_dtype,
            )
        return plan_.algo, plan_.chunks, wire_dtype

    def all_reduce(
        self, x: jax.Array, op: str = ReduceOp.SUM, algo: str = "xla",
        wire_dtype=None,
    ) -> jax.Array:
        """out[i] = reduce_j x[j] for every rank i.

        ``algo="xla"`` lowers to lax.psum (XLA's collective schedule);
        ``algo="ring"`` runs the explicit bidirectional chunk-ring schedule
        from :mod:`uccl_tpu.collective.plan` (sum only);
        ``algo="hd"`` runs the log-step recursive halving-doubling plan
        (sum only; power-of-two worlds, ring fallback otherwise);
        ``algo="torus"`` runs the 2D axis-pair chunk-graph schedule (sum
        only; the communicator must span exactly two mesh axes);
        ``algo="pallas"`` runs the same ring schedule as device-level
        remote-DMA kernels (:mod:`uccl_tpu.collective.pallas_ccl`; sum only,
        single-axis, VMEM-budget fallback to the plan lowering);
        ``algo="bidir"`` pairs two counter-rotating pallas ring kernels on
        paired collective ids, each carrying half the payload (sum only,
        single-axis — :func:`~uccl_tpu.collective.pallas_ccl.
        bidir_all_reduce`, FlexLink-style both-directions utilization);
        ``algo="auto"`` asks the :class:`~uccl_tpu.collective.plan.
        CollectivePlanner` — the alpha-beta-gamma cost model over actual
        WIRE bytes (quantized payloads shift the thresholds), with
        UCCL_TPU_AR_ALGO still honored as a forced-calibration override.
        Every resolution (modeled, forced, or explicit) is emitted on
        ``collective_plan_total``.

        ``wire_dtype="fp8"|"int8"`` (pallas/bidir algos) block-quantizes
        the wire payloads — per-hop quantized reduce-scatter with
        input-precision accumulation plus a quantize-once all-gather
        (docs/QUANT_WIRE.md error model). With ``algo="auto"`` the planner
        prices algorithms at the quantized wire size; if the winner cannot
        carry a quantized wire the payload ships full precision — counted
        on ``ep_wire_fallback_total`` (reason ``quant_algo``), never
        silently.
        """
        self._check(x)
        if wire_dtype is not None and algo not in ("pallas", "bidir",
                                                   "auto"):
            raise ValueError(
                "wire_dtype quantization rides the pallas/bidir allreduce "
                "only"
            )
        ax = self._axis_name()
        from uccl_tpu.collective import plan as _plan

        # resolve the request to a plan ONCE per distinct (request, forced
        # override) — the memo keeps planner emission + quant-downgrade
        # counting per-compile, so the hot path and timed bench iterations
        # never pay obs work. The forced-algo param is part of the memo key
        # so flipping UCCL_TPU_AR_ALGO between calls still re-plans.
        req = (op, algo, x.shape, x.dtype, wire_dtype,
               _plan._AR_FORCE_ALGO.get() if algo == "auto" else "")
        memo = self._plan_memo.get(req)
        if memo is None:
            memo = self._resolve_ar_plan(x, op, algo, wire_dtype)
            self._plan_memo[req] = memo
        algo, chunks, wire_dtype = memo
        # cache key carries the RESOLVED plan (algo + chunks + wire_dtype),
        # never the "auto" spelling: two calls whose plans resolve apart
        # (env override flipped, wire_dtype shifted a threshold) must not
        # share a compiled fn
        key = ("ar", op, algo, chunks, x.shape, x.dtype, wire_dtype)

        def build():
            def f(v):
                if algo in ("pallas", "bidir"):
                    if op != ReduceOp.SUM:
                        raise ValueError(
                            f"{algo} allreduce supports sum only"
                        )
                    if len(self.axes) != 1:
                        raise ValueError(
                            f"{algo} allreduce rings a single mesh axis"
                        )
                    from uccl_tpu.collective import pallas_ccl

                    if algo == "bidir":
                        return pallas_ccl.bidir_all_reduce(
                            v, ax, wire_dtype=wire_dtype
                        )
                    return pallas_ccl.ring_all_reduce(
                        v, ax, wire_dtype=wire_dtype
                    )
                if algo in ("ring", "hd"):
                    if op != ReduceOp.SUM:
                        raise ValueError(f"{algo} allreduce supports sum only")
                    from uccl_tpu.collective.plan import (
                        hd_all_reduce,
                        ring_all_reduce,
                    )

                    fn = hd_all_reduce if algo == "hd" else ring_all_reduce
                    return fn(v, ax)
                if algo == "torus":
                    if op != ReduceOp.SUM:
                        raise ValueError("torus allreduce supports sum only")
                    if len(self.axes) != 2:
                        raise ValueError(
                            "torus allreduce needs a 2-axis communicator"
                        )
                    from uccl_tpu.collective.plan import torus_all_reduce

                    return torus_all_reduce(v, self.axes)
                if op == ReduceOp.SUM:
                    return lax.psum(v, ax)
                if op == ReduceOp.MAX:
                    return lax.pmax(v, ax)
                if op == ReduceOp.MIN:
                    return lax.pmin(v, ax)
                if op == ReduceOp.AVG:
                    return lax.pmean(v, ax)
                if op == ReduceOp.PROD:
                    g = lax.all_gather(v, ax, axis=0, tiled=True)
                    return jnp.prod(g, axis=0, keepdims=True)
                raise ValueError(f"unsupported op {op!r}")

            spec = self._ranked(x.ndim - 1)
            return self._shard_jit(f, spec, spec)

        return self._compiled(key, build)(x)

    def _resolve_ag_plan(self, x, algo, wire_dtype):
        """Resolve one all_gather request to (algo, wire_dtype), emitting
        the planner decision (verb="all_gather") and counting any quant
        downgrade — once per distinct request (the _plan_memo guard)."""
        from uccl_tpu.collective import plan as _plan

        planner = _plan.get_planner()
        payload_shape = self._payload_shape(x)
        worlds = tuple(self.mesh.shape[a] for a in self.axes)
        if algo == "auto":
            p = planner.plan_all_gather(
                payload_shape, x.dtype, self.world,
                n_axes=len(self.axes), worlds=worlds,
                wire_dtype=wire_dtype, pallas_ok=self._pallas_ok(),
            )
            algo = p.algo
            if wire_dtype is not None and algo not in ("ring", "bidir"):
                from uccl_tpu.collective import dma as _dma

                _dma.record_fallback(
                    "all_gather_plan", "quant_algo", detail=algo,
                    msg=f"all_gather plan {algo!r} cannot carry a "
                        f"quantized wire; shipping full precision",
                )
                wire_dtype = None
            return algo, wire_dtype
        if algo not in ("xla", "ring", "bidir"):
            raise ValueError(f"unknown all_gather algo {algo!r}")
        planner.plan_explicit(
            algo, payload_shape, x.dtype, self.world,
            n_axes=len(self.axes), worlds=worlds, wire_dtype=wire_dtype,
            verb="all_gather",
        )
        return algo, wire_dtype

    def all_gather(self, x: jax.Array, algo: str = "auto",
                   wire_dtype=None) -> jax.Array:
        """Every rank receives the concatenation over the rank dim: out is
        the same global array, fully replicated (NCCL allgather
        semantics).

        ``algo="xla"`` lowers to lax.all_gather; ``algo="ring"`` runs the
        write-once pallas ring kernel
        (:func:`~uccl_tpu.collective.pallas_ccl.ring_all_gather`);
        ``algo="bidir"`` pairs two counter-rotating AG kernels, each
        carrying half the payload; ``algo="auto"`` (the default) asks the
        :class:`~uccl_tpu.collective.plan.CollectivePlanner` — priced at
        actual wire bytes, emitted on ``collective_plan_total`` with
        ``verb="all_gather"``. ``wire_dtype="fp8"|"int8"`` (ring/bidir)
        block-quantizes the contributed payload ONCE and forwards wire
        bytes verbatim: one quantize round trip of error, all members
        identical. Full precision stays bit-exact (pure data movement)."""
        self._check(x)
        if wire_dtype is not None and algo not in ("ring", "bidir", "auto"):
            raise ValueError(
                "wire_dtype quantization rides the ring/bidir all_gather "
                "only"
            )
        ax = self._axis_name()
        req = ("ag", algo, x.shape, x.dtype, wire_dtype)
        memo = self._plan_memo.get(req)
        if memo is None:
            memo = self._resolve_ag_plan(x, algo, wire_dtype)
            self._plan_memo[req] = memo
        algo, wire_dtype = memo
        key = ("ag", algo, x.shape, x.dtype, wire_dtype)

        def build():
            def f(v):
                if algo in ("ring", "bidir"):
                    if len(self.axes) != 1:
                        raise ValueError(
                            f"{algo} all_gather rings a single mesh axis"
                        )
                    from uccl_tpu.collective import pallas_ccl

                    fn = (pallas_ccl.bidir_all_gather if algo == "bidir"
                          else pallas_ccl.ring_all_gather)
                    return fn(v, ax, wire_dtype=wire_dtype)
                return lax.all_gather(v, ax, axis=0, tiled=True)

            return self._shard_jit(f, self._ranked(x.ndim - 1), P(*([None] * x.ndim)))

        return self._compiled(key, build)(x)

    def _resolve_rs_plan(self, x, algo, wire_dtype):
        """Resolve one reduce_scatter request to (algo, wire_dtype),
        emitting the planner decision (verb="reduce_scatter") and counting
        any quant downgrade — once per distinct request (the _plan_memo
        guard), same shape as _resolve_ag_plan."""
        from uccl_tpu.collective import plan as _plan

        planner = _plan.get_planner()
        payload_shape = self._payload_shape(x)
        worlds = tuple(self.mesh.shape[a] for a in self.axes)
        if algo == "auto":
            p = planner.plan_reduce_scatter(
                payload_shape, x.dtype, self.world,
                n_axes=len(self.axes), worlds=worlds,
                wire_dtype=wire_dtype, pallas_ok=self._pallas_ok(),
            )
            algo = p.algo
            if wire_dtype is not None and algo != "ring":
                from uccl_tpu.collective import dma as _dma

                _dma.record_fallback(
                    "reduce_scatter_plan", "quant_algo", detail=algo,
                    msg=f"reduce_scatter plan {algo!r} cannot carry a "
                        f"quantized wire; shipping full precision",
                )
                wire_dtype = None
            return algo, wire_dtype
        if algo not in ("xla", "ring"):
            raise ValueError(f"unknown reduce_scatter algo {algo!r}")
        planner.plan_explicit(
            algo, payload_shape, x.dtype, self.world,
            n_axes=len(self.axes), worlds=worlds, wire_dtype=wire_dtype,
            verb="reduce_scatter",
        )
        return algo, wire_dtype

    def reduce_scatter(self, x: jax.Array, op: str = ReduceOp.SUM,
                       algo: str = "auto", wire_dtype=None) -> jax.Array:
        """x: [world, N, ...] (each rank contributes a full buffer); out:
        [world, N/world, ...] with out[i] = reduce_j x[j] chunk i.

        ``algo="xla"`` lowers to lax.psum_scatter; ``algo="ring"`` runs
        the RS half of the pallas ring pair
        (:func:`~uccl_tpu.collective.pallas_ccl.ring_reduce_scatter` —
        write-once reducing hops, with its bit-identical lax mirror past
        the VMEM budget); ``algo="auto"`` (the default) asks the
        :class:`~uccl_tpu.collective.plan.CollectivePlanner` — priced at
        wire bytes under the ONE alpha-beta-gamma model, emitted on
        ``collective_plan_total`` with ``verb="reduce_scatter"`` — so all
        four verbs are planner-arbitrated. ``wire_dtype="fp8"|"int8"``
        (ring only) block-quantizes every hop's partial sum: one quantize
        round trip of error per hop."""
        self._check(x)
        if x.ndim < 2 or x.shape[1] % self.world != 0:
            raise ValueError(
                f"reduce_scatter payload dim {x.shape} must divide world {self.world}"
            )
        if op != ReduceOp.SUM:
            raise NotImplementedError("reduce_scatter supports sum only")
        if wire_dtype is not None and algo not in ("ring", "auto"):
            raise ValueError(
                "wire_dtype quantization rides the ring reduce_scatter only"
            )
        ax = self._axis_name()
        req = ("rs", algo, x.shape, x.dtype, wire_dtype)
        memo = self._plan_memo.get(req)
        if memo is None:
            memo = self._resolve_rs_plan(x, algo, wire_dtype)
            self._plan_memo[req] = memo
        algo, wire_dtype = memo
        key = ("rs", algo, x.shape, x.dtype, wire_dtype)

        def build():
            def f(v):
                if algo == "ring":
                    if len(self.axes) != 1:
                        raise ValueError(
                            "ring reduce_scatter rings a single mesh axis"
                        )
                    from uccl_tpu.collective import pallas_ccl

                    return pallas_ccl.ring_reduce_scatter(
                        v[0], ax, wire_dtype=wire_dtype
                    )[None]
                return lax.psum_scatter(v, ax, scatter_dimension=1, tiled=True)

            spec = self._ranked(x.ndim - 1)
            return self._shard_jit(f, spec, spec)

        return self._compiled(key, build)(x)

    def all_to_all(self, x: jax.Array) -> jax.Array:
        """x: [world, world, ...]; out[i, j] = x[j, i] (transpose of the first
        two dims, moved over the wire — NCCL alltoall semantics)."""
        self._check(x)
        if x.ndim < 2 or x.shape[1] != self.world:
            raise ValueError(f"all_to_all needs shape [world, world, ...], got {x.shape}")
        ax = self._axis_name()
        key = ("a2a", x.shape, x.dtype)

        def build():
            def f(v):
                # v: [1, world, ...]; block j of dim 1 goes to rank j, and the
                # block received from rank j lands at position j of dim 1 —
                # i.e. out[i, j] = x[j, i].
                return lax.all_to_all(v, ax, split_axis=1, concat_axis=1, tiled=True)

            spec = self._ranked(x.ndim - 1)
            return self._shard_jit(f, spec, spec)

        return self._compiled(key, build)(x)

    def _resolve_bcast_plan(self, x, algo, wire_dtype):
        """Resolve one broadcast request to (algo, wire_dtype), emitting
        the planner decision (verb="broadcast") and counting any quant
        downgrade — once per distinct request (the _plan_memo guard)."""
        from uccl_tpu.collective import plan as _plan

        planner = _plan.get_planner()
        payload_shape = self._payload_shape(x)
        worlds = tuple(self.mesh.shape[a] for a in self.axes)
        if algo == "auto":
            p = planner.plan_broadcast(
                payload_shape, x.dtype, self.world,
                n_axes=len(self.axes), worlds=worlds,
                wire_dtype=wire_dtype, pallas_ok=self._pallas_ok(),
            )
            algo = p.algo
            if wire_dtype is not None and algo != "scatter_ag":
                from uccl_tpu.collective import dma as _dma

                _dma.record_fallback(
                    "broadcast_plan", "quant_algo", detail=algo,
                    msg=f"broadcast plan {algo!r} cannot carry a "
                        f"quantized wire; shipping full precision",
                )
                wire_dtype = None
            return algo, wire_dtype
        if algo not in ("xla", "tree", "scatter_ag", "psum"):
            raise ValueError(f"unknown broadcast algo {algo!r}")
        planner.plan_explicit(
            algo, payload_shape, x.dtype, self.world,
            n_axes=len(self.axes), worlds=worlds, wire_dtype=wire_dtype,
            verb="broadcast",
        )
        return algo, wire_dtype

    def broadcast(self, x: jax.Array, root: int = 0, algo: str = "auto",
                  wire_dtype=None) -> jax.Array:
        """out[i] = x[root] for every i.

        ``algo="xla"`` lowers to the lax scatter-allgather schedule
        (:func:`~uccl_tpu.collective.pallas_ccl.
        scatter_gather_broadcast_lax` — direct root→j chunk ppermutes +
        one ring all-gather), replacing the legacy psum-of-zeros lowering
        that shipped the full payload through a reduction plus world-1
        adds of zeros; ``algo="tree"`` runs the binomial tree
        (:func:`~uccl_tpu.collective.plan.tree_broadcast` — log2(n)
        full-payload rounds, the alpha-dominated range);
        ``algo="scatter_ag"`` runs the pallas scatter-allgather kernel
        pair (root scatters S/n chunks, a counter-rotating all-gather
        pair completes — the bandwidth-optimal decomposition, PAPERS.md);
        ``algo="psum"`` keeps the legacy masked-psum lowering as the
        counter-audited baseline; ``algo="auto"`` (the default) asks the
        planner — emitted on ``collective_plan_total`` with
        ``verb="broadcast"``. ``wire_dtype="fp8"|"int8"`` (scatter_ag)
        quantizes the all-gather legs once: one round trip of error,
        every member identical; full precision is bit-exact on every
        algo (pure data movement — psum aside, which adds zeros)."""
        self._check(x)
        if not 0 <= root < self.world:
            raise ValueError(f"root {root} outside world {self.world}")
        if wire_dtype is not None and algo not in ("scatter_ag", "auto"):
            raise ValueError(
                "wire_dtype quantization rides the scatter_ag broadcast "
                "only"
            )
        ax = self._axis_name()
        req = ("bc", algo, x.shape, x.dtype, wire_dtype)
        memo = self._plan_memo.get(req)
        if memo is None:
            memo = self._resolve_bcast_plan(x, algo, wire_dtype)
            self._plan_memo[req] = memo
        algo, wire_dtype = memo
        key = ("bc", root, algo, x.shape, x.dtype, wire_dtype)

        def build():
            def f(v):
                from uccl_tpu.collective import pallas_ccl
                from uccl_tpu.collective import plan as _plan

                if algo == "scatter_ag":
                    if len(self.axes) != 1:
                        raise ValueError(
                            "scatter_ag broadcast rings a single mesh axis"
                        )
                    return pallas_ccl.scatter_ag_broadcast(
                        v, ax, root, wire_dtype=wire_dtype
                    )
                if algo == "tree":
                    return _plan.tree_broadcast(v, ax, root)
                if algo == "psum":
                    # the legacy lowering, kept as the wire-byte baseline:
                    # mask every non-root contribution to zero, then psum —
                    # a full-payload reduction whose every hop carries the
                    # whole buffer (counted at the up-and-down tree volume
                    # 2S; a ring-lowered psum would pay 2(n-1)/n·S, still
                    # ~2x the scatter-allgather's ~S — docs/PLAN_BENCH.md)
                    pallas_ccl._count_wire_bytes(
                        "bcast", "psum", None, 2 * v.size * v.dtype.itemsize
                    )
                    idx = lax.axis_index(ax).reshape((1,) * v.ndim)
                    masked = jnp.where(idx == root, v, jnp.zeros_like(v))
                    return lax.psum(masked, ax)
                return pallas_ccl.scatter_gather_broadcast_lax(v, ax, root)

            spec = self._ranked(x.ndim - 1)
            return self._shard_jit(f, spec, spec)

        return self._compiled(key, build)(x)

    def permute(self, x: jax.Array, perm: Sequence[Tuple[int, int]]) -> jax.Array:
        """Point-to-point sends: out[dst] = x[src] for each (src, dst); ranks not
        named as a dst receive zeros (lax.ppermute semantics — this is the
        send/recv primitive the P2P-over-ICI path uses)."""
        self._check(x)
        ax = self._axis_name()
        perm = tuple((int(s), int(d)) for s, d in perm)
        key = ("pp", perm, x.shape, x.dtype)

        def build():
            def f(v):
                return lax.ppermute(v, ax, perm=list(perm))

            spec = self._ranked(x.ndim - 1)
            return self._shard_jit(f, spec, spec)

        return self._compiled(key, build)(x)

    def ring_shift(self, x: jax.Array, shift: int = 1) -> jax.Array:
        return self.permute(x, ppermute_pairs(self.world, shift))

    def send_recv(self, x: jax.Array, src: int, dst: int) -> jax.Array:
        return self.permute(x, [(src, dst)])

    def barrier(self) -> None:
        """Execute a tiny allreduce and block on it."""
        token = jnp.zeros((self.world, 1), jnp.float32)
        jax.block_until_ready(self.all_reduce(self.device_put(token)))
