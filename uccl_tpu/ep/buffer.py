"""DeepEP-shaped host API for expert-parallel dispatch/combine.

The reference exposes EP through a ``Buffer`` class with a DeepEP-identical
surface (ep/src/uccl_ep.cc:348; python mirror ep/bench/buffer.py —
``get_dispatch_layout``:797, ``dispatch``, ``combine``,
``low_latency_dispatch``:285, ``low_latency_combine``:454). This Buffer keeps
those verbs and tensor contracts in jax-global form: arrays carry a leading EP
rank dimension (one row per EP member, sharded over the EP mesh axes), and each
verb is a cached jit of the per-shard primitives in :mod:`uccl_tpu.ep.ops`.

``low_latency_*`` maps to the fp8-wire path (the reference's LL kernels pack
fp8+scales, internode_ll.cu:62); normal dispatch/combine move payloads at full
precision (the reference's "normal" internode mode).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uccl_tpu.ep import ll as ep_ll
from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.obs import counters as _obsc
from uccl_tpu.obs import tracer as _obst
from uccl_tpu.parallel.mesh import AXIS, get_mesh, mesh_axis_size
from uccl_tpu.utils.logging import get_logger

_log = get_logger("EP")

# host-level wire telemetry: WIRE bytes of the payload handed to each EP
# verb (the global [W, ...] array — what the exchange moves end to end):
# quantized payload + f32 scale sidecar when a wire_dtype applies
# (ep_ops.wire_bytes_of — the one arithmetic benches share), raw element
# bytes otherwise; labeled by verb, the wire that carried it, and the
# wire_dtype ("none" = full precision). The companion span on the "wire"
# track measures the verb's HOST call window (dispatch + any compile on
# first call) — device time proper belongs to jax.profiler.
EP_BYTES = _obsc.counter(
    "ep_bytes_total",
    "actual wire bytes moved by EP verbs and ring collectives (quantized "
    "payload + f32 scale sidecar when a wire_dtype applies, raw element "
    "bytes otherwise), by verb, wire, and wire_dtype",
)


def _observed_call(verb: str, fn, args, *, wire: str, n_chunks: int,
                   payload, wire_dtype=None) -> tuple:
    """Run one verb's jitted fn under the bytes counter + wire span."""
    nbytes = ep_ops.wire_bytes_of(payload.shape, payload.dtype, wire_dtype)
    EP_BYTES.inc(nbytes, verb=verb, wire=wire,
                 wire_dtype=wire_dtype or "none")
    with _obst.span(f"ep.{verb}", track="wire", wire=wire,
                    n_chunks=n_chunks, bytes=nbytes,
                    wire_dtype=wire_dtype or "none"):
        return fn(*args)


class EventOverlap:
    """The overlap half of the DeepEP contract, re-expressed in dataflow.

    On GPU, DeepEP records a CUDA event after the comm kernels and consumers
    either wait on it from the current stream or pass it as
    ``previous_event`` to order a later kernel behind it
    (``EventOverlap`` in the reference's ep/bench/utils.py, used throughout
    ep/bench/buffer.py:285-464). On TPU there are no user-visible streams —
    XLA's async dispatch makes every returned array a future, and ordering
    is dataflow. This class therefore wraps the arrays a verb produced:

    * ``current_stream_wait()`` — host-side barrier on those arrays (the
      analog of ``event.current_stream_wait()``; jax arrays self-order for
      device consumers, so this is only needed for host readbacks/timing).
    * as ``previous_event`` — the next verb ties its computation to this
      event's token array with ``lax.optimization_barrier``, so the later
      jit cannot begin before the earlier verb's outputs exist (a REAL
      cross-jit dependency, not a host sync; an unused jit arg would be
      pruned, hence the explicit tie).
    """

    def __init__(self, arrays):
        self._arrays = arrays

    @property
    def token(self) -> jax.Array:
        """A representative array consumers tie ordering to (global form,
        leading EP-rank dim)."""
        return jax.tree.leaves(self._arrays)[0]

    def current_stream_wait(self) -> None:
        jax.block_until_ready(self._arrays)

    wait = current_stream_wait


def _tie(x, tok):
    """Order ``x`` after ``tok`` inside a jit without consuming values."""
    x, _ = lax.optimization_barrier((x, tok))
    return x


@dataclasses.dataclass(frozen=True)
class Config:
    """Tuning hints — the TPU mapping of the reference ``Config`` row
    ``(num_sms, send_tokens, recv_tokens, rdma_send_tokens, chunk)`` from
    ep/bench/buffer.py:741-796. SM counts and NVL/RDMA chunk depths have no
    TPU meaning; the knobs that do are the wire form, fp8 packing, and
    recv-buffer sizing. A Config only fills knobs the caller left unset —
    an explicit keyword always wins.

    ``wire`` picks the transport: ``ragged``/``dense`` are the LL layouts on
    XLA collectives, ``pallas`` is the device-initiated remote-DMA
    all-to-all (:mod:`uccl_tpu.ep.pallas_a2a`; applies to BOTH the normal
    and LL verbs), ``auto`` defers to the Buffer/backend resolution.
    ``n_chunks`` is the pallas-wire chunk-pipeline depth (0 = auto, 1 =
    strictly phased; ignored off the pallas wire). ``wire_dtype`` picks the
    block-quantized wire payload ("fp8" | "int8", the shared ops.quant
    codec; None defers to ``wire_fp8``/the Buffer default)."""

    max_tokens_per_rank: Optional[int] = None  # LL recv-buffer sizing
    pair_capacity_factor: Optional[float] = None  # dense-wire pair capacity
    wire: str = "auto"  # ragged | dense | pallas | auto
    wire_fp8: bool = True
    n_chunks: Optional[int] = None  # pallas chunk-pipeline depth (0 = auto)
    wire_dtype: Optional[str] = None  # fp8 | int8 | None (full precision)
    a2a_sched: Optional[str] = None  # off | on | auto (None = Buffer's)


class DispatchHandle(NamedTuple):
    """Opaque handle threaded from dispatch to combine (the analog of the
    reference's handle tuple, ep/bench/buffer.py dispatch returns). Compact
    sorted-form routing — O(T·K) per rank, not a dense [T,E,C] mask.

    ``recv_counts`` mirrors the reference handle's received-row bookkeeping:
    entry [w, s, le] is how many of source s's rows landed for shard w's
    local expert le — i.e. the occupancy of the [s*C, s*C+C) chunk of
    ``recv_x[w, le]``. A consumer can skip empty slots or size grouped GEMMs
    from it instead of assuming full capacity.

    ``wire`` records which transport carried dispatch ("lax" XLA collective
    or "pallas" device-initiated remote DMA) and ``n_chunks`` its
    chunk-pipeline depth, so combine retraces the same path without
    re-resolving — the same role LowLatencyHandle.wire plays.
    ``wire_dtype`` records dispatch's quantized wire payload (audit +
    stats; combine resolves its OWN quantization — get_combine_config
    deliberately keeps the return path full-precision by default, since
    gate weights amplify combine error)."""

    slot: jax.Array  # [W, T, K] int32 slot per assignment (E*C = dropped)
    weights: jax.Array  # [W, T, K] f32 gate weights
    recv_counts: jax.Array  # [W, W_src, E_local] int32 (always populated)
    wire: str = "lax"  # lax | pallas (defaulted: pre-wire handles pickle)
    n_chunks: int = 1  # pallas chunk depth (defaulted: pre-chunk handles)
    wire_dtype: Optional[str] = None  # fp8 | int8 | None (pre-quant: None)
    a2a_sched: bool = False  # dispatch rode the scheduled rounds; combine
    #   rebuilds the TRANSPOSED schedule (defaulted: pre-sched handles)


class LowLatencyHandle(NamedTuple):
    """Handle for the packed low-latency path (ep/ll.py): the global [W, ...]
    form of :class:`uccl_tpu.ep.ll.LLState` plus the static wire choice —
    DeepEP keeps the same bookkeeping inside its returned handle tuple
    (ep/bench/buffer.py:285-454)."""

    send_slot: jax.Array  # [W, T, K]
    weights: jax.Array  # [W, T, K]
    send_mat: jax.Array  # [W, W, E_local]
    recv_mat: jax.Array  # [W, W, E_local]
    regroup: jax.Array  # [W, R_max]
    src_in_offsets: jax.Array  # [W, W]
    wire: str
    wire_fp8: bool
    n_chunks: int = 1  # pallas chunk depth (defaulted: pre-chunk handles)
    wire_dtype: Optional[str] = None  # resolved quantized payload (None =
    #   wire_fp8 decides — pre-quant handles unpickle to that legacy rule)


class Buffer:
    """Expert-parallel buffer bound to a mesh's EP axes.

    Args mirror the reference Buffer's construction knobs (group/world implied
    by the mesh; hidden size checked at call time; capacity via factor).

    ``wire`` selects the transport every verb rides unless a call overrides
    it: ``"auto"`` keeps today's resolution (XLA collectives; ragged LL wire
    where the backend lowers it), ``"pallas"`` routes the member-major
    exchanges of BOTH the normal (sorted) and low-latency row formats
    through the device-initiated remote-DMA all-to-all kernel
    (:mod:`uccl_tpu.ep.pallas_a2a`), keeping ``lax`` as the transparent
    fallback past its VMEM budget.

    ``n_chunks`` sets the pallas wire's chunk-pipeline depth: the
    capacity/slot axis splits into that many double-buffered per-chunk
    kernels on rotated collective ids, so a consumer's expert compute can
    hide under the neighboring chunks' DMAs (0 = auto, 1 = strictly
    phased). Identical numerics either way; over the 2x double-buffer
    budget the verbs fall back to the unchunked wire automatically, and
    the knob is ignored off the pallas wire.

    ``wire_dtype`` quantizes every verb's wire payload with the shared
    block-scale codec ("fp8" | "int8", :mod:`uccl_tpu.ops.quant`; values +
    per-block f32 scales move, one quantize round trip of error per
    exchange — docs/QUANT_WIRE.md). Per-call ``wire_dtype=``/``wire_fp8=``
    keywords and a Config override it; None keeps full precision.

    ``a2a_sched`` orders the pallas wire's exchange as contention-free
    permutation rounds built from ``a2a_traffic`` (the host [W, W] routing
    matrix; :mod:`uccl_tpu.ep.a2a_sched`): "on" pins the schedule, "auto"
    lets the planner flip between it and the fixed streams off the traffic
    skew, "off" (default) keeps the streams. Bit-identical output either
    way — the schedule reorders the same write-once DMAs."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        axis=AXIS.EP,
        *,
        num_experts: int,
        num_selected: int = 2,
        capacity_factor: float = 1.25,
        wire: str = "auto",
        n_chunks: int = 1,
        wire_dtype: Optional[str] = None,
        a2a_sched: str = "off",
        a2a_traffic=None,
    ):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.axes = (axis,) if isinstance(axis, str) else tuple(axis)
        self.world = mesh_axis_size(self.mesh, self.axes)
        if num_experts % self.world:
            raise ValueError(
                f"num_experts {num_experts} must divide EP world {self.world}"
            )
        if wire not in ("auto", "ragged", "dense", "pallas"):
            raise ValueError(
                f"unknown wire {wire!r} (want 'auto', 'ragged', 'dense', or "
                "'pallas')"
            )
        if n_chunks < 0:
            raise ValueError(f"n_chunks must be >= 0 (0 = auto), got "
                             f"{n_chunks}")
        if a2a_sched not in ("off", "on", "auto"):
            raise ValueError(
                f"unknown a2a_sched {a2a_sched!r} (want 'off', 'on', or "
                "'auto')"
            )
        from uccl_tpu.ops import quant as _quant

        self.num_experts = num_experts
        self.num_local_experts = num_experts // self.world
        self.num_selected = num_selected
        self.capacity_factor = capacity_factor
        self.wire = wire
        self.n_chunks = n_chunks
        self.wire_dtype = _quant.resolve_wire_dtype(wire_dtype)
        # contention-aware a2a rounds (uccl_tpu.ep.a2a_sched): "on" always
        # rides the Birkhoff schedule on the pallas wire, "auto" lets the
        # planner arbitrate off the traffic skew. ``a2a_traffic`` is the
        # host [W, W] per-step routing matrix the schedule is built from
        # (a2a_sched.traffic_from_topk / zipf_topk; None = uniform, which
        # auto correctly answers with the fixed streams). Static per
        # Buffer — a new routing regime warrants a new matrix, i.e. a new
        # Buffer or an explicit re-assignment before the next dispatch.
        self.a2a_sched = a2a_sched
        self.a2a_traffic = (None if a2a_traffic is None
                            else np.asarray(a2a_traffic, float))
        self._cache = {}
        # host-path wire/chunk resolutions memoize per distinct config:
        # the fallback counter's contract is one event per compiled
        # program (collective/dma.py WIRE_FALLBACK), and these decisions
        # are static per (buffer, shape/knob tuple) — re-resolving them on
        # every verb call of a hot serving loop would re-count a single
        # decision thousands of times
        self._resolve_memo = {}
        # per-op stats (reference: EP Stats bound at uccl_ep.cc:2411 and the
        # dispatch_wait_recv_cost_stats tensor plumbed through
        # internode_ll.cu:66): op counters update eagerly; row/byte
        # aggregates are computed lazily from saved device refs in stats()
        self._op_counts = {
            "dispatch": 0, "combine": 0,
            "low_latency_dispatch": 0, "low_latency_combine": 0,
            "get_dispatch_layout": 0,
        }
        self._last_dispatch = None  # (topk_idx ref, capacity)
        self._last_ll = None  # (group_sizes ref, r_max, hidden, wire_fp8)
        # flight-bundle face (obs/flight.py): host-resident EP state only
        # — stats() syncs saved device refs, which a post-mortem dump
        # mid-failure must never do
        from uccl_tpu.obs import flight as _obsf

        _obsf.register_provider("ep_buffer", self._flight_state)

    def _flight_state(self) -> dict:
        return {
            "world": self.world,
            "num_experts": self.num_experts,
            "wire": self.wire,
            "wire_dtype": str(self.wire_dtype),
            "a2a_sched": self.a2a_sched,
            "ops": dict(self._op_counts),
        }

    # ------------------------------------------------------------------
    def _axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def _resolve_wire(self, requested, config) -> str:
        """Effective wire for a verb: explicit call value, else the Config,
        else the Buffer's."""
        wire = requested if requested is not None else "auto"
        if wire == "auto" and config is not None:
            wire = config.wire
        if wire == "auto":
            wire = self.wire
        return wire

    def _resolve_chunks(self, requested, config, wire: str) -> int:
        """Effective chunk-pipeline depth for a verb: explicit call value,
        else the Config, else the Buffer's. Collapses to 1 off the pallas
        wire or at world 1; 0 stays 0 (= auto) for the per-shard resolver,
        which also owns the double-buffer budget fallback."""
        n = requested
        if n is None and config is not None:
            n = config.n_chunks
        if n is None:
            n = self.n_chunks
        n = int(n)
        if n < 0:  # same contract as the Buffer constructor
            raise ValueError(f"n_chunks must be >= 0 (0 = auto), got {n}")
        if wire != "pallas" or self.world <= 1:
            # an EXPLICIT depth > 1 on the pallas wire collapsing at world
            # 1 is the same downgrade the per-shard resolvers record —
            # count it here too (once: the world is static per Buffer), or
            # counter coverage would depend on which call path resolved it
            if n > 1 and wire == "pallas" and self.world <= 1 \
                    and "chunks_world" not in self._resolve_memo:
                self._resolve_memo["chunks_world"] = True
                from uccl_tpu.collective import dma

                dma.record_fallback("buffer_verb", "world_size",
                                    detail=self.world)
            return 1
        return n

    def _resolve_wire_dtype(self, wire_dtype, wire_fp8, config,
                            default_fp8: bool = False):
        """Effective quantized wire payload for a verb: explicit
        ``wire_dtype`` keyword, else the explicit ``wire_fp8`` bool (True =
        "fp8", False = full precision), else the Config (its wire_dtype,
        then its wire_fp8), else the Buffer default, else ``default_fp8``
        (the LL verbs' legacy fp8-on default)."""
        from uccl_tpu.ops import quant as _quant

        if wire_dtype is not None:
            return _quant.resolve_wire_dtype(wire_dtype)
        if wire_fp8 is not None:
            return "fp8" if wire_fp8 else None
        if config is not None:
            if config.wire_dtype is not None:
                return _quant.resolve_wire_dtype(config.wire_dtype)
            if config.wire_fp8 is not None:
                return "fp8" if config.wire_fp8 else None
        if self.wire_dtype is not None:
            return self.wire_dtype
        return "fp8" if default_fp8 else None

    def _sched_chunk_charge(self, n_chunks: int, cap: int, slot_elems: int):
        """Per-chunk per-peer element count of the chunk-pipelined
        scheduled wire — pallas_a2a._scheduled_chunked's own arithmetic
        (slot axis padded to a chunk multiple), so plan_ep_a2a's budget
        probe charges exactly what the device gate will. None when the
        effective depth degenerates to 1 (monolithic gate applies)."""
        from uccl_tpu.collective import dma as _dma

        nc = min(int(n_chunks), int(cap))
        if nc <= 1:
            return None
        return int(slot_elems) * (_dma.pad_capacity(int(cap), nc) // nc)

    def _resolve_a2a_sched(self, config, wire: str, verb: str,
                           payload_shape, dtype, wire_dtype,
                           n_chunks: int = 1):
        """Effective round schedule for a verb's exchange, or None for the
        fixed streams: resolution Config > Buffer mode, then — on the
        pallas wire at world > 1 — the Birkhoff schedule is built from the
        Buffer's traffic matrix (combine sees it TRANSPOSED: traffic flows
        home) and either pinned ("on", recorded as an explicit plan) or
        arbitrated by the planner off the skew ("auto",
        CollectivePlanner.plan_ep_a2a). Memoized per static config — the
        decision, the plan counter event and the rounds/skew series fire
        once per compiled program, like every other host resolution."""
        mode = None
        if config is not None and config.a2a_sched is not None:
            mode = config.a2a_sched
        if mode is None:
            mode = self.a2a_sched
        if mode not in ("off", "on", "auto"):
            raise ValueError(
                f"unknown a2a_sched {mode!r} (want 'off', 'on', or 'auto')"
            )
        if mode == "off" or self.world <= 1:
            return None
        if wire != "pallas":
            # an explicit "on" off the pallas wire is a real downgrade (the
            # lax wire has no round order to steer) — counted once
            if mode == "on" and "a2a_sched_wire" not in self._resolve_memo:
                self._resolve_memo["a2a_sched_wire"] = True
                from uccl_tpu.collective import dma

                dma.record_fallback(
                    "ep_a2a_sched", "wire", detail=wire,
                    msg="a2a_sched='on' needs the pallas wire (XLA owns "
                        "the lax schedule); riding the fixed streams",
                )
            return None
        mat = self.a2a_traffic
        if mat is None:
            mat = np.ones((self.world, self.world), float)
            np.fill_diagonal(mat, 0.0)
        mat = np.asarray(mat, float)
        if verb == "combine":
            mat = mat.T
        key = ("a2a_sched", mode, verb, tuple(payload_shape),
               jnp.dtype(dtype).name, wire_dtype, n_chunks, mat.tobytes())
        if key in self._resolve_memo:
            return self._resolve_memo[key]
        from uccl_tpu.collective.plan import get_planner
        from uccl_tpu.ep import a2a_sched as _sched

        schedule = _sched.wire_schedule(mat, self.world)
        n_rounds = len(schedule[0])
        planner = get_planner()
        if mode == "on":
            planner.plan_explicit("ep_sched", payload_shape, dtype,
                                  self.world, wire_dtype=wire_dtype,
                                  verb="ep_a2a")
            algo = "ep_sched"
        else:
            # the wire buffer is [W, E_local, C, H] for both verbs; its
            # chunked slot axis is C, so the per-chunk per-peer charge
            # (what _scheduled_chunked's gate checks) is E_local*cs*H.
            # dispatch's payload_shape is that buffer; combine's is the
            # [E_local, W*C, H] expert view of the same bytes.
            elems = int(np.prod(payload_shape))
            cap = int(payload_shape[-2])
            if verb != "dispatch":
                cap //= self.world
            slot_elems = elems // self.world // max(cap, 1)
            cep = (self._sched_chunk_charge(n_chunks, cap, slot_elems)
                   if n_chunks > 1 and cap else None)
            algo = planner.plan_ep_a2a(
                payload_shape, dtype, self.world,
                skew=_sched.skew(mat), n_rounds=n_rounds,
                wire_dtype=wire_dtype,
                n_chunks=n_chunks if cep is not None else 1,
                chunk_elems_per_peer=cep,
            ).algo
        _sched.record_decision(
            algo, self.world,
            n_rounds=n_rounds if algo == "ep_sched" else None,
            matrix=mat,
        )
        result = schedule if algo == "ep_sched" else None
        self._resolve_memo[key] = result
        return result

    def _spec(self, extra_dims: int) -> P:
        return P(self.axes, *([None] * extra_dims))

    def _jit(self, key, fn, n_in_extra, n_out_extra):
        cached = self._cache.get(key)
        if cached is None:
            in_specs = tuple(self._spec(d) for d in n_in_extra)
            out_specs = jax.tree.map(lambda d: self._spec(d), n_out_extra)
            cached = jax.jit(
                shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False,
                )
            )
            self._cache[key] = cached
        return cached

    def stats(self) -> dict:
        """Per-op EP stats (reference: the `Stats` class bound at
        uccl_ep.cc:2411 + the dispatch cost tensors internode_ll.cu:66):
        op counters plus aggregates of the LAST dispatch of each mode —
        routed/kept/dropped rows for the capacity path (computed from the
        routing demand vs capacity, the exact drop rule of the sorted
        layout), and recv rows + approximate wire payload bytes for the
        low-latency path. Reading materializes saved device values (a sync
        point) — call it off the hot loop, like the reference's stats
        thread."""
        out = {"ops": dict(self._op_counts)}
        if self._last_dispatch is not None:
            idx, cap = self._last_dispatch
            idx_np = np.asarray(idx)  # [W, T, K]
            # capacity bounds each SOURCE shard's rows per expert (the
            # sorted layout assigns cap slots per expert per shard), so the
            # drop rule applies shard-wise before summing
            routed = kept = 0
            for r in range(idx_np.shape[0]):
                flat = idx_np[r].reshape(-1)
                # -1 = "no expert" (DeepEP-supported): claims no slot, so it
                # must not be counted as expert-0 demand
                flat = flat[flat >= 0]
                d = np.bincount(flat, minlength=self.num_experts)
                routed += int(d.sum())
                kept += int(np.minimum(d, cap).sum())
            out["dispatch"] = {
                "capacity": int(cap),
                "routed_rows": routed,
                "kept_rows": kept,
                "dropped_rows": routed - kept,
                "drop_fraction": float((routed - kept) / max(1, routed)),
            }
        if self._last_ll is not None:
            counts, r_max, hidden, wire_fp8 = self._last_ll
            rows = int(np.asarray(counts).sum())
            payload = hidden * (1 if wire_fp8 else 2)
            out["low_latency"] = {
                "recv_rows": rows,
                "r_max_per_rank": int(r_max),
                "wire_payload_bytes": rows * payload,
            }
        return out

    @staticmethod
    def get_dispatch_config(num_ranks: int) -> Config:
        """Recommended dispatch config per EP world size (the role of
        ep/bench/buffer.py:741 ``get_dispatch_config``). Small worlds ride
        the ragged wire; larger worlds shrink the dense-wire pair capacity
        so padded slots don't dominate the exchanged volume."""
        if num_ranks <= 8:
            return Config(wire="auto", wire_fp8=True)
        if num_ranks <= 32:
            return Config(wire="auto", wire_fp8=True,
                          pair_capacity_factor=1.0)
        return Config(wire="auto", wire_fp8=True, pair_capacity_factor=0.75)

    @staticmethod
    def get_combine_config(num_ranks: int) -> Config:
        """Recommended combine config per EP world size (reference
        get_combine_config, ep/bench/buffer.py:771), consumable by the
        normal-mode :meth:`combine` ``config=`` parameter. Combine payloads
        stay bf16/f32 (gate weights are applied at the destination, so fp8
        error would be amplified by the reduction), hence wire_fp8=False."""
        cfg = Buffer.get_dispatch_config(num_ranks)
        return dataclasses.replace(cfg, wire_fp8=False)

    def capacity(self, num_tokens: int) -> int:
        return max(
            1,
            int(
                self.capacity_factor
                * num_tokens
                * self.num_selected
                / self.num_experts
            ),
        )

    def device_put(self, x) -> jax.Array:
        x = jnp.asarray(x)
        return jax.device_put(
            x, NamedSharding(self.mesh, self._spec(x.ndim - 1))
        )

    # ------------------------------------------------------------------
    def get_dispatch_layout(self, topk_idx: jax.Array):
        """topk_idx: [W, T, K] global expert ids.

        Returns (num_tokens_per_rank [W, W], num_tokens_per_expert [W, E],
        is_token_in_rank [W, T, W]) — the counting contract of the reference's
        get_dispatch_layout (ep/bench/buffer.py:797) minus the CUDA event.
        """
        e, w = self.num_experts, self.world
        e_local = self.num_local_experts
        key = ("layout", topk_idx.shape)

        def f(idx):
            idx = idx[0]  # [T, K]
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [T, K, E]
            per_expert = jnp.sum(onehot, axis=(0, 1))  # [E]
            per_rank_tok = (
                jnp.sum(onehot, axis=1).reshape(-1, w, e_local).sum(-1) > 0
            )  # [T, W] token touches rank
            per_rank = jnp.sum(per_rank_tok.astype(jnp.int32), axis=0)  # [W]
            return (
                per_rank[None],
                per_expert[None],
                per_rank_tok[None],
            )

        fn = self._jit(key, f, (2,), (1, 1, 2))
        self._op_counts["get_dispatch_layout"] += 1
        return fn(topk_idx)

    def dispatch(
        self,
        x: jax.Array,
        topk_idx: jax.Array,
        topk_weights: Optional[jax.Array] = None,
        *,
        wire_fp8: Optional[bool] = None,
        wire_dtype: Optional[str] = None,
        config: Optional[Config] = None,
        previous_event: Optional[EventOverlap] = None,
        async_finish: bool = False,
        allocate_on_comm_stream: bool = False,
    ):
        """x: [W, T, H]; topk_idx: [W, T, K]; topk_weights: [W, T, K] (defaults
        to uniform 1/K). Returns (recv_x [W, E_local, W*C, H], handle), plus
        an :class:`EventOverlap` when ``async_finish`` is set.

        ``wire_dtype`` ("fp8" | "int8") block-quantizes the wire payload
        (``wire_fp8=True`` is the legacy spelling of "fp8"; resolution:
        explicit keyword > Config > Buffer default).

        Overlap knobs (reference dispatch, ep/bench/buffer.py:801-824):
        ``config`` fills wire knobs the caller left unset (explicit keywords
        win); ``previous_event`` orders this dispatch after another verb's
        event by dataflow; ``async_finish`` returns an event to wait on /
        chain from; ``allocate_on_comm_stream`` is stream-allocator
        bookkeeping with no TPU meaning — accepted (with the reference's own
        precondition) and otherwise a no-op, since XLA owns allocation."""
        wire_dtype = self._resolve_wire_dtype(wire_dtype, wire_fp8, config)
        if allocate_on_comm_stream and not (
            previous_event is not None and async_finish
        ):
            raise ValueError(
                "allocate_on_comm_stream requires previous_event and "
                "async_finish (reference precondition, buffer.py:826)"
            )
        # "pallas" = device-initiated remote-DMA all-to-all; else the XLA
        # collective ("ragged"/"dense" are LL-layout knobs, not this path's)
        wire = (
            "pallas" if self._resolve_wire(None, config) == "pallas"
            else "lax"
        )
        w, t, h = x.shape
        k = topk_idx.shape[-1]
        cap = self.capacity(t)
        e = self.num_experts
        n_chunks = self._resolve_chunks(None, config, wire)
        if n_chunks != 1:
            # memoized: resolve_chunks records budget/capacity fallbacks,
            # and this host call repeats per dispatch() of one static
            # config — count once, like the traced (per-compile) gates
            rkey = ("chunks", n_chunks, wire, cap, h, wire_dtype,
                    jnp.dtype(x.dtype).name)
            if rkey not in self._resolve_memo:
                self._resolve_memo[rkey] = ep_ops.resolve_chunks(
                    n_chunks, wire, self.world, cap,
                    self.num_local_experts, h,
                    ep_ops.wire_itemsize(False, h, x.dtype,
                                         wire_dtype=wire_dtype),
                    wire_dtype=wire_dtype,
                )
            n_chunks = self._resolve_memo[rkey]
        schedule = self._resolve_a2a_sched(
            config, wire, "dispatch",
            (self.world, self.num_local_experts, cap, h), x.dtype,
            wire_dtype, n_chunks=n_chunks,
        )
        has_ev = previous_event is not None
        tok = previous_event.token if has_ev else None
        key = ("dispatch", x.shape, topk_idx.shape, wire_dtype, x.dtype,
               wire, n_chunks, has_ev and (tok.shape, tok.dtype),
               schedule is not None
               and (tuple(schedule[0]), schedule[1].tobytes()))

        def f(xv, idx, *tok_arg):
            xv, idx = xv[0], idx[0]
            if tok_arg:
                xv = _tie(xv, tok_arg[0])
            # sorted/ragged layout (the fast path): ONE argsort per routing
            # decision builds the SlotPlan both sides of the layer consume;
            # dispatch is a gather; drops match the dense oracle exactly
            # (ep/ops.py)
            plan = ep_ops.plan_slots(idx, e, cap)
            slot, kept = plan.slot, plan.kept
            recv = ep_ops.dispatch_sorted(
                xv, plan, e, cap, self._axis_name(),
                wire_dtype=wire_dtype, wire=wire, n_chunks=n_chunks,
                schedule=schedule,
            )
            # per-(source, local-expert) received-row counts: kept[E] is MY
            # contribution per global expert; the all_to_all hands each
            # member row s = source s's counts for ITS experts (the same
            # counts exchange as the LL path's recv_mat). Always on — the
            # DeepEP handle always carries receive bookkeeping, and the
            # [W, E_local] int32 exchange is launch-latency-only next to
            # the payload all_to_all it accompanies.
            rc = ep_ops.counts_exchange(
                kept.reshape(-1, self.num_local_experts).astype(jnp.int32),
                self._axis_name(),
            )
            return recv[None], slot[None], rc[None]

        if topk_weights is None:
            topk_weights = jnp.full(topk_idx.shape, 1.0 / k, jnp.float32)
        extra_in = (2, 2) + ((tok.ndim - 1,) if has_ev else ())
        fn = self._jit(key, f, extra_in, (3, 2, 2))
        args = (x, topk_idx) + ((tok,) if has_ev else ())
        recv, slot, recv_counts = _observed_call(
            "dispatch", fn, args, wire=wire, n_chunks=n_chunks, payload=x,
            wire_dtype=wire_dtype,
        )
        self._op_counts["dispatch"] += 1
        self._last_dispatch = (topk_idx, cap)
        # weights go straight into the handle (combine reshards them itself)
        handle = DispatchHandle(slot, topk_weights, recv_counts, wire,
                                n_chunks, wire_dtype,
                                schedule is not None)
        if async_finish:
            return recv, handle, EventOverlap((recv, slot, recv_counts))
        return recv, handle

    def combine(
        self,
        expert_out: jax.Array,
        handle: DispatchHandle,
        *,
        wire_fp8: Optional[bool] = None,
        wire_dtype: Optional[str] = None,
        config: Optional[Config] = None,
        previous_event: Optional[EventOverlap] = None,
        async_finish: bool = False,
        allocate_on_comm_stream: bool = False,
    ):
        """expert_out: [W, E_local, W*C, H] → [W, T, H] (plus an
        :class:`EventOverlap` when ``async_finish``); overlap knobs as in
        :meth:`dispatch` (``config``: see :meth:`get_combine_config`). The
        reverse exchange rides the wire (and chunk depth) the handle's
        dispatch used; ``wire_dtype`` resolves independently of dispatch's
        (explicit keyword > Config > Buffer default — combine error is
        amplified by the gate weights, so get_combine_config keeps the
        return path full-precision even under an fp8 dispatch Config)."""
        wire_dtype = self._resolve_wire_dtype(wire_dtype, wire_fp8, config)
        if allocate_on_comm_stream and not (
            previous_event is not None and async_finish
        ):
            raise ValueError(
                "allocate_on_comm_stream requires previous_event and "
                "async_finish (reference precondition, buffer.py:826)"
            )
        wire = handle.wire
        n_chunks = handle.n_chunks  # retrace dispatch's chunking exactly
        schedule = None
        if handle.a2a_sched:
            # dispatch rode the scheduled rounds: the return exchange is
            # the transposed traffic (every row flows home), so combine
            # rebuilds its own schedule, arbitrated for ITS direction (row
            # and column skew differ on asymmetric matrices)
            schedule = self._resolve_a2a_sched(
                config, wire, "combine", expert_out.shape[1:],
                expert_out.dtype, wire_dtype, n_chunks=n_chunks,
            )
        has_ev = previous_event is not None
        tok = previous_event.token if has_ev else None
        key = ("combine", expert_out.shape, handle.slot.shape, wire_dtype,
               wire, n_chunks, has_ev and (tok.shape, tok.dtype),
               schedule is not None
               and (tuple(schedule[0]), schedule[1].tobytes()))

        def f(y, slot, wts, *tok_arg):
            if tok_arg:
                y = _tie(y, tok_arg[0])
            out = ep_ops.combine_sorted(
                y[0], slot[0], wts[0], self._axis_name(),
                wire_dtype=wire_dtype, wire=wire, n_chunks=n_chunks,
                schedule=schedule,
            )
            return out[None]

        extra_in = (3, 2, 2) + ((tok.ndim - 1,) if has_ev else ())
        fn = self._jit(key, f, extra_in, 2)
        self._op_counts["combine"] += 1
        args = (expert_out, handle.slot, handle.weights) + (
            (tok,) if has_ev else ()
        )
        out = _observed_call(
            "combine", fn, args, wire=wire, n_chunks=n_chunks,
            payload=expert_out, wire_dtype=wire_dtype,
        )
        if async_finish:
            return out, EventOverlap(out)
        return out

    # -- low-latency mode: packed fp8 payloads + recv counts -------------
    def low_latency_dispatch(
        self,
        x: jax.Array,
        topk_idx: jax.Array,
        num_max_dispatch_tokens_per_rank: Optional[int] = None,
        topk_weights: Optional[jax.Array] = None,
        *,
        pair_capacity_factor: Optional[float] = None,
        wire: str = "auto",
        wire_fp8: Optional[bool] = None,
        wire_dtype: Optional[str] = None,
        n_chunks: Optional[int] = None,
        config: Optional[Config] = None,
        previous_event: Optional[EventOverlap] = None,
        async_finish: bool = False,
        return_recv_hook: bool = False,
    ):
        """The DeepEP low-latency contract (ep/bench/buffer.py:285-454):
        packed per-expert buffers sized by ``num_max_dispatch_tokens_per_rank``
        plus per-expert receive counts, fp8 on the wire.

        x: [W, T, H]; topk_idx: [W, T, K] — entries of ``-1`` mean "no
        expert" (DeepEP-supported): such assignments claim no wire slot and
        contribute zero in combine. Returns
        (recv_x [W, R_max, H] group-major packed,
         recv_count [W, E_local],
         handle) — the consumer feeds (recv_x, recv_count) straight into
        grouped GEMMs (:func:`uccl_tpu.ep.ll.grouped_ffn`) so neither wire
        nor MXU touches padding.

        Overlap knobs (reference LL dispatch, ep/bench/buffer.py:285-346):
        ``config`` supplies defaults for the wire/sizing knobs
        (:class:`Config`, see get_dispatch_config); ``previous_event``
        orders this verb after another's event; ``async_finish`` /
        ``return_recv_hook`` switch the return to the reference's 5-tuple
        ``(recv_x, recv_count, handle, event, hook)`` — the hook is the
        two-phase receive: the dispatch is issued asynchronously and
        ``hook()`` blocks until the receive buffers have landed (on GPU the
        unhooked kernel skips the receive entirely; on TPU arrival is the
        XLA program itself, so the hook is the explicit arrival barrier)."""
        # the quantized-payload knob resolves through the one Buffer rule
        # (explicit wire_dtype/wire_fp8 > Config > Buffer default), with
        # the LL legacy default of fp8-on (internode_ll.cu's fp8 wire)
        wire_dtype = self._resolve_wire_dtype(wire_dtype, wire_fp8, config,
                                              default_fp8=True)
        if config is not None:
            if num_max_dispatch_tokens_per_rank is None:
                num_max_dispatch_tokens_per_rank = config.max_tokens_per_rank
            if pair_capacity_factor is None:
                pair_capacity_factor = config.pair_capacity_factor
            if wire == "auto":
                wire = config.wire
        w, t, h = x.shape
        k = topk_idx.shape[-1]
        # Buffer-level default + the pallas addressability gate (config was
        # already applied by the fill block above)
        wire = self._resolve_wire(wire, None)
        if wire == "auto":
            wire = "ragged" if ep_ll.wire_supports_ragged() else "dense"
        # resolve the chunk depth HERE (the shared ll rule) so the handle
        # records exactly the depth dispatch traced with
        per_pair, _ = ep_ll.ll_bounds(
            t, k, self.num_local_experts, self.world,
            num_max_dispatch_tokens_per_rank, pair_capacity_factor,
        )
        n_chunks = ep_ll.resolve_ll_chunks(
            self._resolve_chunks(n_chunks, config, wire), wire, self.world,
            per_pair,
        )
        if topk_weights is None:
            topk_weights = jnp.full(topk_idx.shape, 1.0 / k, jnp.float32)
        has_ev = previous_event is not None
        tok = previous_event.token if has_ev else None
        key = (
            "ll_dispatch", x.shape, topk_idx.shape, x.dtype,
            num_max_dispatch_tokens_per_rank, pair_capacity_factor, wire,
            wire_dtype, n_chunks, has_ev and (tok.shape, tok.dtype),
        )

        def f(xv, idx, wts, *tok_arg):
            if tok_arg:
                xv = _tie(xv, tok_arg[0])
            r = ep_ll.ll_dispatch(
                xv[0], idx[0], wts[0], self.num_experts, self._axis_name(),
                num_max_dispatch_tokens_per_rank=(
                    num_max_dispatch_tokens_per_rank
                ),
                pair_capacity_factor=pair_capacity_factor,
                wire=wire, wire_fp8=False, wire_dtype=wire_dtype,
                n_chunks=n_chunks,
            )
            s = r.state
            return (
                r.recv_x[None], r.group_sizes[None], s.send_slot[None],
                s.weights[None], s.send_mat[None], s.recv_mat[None],
                s.regroup[None], s.src_in_offsets[None],
            )

        extra_in = (2, 2, 2) + ((tok.ndim - 1,) if has_ev else ())
        fn = self._jit(key, f, extra_in, (2, 1, 2, 2, 2, 2, 1, 1))
        args = (x, topk_idx, topk_weights) + ((tok,) if has_ev else ())
        (recv_x, counts, send_slot, weights, send_mat, recv_mat, regroup,
         src_in_offsets) = _observed_call(
            "low_latency_dispatch", fn, args, wire=wire, n_chunks=n_chunks,
            payload=x, wire_dtype=wire_dtype,
        )
        handle = LowLatencyHandle(
            send_slot, weights, send_mat, recv_mat, regroup,
            src_in_offsets, wire, wire_dtype == "fp8", n_chunks, wire_dtype,
        )
        self._op_counts["low_latency_dispatch"] += 1
        self._last_ll = (counts, recv_x.shape[1], x.shape[-1],
                         wire_dtype is not None)
        if async_finish or return_recv_hook:
            event = EventOverlap((recv_x, counts)) if async_finish else None
            hook: Optional[Callable[[], None]] = (
                (lambda: jax.block_until_ready((recv_x, counts)))
                if return_recv_hook else None
            )
            return recv_x, counts, handle, event, hook
        return recv_x, counts, handle

    def low_latency_combine(
        self,
        expert_out: jax.Array,
        handle: LowLatencyHandle,
        *,
        previous_event: Optional[EventOverlap] = None,
        async_finish: bool = False,
        return_recv_hook: bool = False,
    ):
        """expert_out: [W, R_max, H] group-major → [W, T, H]; with
        ``async_finish``/``return_recv_hook`` set, returns the reference's
        ``(combined_x, event, hook)`` triple (ep/bench/buffer.py:454-530)."""
        # pre-quant pickled handles carry wire_dtype=None + the legacy
        # wire_fp8 bool — the resolution every reader must apply
        wire_dtype = handle.wire_dtype or (
            "fp8" if handle.wire_fp8 else None
        )
        has_ev = previous_event is not None
        tok = previous_event.token if has_ev else None
        key = (
            "ll_combine", expert_out.shape, handle.send_slot.shape,
            expert_out.dtype, handle.wire, wire_dtype,
            handle.n_chunks, has_ev and (tok.shape, tok.dtype),
        )

        def f(y, send_slot, wts, send_mat, recv_mat, regroup, src_off,
              *tok_arg):
            if tok_arg:
                y = _tie(y, tok_arg[0])
            state = ep_ll.LLState(
                send_slot[0], wts[0], send_mat[0], recv_mat[0],
                regroup[0], src_off[0], handle.wire, handle.n_chunks,
            )
            out = ep_ll.ll_combine(
                y[0], state, self._axis_name(), wire_fp8=False,
                wire_dtype=wire_dtype,
            )
            return out[None]

        extra_in = (2, 2, 2, 2, 2, 1, 1) + ((tok.ndim - 1,) if has_ev else ())
        fn = self._jit(key, f, extra_in, 2)
        self._op_counts["low_latency_combine"] += 1
        args = (
            expert_out, handle.send_slot, handle.weights, handle.send_mat,
            handle.recv_mat, handle.regroup, handle.src_in_offsets,
        ) + ((tok,) if has_ev else ())
        out = _observed_call(
            "low_latency_combine", fn, args, wire=handle.wire,
            n_chunks=handle.n_chunks, payload=expert_out,
            wire_dtype=wire_dtype,
        )
        if async_finish or return_recv_hook:
            event = EventOverlap(out) if async_finish else None
            hook: Optional[Callable[[], None]] = (
                (lambda: jax.block_until_ready(out))
                if return_recv_hook else None
            )
            return out, event, hook
        return out
