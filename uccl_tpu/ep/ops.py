"""Per-shard MoE expert-parallel primitives (use inside shard_map).

Capacity-based top-k routing + all-to-all dispatch/combine, the TPU-native
re-design of the reference's EP kernels (ep/src/internode_ll.cu dispatch:62 /
combine:747 pack per-expert token messages and RDMA them via a CPU proxy;
ep/src/layout.cu computes the dispatch layout). Here the same contracts are
static-shape einsums + ``lax.all_to_all`` so XLA can schedule the exchange on
ICI and keep the expert GEMMs on the MXU:

* :func:`route_topk`   — top-k gating with per-expert capacity, position
  assignment, load-balance + z losses (= get_dispatch_layout's counting,
  ep/bench/buffer.py:797, done as cumsums).
* :func:`dispatch`     — [T,H] tokens → [E_local, W*C, H] per-expert buffers on
  the owning EP member (= Buffer.dispatch).
* :func:`combine`      — weighted return path (= Buffer.combine).

Two implementations of the same contract:

* **dense** (``dispatch``/``combine``): one-hot ``[T,E,C]`` mask einsums —
  simple, always correct, kept as the oracle. Cost O(T·E·C·H) FLOPs.
* **sorted** (``dispatch_sorted``/``combine_sorted``): the fast path — a
  k-major stable argsort by expert id assigns capacity slots, dispatch is one
  [E·C, H]-row gather and combine a [T,K]-row gather, so cost is O(T·K·H)
  data movement with no mask tensor at all. This is the TPU re-design of the
  reference's ragged message packing (ep/src/internode_ll.cu:62 packs per-
  expert token messages; ep/src/layout.cu computes the layout): the argsort
  plays the role of the layout kernel, the gathers the role of the pack/unpack
  copies. Drop priority is identical to the dense path (earlier k-slots fill
  expert queues first, then token order), so the two paths agree exactly —
  including which tokens drop — at any capacity.

Token layout convention: ``E`` global experts, EP world ``W``, ``E_local=E/W``
experts per member, per-member capacity ``C`` tokens per expert per source
member. Dropped tokens (over capacity) contribute zero, matching
drop-and-renormalize MoE training semantics. ``C`` comes from
:func:`expert_capacity`: ``capacity_factor`` times the balanced share, and
never more than the source's own token count — top-k picks distinct experts
per token, so a longer queue could not be filled by any routing.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from uccl_tpu.collective import dma as _dma
from uccl_tpu.ops import quant as _quant
from uccl_tpu.ops.quant import dequantize_block, quantize_block

# checkpoint_name tags on the expert-GEMM operands/results, shared by the
# sort/dense path here, the ll path (ep.ll.grouped_ffn), and the
# remat="mlp" save policy (models.flagship._remat_wrap). A drifted name
# fails SILENTLY (the policy just stops matching and the memory win
# evaporates), so every site must import this tuple.
MOE_CHECKPOINT_NAMES = ("moe_xe", "moe_hg", "moe_hu", "moe_ye")
_XE, _HG, _HU, _YE = MOE_CHECKPOINT_NAMES

Axis = Union[str, Tuple[str, ...]]


class Routing(NamedTuple):
    """Routing decision for one shard's tokens."""

    dispatch_mask: jax.Array  # [T, E, C] one-hot slot assignment (bool)
    combine_weights: jax.Array  # [T, E, C] f32 gate weights at assigned slots
    aux_loss: jax.Array  # load-balance loss (scalar)
    z_loss: jax.Array  # router z-loss (scalar)
    counts: jax.Array  # [E] tokens kept per expert (before capacity the raw
    # demand is counts_raw; kept counts reflect drops)


GATES = ("softmax", "sigmoid_bias")


def _gate_topk(router_logits, num_selected: int, renormalize: bool,
               gate: str = "softmax", bias=None, scale: float = 1.0):
    """THE gate, shared by every routing impl (``sort``, ``dense``, ``ll``
    and the chunk-pipelined layer): scores, top-k choice, weights, losses.

    ``softmax``: softmax gates, z-loss, (renormalized) top-k selection,
    GShard load-balance loss. ``sigmoid_bias``: per-expert sigmoid scores in
    float32; the k experts are CHOSEN by ``score + bias`` (``bias`` [E], the
    auxiliary-loss-free balancing term) and WEIGHTED by the score alone,
    renormalised over the chosen and scaled by ``scale`` — no auxiliary
    losses (both zero). ``scale`` multiplies the weights of either gate.
    Returns (topk_vals [T,K], topk_idx [T,K], aux_loss, z_loss)."""
    e = router_logits.shape[-1]
    logits32 = router_logits.astype(jnp.float32)
    if gate == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits32)  # [T, E]
        choose = scores if bias is None else scores + bias.astype(jnp.float32)
        _, topk_idx = lax.top_k(choose, num_selected)
        topk_vals = jnp.take_along_axis(scores, topk_idx, axis=-1)
        if renormalize:
            topk_vals = topk_vals / (
                jnp.sum(topk_vals, axis=-1, keepdims=True) + 1e-20
            )
        zero = jnp.zeros((), jnp.float32)
        return topk_vals * scale, topk_idx, zero, zero
    if gate != "softmax":
        raise ValueError(f"unknown gate {gate!r} (want one of {GATES})")
    gates = jax.nn.softmax(logits32, axis=-1)  # [T, E]
    # z-loss stabilizes router logits; load-balance loss follows GShard.
    z = jax.nn.logsumexp(logits32, axis=-1)
    z_loss = jnp.mean(z * z)

    topk_vals, topk_idx = lax.top_k(gates, num_selected)  # [T, K]
    if renormalize:
        topk_vals = topk_vals / jnp.maximum(
            jnp.sum(topk_vals, axis=-1, keepdims=True), 1e-9
        )
    if scale != 1.0:
        topk_vals = topk_vals * scale

    # GShard load-balance loss: E * mean(fraction routed) . mean(gate prob)
    me = jnp.mean(gates, axis=0)  # [E]
    raw_onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [T, K, E]
    ce = jnp.mean(jnp.sum(raw_onehot, axis=1), axis=0)  # [E] fraction demand
    aux_loss = jnp.sum(me * ce) * (e / num_selected)
    return topk_vals, topk_idx, aux_loss, z_loss


def route_topk(
    router_logits: jax.Array,
    num_selected: int,
    capacity: int,
    *,
    renormalize: bool = True,
    gate: str = "softmax",
    gate_bias=None,
    routed_scale: float = 1.0,
) -> Routing:
    """Top-k gating with per-expert capacity and in-expert position assignment.

    router_logits: [T, E]. Returns masks/weights of shape [T, E, C].
    ``gate``/``gate_bias``/``routed_scale``: :func:`_gate_topk`.
    """
    e = router_logits.shape[-1]
    topk_vals, topk_idx, aux_loss, z_loss = _gate_topk(
        router_logits, num_selected, renormalize, gate, gate_bias,
        routed_scale,
    )
    dispatch, combine, counts_running = masks_from_topk(
        topk_idx, topk_vals, e, capacity
    )
    return Routing(dispatch, combine, aux_loss, z_loss, counts_running)


def masks_from_topk(
    idx: jax.Array, wts: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build [T,E,C] dispatch/combine masks from explicit top-k assignments.

    Position assignment is sequential over the k slots so earlier choices fill
    expert queues first; over-capacity assignments drop (zero contribution).
    Returns (dispatch_mask bool, combine_weights f32, kept counts [E]).
    """
    t, k = idx.shape
    counts = jnp.zeros((num_experts,), jnp.int32)
    dispatch = jnp.zeros((t, num_experts, capacity), jnp.bool_)
    combine = jnp.zeros((t, num_experts, capacity), jnp.float32)
    for j in range(k):
        onehot = jax.nn.one_hot(idx[:, j], num_experts, dtype=jnp.int32)  # [T,E]
        # position of each token inside its expert's queue for this k-slot,
        # continuing from tokens already placed by earlier k-slots
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        keep = (pos < capacity) & (onehot > 0)
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.bool_)  # [T,E,C]
        d_j = slot & keep[..., None]
        dispatch = dispatch | d_j
        combine = combine + d_j.astype(jnp.float32) * wts[:, j][:, None, None]
        counts = counts + jnp.sum(keep.astype(jnp.int32), axis=0)
    return dispatch, combine, counts


class SortedRouting(NamedTuple):
    """Routing decision in sorted/ragged form (no [T,E,C] mask tensor)."""

    token_for_slot: jax.Array  # [E*C] int32 source token per slot (T = empty)
    slot: jax.Array  # [T, K] int32 slot per assignment (E*C = dropped)
    weights: jax.Array  # [T, K] f32 gate weights (renormalized)
    aux_loss: jax.Array  # load-balance loss (scalar)
    z_loss: jax.Array  # router z-loss (scalar)
    counts: jax.Array  # [E] tokens kept per expert


def counts_exchange(mat, axis):
    """[W, ...] per-destination rows → [W, ...] per-source rows (row s of
    the result is what source s computed for me). The counts/offsets
    exchange both dispatch paths use for receive bookkeeping (sorted-path
    recv_counts, LL recv_mat/offsets)."""
    return lax.all_to_all(mat, axis, split_axis=0, concat_axis=0, tiled=True)


def sorted_from_topk(
    idx: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Slot assignment from explicit top-k expert ids via one stable argsort.

    idx: [T, K]. Flattening is k-major so earlier k-slots fill expert queues
    first (then token order) — byte-identical drop semantics to
    :func:`masks_from_topk`. Returns (token_for_slot [E*C] with T as the
    empty sentinel, slot [T, K] with E*C as the dropped sentinel,
    kept counts [E]).
    """
    t, k = idx.shape
    tk = t * k
    flat_e = idx.T.reshape(tk)  # k-major
    flat_t = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_t = flat_t[order]
    counts = jnp.bincount(flat_e, length=num_experts)  # [E] raw demand
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos = jnp.arange(tk, dtype=jnp.int32) - seg_start[sorted_e].astype(jnp.int32)
    keep = pos < capacity
    slot_sorted = jnp.where(
        keep, sorted_e * capacity + pos, num_experts * capacity
    ).astype(jnp.int32)
    slot = (
        jnp.zeros((tk,), jnp.int32).at[order].set(slot_sorted).reshape(k, t).T
    )
    # Inverse view: which sorted position feeds slot (e, p)?
    slot_ids = jnp.arange(num_experts * capacity, dtype=jnp.int32)
    e_of_slot = slot_ids // capacity
    p_of_slot = slot_ids % capacity
    j = seg_start[e_of_slot].astype(jnp.int32) + p_of_slot
    kept = jnp.minimum(counts, capacity).astype(jnp.int32)
    valid = p_of_slot < kept[e_of_slot]
    token_for_slot = jnp.where(
        valid, sorted_t[jnp.clip(j, 0, tk - 1)], t
    ).astype(jnp.int32)
    return token_for_slot, slot, kept


class SlotPlan(NamedTuple):
    """The slot permutation of ONE routing decision, computed once and
    consumed by BOTH sides of the layer: dispatch gathers payload rows with
    ``token_for_slot`` (the forward permutation), combine gathers returned
    rows with ``slot`` (its inverse). Both views come out of the single
    stable argsort in :func:`sorted_from_topk`; building the plan once per
    routing decision (instead of re-deriving index math on each side) is
    what keeps the two sides structurally unable to disagree on drops —
    and gives the chunk-pipelined layer one shared index set to slice."""

    token_for_slot: jax.Array  # [E*C] int32 source token per slot (T = empty)
    slot: jax.Array  # [T, K] int32 slot per assignment (E*C = dropped)
    kept: jax.Array  # [E] int32 tokens kept per expert

    def chunk_token_for_slot(self, num_experts: int, n_chunks: int,
                             empty_sentinel: int) -> jax.Array:
        """Per-chunk gather indices for the pipelined layer: the [E*C] slot
        axis padded (``dma.pad_capacity`` — the shared rounding rule) with
        empty slots and resliced to [n_chunks, E * C_pad/n_chunks]. Padding
        lives only on the wire; it never changes which tokens drop."""
        cap = self.token_for_slot.shape[0] // num_experts
        cap_p = _dma.pad_capacity(cap, n_chunks)
        tfs = self.token_for_slot.reshape(num_experts, cap)
        if cap_p != cap:
            tfs = jnp.pad(tfs, ((0, 0), (0, cap_p - cap)),
                          constant_values=empty_sentinel)
        cs = cap_p // n_chunks
        return tfs.reshape(num_experts, n_chunks, cs).transpose(1, 0, 2)


def plan_slots(
    topk_idx: jax.Array, num_experts: int, capacity: int
) -> SlotPlan:
    """One argsort → the reusable :class:`SlotPlan` for a routing decision
    (dispatch- and combine-side gather indices plus kept counts)."""
    return SlotPlan(*sorted_from_topk(topk_idx, num_experts, capacity))


def route_topk_sorted(
    router_logits: jax.Array,
    num_selected: int,
    capacity: int,
    *,
    renormalize: bool = True,
    gate: str = "softmax",
    gate_bias=None,
    routed_scale: float = 1.0,
) -> SortedRouting:
    """Top-k gating in sorted/ragged form — same math and losses as
    :func:`route_topk`, without materializing [T,E,C] masks."""
    e = router_logits.shape[-1]
    topk_vals, topk_idx, aux_loss, z_loss = _gate_topk(
        router_logits, num_selected, renormalize, gate, gate_bias,
        routed_scale,
    )
    token_for_slot, slot, kept = sorted_from_topk(topk_idx, e, capacity)
    return SortedRouting(token_for_slot, slot, topk_vals, aux_loss, z_loss, kept)


def dispatch_sorted(
    x: jax.Array,
    token_for_slot,
    num_experts: int,
    capacity: int,
    axis: Axis,
    *,
    wire_fp8: bool = False,
    quant_group: int = 128,
    wire: str = "lax",
    n_chunks: int = 1,
    wire_dtype=None,
    schedule=None,
) -> jax.Array:
    """Ragged dispatch: one gather packs [E*C, H] slot payloads, then the same
    member-major all-to-all as the dense path. Empty slots (sentinel index T,
    out of bounds) gather as zeros. ``token_for_slot`` may be the raw [E*C]
    index array or a :class:`SlotPlan` (the once-per-routing-decision form).
    ``n_chunks > 1`` splits the capacity axis of the pallas wire into that
    many double-buffered chunk kernels (identical numerics; lax wire
    ignores it — XLA owns that schedule). ``wire_dtype="fp8"|"int8"``
    block-quantizes the wire payload (wire_fp8=True = legacy "fp8").
    ``schedule`` runs the pallas wire one contention-free permutation
    round at a time (a2a_sched.wire_schedule; bit-identical output).
    Returns [E_local, W*C, H]."""
    if isinstance(token_for_slot, SlotPlan):
        token_for_slot = token_for_slot.token_for_slot
    w = lax.axis_size(axis)
    if num_experts % w:
        raise ValueError(f"experts {num_experts} not divisible by EP world {w}")
    e_local = num_experts // w
    h = x.shape[-1]
    buf = jnp.take(x, token_for_slot, axis=0, mode="fill", fill_value=0)
    buf = buf.reshape(w, e_local, capacity, h)
    cid = _dma.CID_SCHED if schedule is not None else _dma.CID_EP_DISPATCH
    buf = _wire_all_to_all(buf, axis, wire_fp8, quant_group, x.dtype, wire,
                           n_chunks=n_chunks, chunk_axis=2,
                           collective_id=cid,
                           wire_dtype=wire_dtype, schedule=schedule)
    return buf.transpose(1, 0, 2, 3).reshape(e_local, w * capacity, h)


def combine_sorted(
    expert_out: jax.Array,
    slot,
    weights: jax.Array,
    axis: Axis,
    *,
    wire_fp8: bool = False,
    quant_group: int = 128,
    wire: str = "lax",
    n_chunks: int = 1,
    wire_dtype=None,
    schedule=None,
) -> jax.Array:
    """Ragged combine: all-to-all the expert outputs home, then one [T, K]-row
    gather + weighted sum. Dropped assignments (sentinel slot E*C, out of
    bounds) gather as zeros. ``slot`` may be the raw [T, K] array or the
    :class:`SlotPlan` dispatch already used — the same permutation, never
    re-derived. ``schedule`` is the combine-direction round schedule (the
    dispatch matrix TRANSPOSED — traffic flows home). expert_out:
    [E_local, W*C, H] → [T, H]."""
    if isinstance(slot, SlotPlan):
        slot = slot.slot
    w = lax.axis_size(axis)
    e_local, wc, h = expert_out.shape
    c = wc // w
    buf = expert_out.reshape(e_local, w, c, h).transpose(1, 0, 2, 3)
    cid = (_dma.CID_SCHED_COMBINE if schedule is not None
           else _dma.CID_EP_COMBINE)
    buf = _wire_all_to_all(buf, axis, wire_fp8, quant_group,
                           expert_out.dtype, wire,
                           n_chunks=n_chunks, chunk_axis=2,
                           collective_id=cid,
                           wire_dtype=wire_dtype, schedule=schedule)
    y = buf.reshape(w * e_local * c, h)  # [E*C, H], expert-major
    yk = jnp.take(y, slot, axis=0, mode="fill", fill_value=0)  # [T, K, H]
    return jnp.einsum("tk,tkh->th", weights.astype(yk.dtype), yk)


def dispatch(
    x: jax.Array,
    dispatch_mask: jax.Array,
    axis: Axis,
    *,
    wire_fp8: bool = False,
    quant_group: int = 128,
    wire: str = "lax",
    wire_dtype=None,
) -> jax.Array:
    """Scatter local tokens to their experts' owners over the EP axis.

    x: [T, H]; dispatch_mask: [T, E, C] with E = W * E_local.
    Returns [E_local, W * C, H]: for each local expert, the capacity slots
    contributed by every source member (source-major order).
    """
    w = lax.axis_size(axis)
    t, e, c = dispatch_mask.shape
    if e % w:
        raise ValueError(f"experts {e} not divisible by EP world {w}")
    e_local = e // w
    buf = jnp.einsum(
        "tec,th->ech", dispatch_mask.astype(x.dtype), x
    )  # [E, C, H]
    buf = buf.reshape(w, e_local, c, x.shape[-1])
    buf = _wire_all_to_all(buf, axis, wire_fp8, quant_group, x.dtype, wire,
                           collective_id=_dma.CID_EP_DISPATCH,
                           wire_dtype=wire_dtype)
    # buf: [W, E_local, C, H] with dim0 = source member
    return buf.transpose(1, 0, 2, 3).reshape(e_local, w * c, x.shape[-1])


def _member_all_to_all(buf, axis, wire, *, n_chunks=1, chunk_axis=1,
                       collective_id=None, schedule=None):
    """One member-major [W, ...] exchange on the selected wire: the XLA
    collective ("lax") or the device-initiated Pallas remote-DMA kernel
    ("pallas", uccl_tpu.ep.pallas_a2a — falls back to lax past its VMEM
    budget). Both implement the identical tiled contract. ``n_chunks``/
    ``chunk_axis``/``collective_id``/``schedule`` reach only the pallas
    kernel (slot-axis chunking on 2-parity rotated ids; ``schedule`` —
    a ``(rounds, K)`` pair from a2a_sched.wire_schedule — swaps in the
    contention-aware per-round wire, bit-identical output); the lax wire
    is XLA-scheduled and ignores them."""
    if wire == "pallas":
        from uccl_tpu.ep import pallas_a2a

        if schedule is not None:
            return pallas_a2a.scheduled_all_to_all(
                buf, axis, schedule, n_chunks=n_chunks,
                chunk_axis=chunk_axis, collective_id=collective_id)
        return pallas_a2a.all_to_all(buf, axis, n_chunks=n_chunks,
                                     chunk_axis=chunk_axis,
                                     collective_id=collective_id)
    if wire != "lax":
        raise ValueError(f"unknown EP wire {wire!r} (want 'lax' or 'pallas')")
    return lax.all_to_all(buf, axis, split_axis=0, concat_axis=0, tiled=True)


# the ONE divisor rule every wire shares — re-exported under the
# long-standing name (uccl_tpu.ops.quant owns the codec now)
_adapt_quant_group = _quant.adapt_block


def resolve_wire_dtype(wire_fp8: bool, wire_dtype=None):
    """The EP knob-resolution rule: an explicit ``wire_dtype`` wins; the
    legacy ``wire_fp8`` bool maps to "fp8"; otherwise full precision."""
    wire_dtype = _quant.resolve_wire_dtype(wire_dtype)
    if wire_dtype is None and wire_fp8:
        wire_dtype = "fp8"
    return wire_dtype


def wire_itemsize(wire_fp8: bool, hidden: int, dtype,
                  quant_group: int = 128, wire_dtype=None) -> int:
    """Bytes per element the wire actually moves — the itemsize budget
    gates must charge: 1 when the block-scaled packing applies (fp8 or
    int8, identical 1-byte payloads), else the raw activation width
    (shared with ep_bench's transport labels so the gate's arithmetic is
    never mirrored)."""
    wire_dtype = resolve_wire_dtype(wire_fp8, wire_dtype)
    if (wire_dtype is not None
            and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
            and _quant.paying_block(hidden, quant_group)):
        return 1
    return jnp.dtype(dtype).itemsize


# the ONE wire-byte arithmetic (codec-owned now: the planner cost model,
# the ep_bytes_total counter and the benches all import the same rule) —
# re-exported under the long-standing EP name
wire_bytes_of = _quant.wire_bytes_of


def _wire_all_to_all(buf, axis, wire_fp8, quant_group, dtype, wire="lax", *,
                     n_chunks=1, chunk_axis=1, collective_id=None,
                     wire_dtype=None, schedule=None):
    """Member-major all-to-all of a [W, ...] buffer, optionally block-scale
    quantized on the wire (``wire_dtype="fp8"|"int8"``; ``wire_fp8=True``
    is the legacy spelling of "fp8" — the analog of internode_ll.cu's
    fp8+scales message packing). ``schedule`` selects the contention-aware
    per-round pallas wire; when quantizing, the scale exchange rides the
    same schedule on its own id lane (same rounds, same exactness)."""

    def xchg(rows, cid_off=0):
        cid = None if collective_id is None else collective_id + cid_off
        return _member_all_to_all(rows, axis, wire, n_chunks=n_chunks,
                                  chunk_axis=chunk_axis, collective_id=cid,
                                  schedule=schedule)

    wire_dtype = resolve_wire_dtype(wire_fp8, wire_dtype)
    if wire_dtype is not None and not jnp.issubdtype(
        jnp.dtype(buf.dtype), jnp.floating
    ):
        # same rule as the rings' _ring_wire_dtype: a non-float payload
        # rides the full-precision wire — counted, never silently cast
        # through the float codec
        _dma.record_fallback(
            "ep_wire_quant", "quant_dtype",
            detail=jnp.dtype(buf.dtype).name,
            msg=f"ep wire_dtype={wire_dtype!r} needs a float payload, got "
                f"{jnp.dtype(buf.dtype).name}; shipping full precision",
        )
        wire_dtype = None
    if wire_dtype is not None:
        group = _quant.paying_block(buf.shape[-1], quant_group)
        if group is None:
            # quantization would inflate traffic — ship raw, but never
            # silently: the quantized→full-precision downgrade is counted
            # like every other transparent wire decision
            _dma.record_fallback(
                "ep_wire_quant", "block_too_small",
                detail=(buf.shape[-1], quant_group),
                msg=f"ep wire_dtype={wire_dtype!r}: hidden {buf.shape[-1]} "
                    f"only admits blocks < 8 (requested {quant_group}); "
                    "scale overhead would exceed the payload saving — "
                    "shipping full precision",
            )
            return xchg(buf)
        q, scale = quantize_block(buf, wire_dtype, group)
        # scales ride their own id lane: the value and scale exchanges have
        # no data dependency and may be airborne together
        q = xchg(q)
        scale = xchg(scale, _dma.CID_SCALE_OFFSET)
        return dequantize_block(q, scale, group, dtype=dtype)
    return xchg(buf)


def combine(
    expert_out: jax.Array,
    combine_weights: jax.Array,
    axis: Axis,
    *,
    wire_fp8: bool = False,
    quant_group: int = 128,
    wire: str = "lax",
    wire_dtype=None,
) -> jax.Array:
    """Return expert outputs to their source members and weight-sum per token.

    expert_out: [E_local, W*C, H]; combine_weights: [T, E, C].
    Returns [T, H].
    """
    w = lax.axis_size(axis)
    t, e, c = combine_weights.shape
    e_local = e // w
    h = expert_out.shape[-1]
    buf = expert_out.reshape(e_local, w, c, h).transpose(1, 0, 2, 3)  # [W,E_l,C,H]
    buf = _wire_all_to_all(buf, axis, wire_fp8, quant_group,
                           expert_out.dtype, wire,
                           collective_id=_dma.CID_EP_COMBINE,
                           wire_dtype=wire_dtype)
    # buf: [W, E_local, C, H] with dim0 = owner member -> [E, C, H]
    buf = buf.reshape(e, c, h)
    out = jnp.einsum("tec,ech->th", combine_weights.astype(buf.dtype), buf)
    return out


def _capacity_by_factor(t: int, num_selected: int, num_experts: int,
                        capacity_factor: float) -> int:
    return int(capacity_factor * t * num_selected / num_experts)


def expert_capacity(t: int, num_selected: int, num_experts: int,
                    capacity_factor: float) -> int:
    """Rows of one (source member, expert) queue for a source of ``t``
    tokens: ``capacity_factor`` times the balanced share ``t*k/E``, bounded
    by ``t``. The bound loses nothing: ``lax.top_k`` ids are distinct per
    token (:func:`_gate_topk`), so one expert receives at most ``t`` rows
    from one source whatever the routing, and ``pos < capacity`` keeps the
    same assignments at any capacity >= ``t``. Below ``t`` (training
    factors, ``capacity_factor * k < E``) the value is the factor's alone.
    The one derivation for :func:`moe_ffn` and the unsharded oracle
    (``models.flagship.reference_forward``). NOT for
    ``ep.Buffer.capacity``: its callers hand in expert ids that may repeat
    within a token, so one expert can be aimed at more than ``t`` times
    there (``ep.ll`` bounds its pair capacity by its own lossless count)."""
    return max(1, min(
        _capacity_by_factor(t, num_selected, num_experts, capacity_factor), t
    ))


def _resolve_capacity(t: int, num_selected: int, num_experts: int,
                      capacity_factor: float) -> int:
    """:func:`expert_capacity` for one traced EP layer, recorded the way
    :func:`resolve_chunks` records its depth (trace time, host side): the
    resolved rows on the ``ep_expert_capacity`` gauge, and one tick of
    ``ep_capacity_bounded_total`` when the token count, not
    ``capacity_factor``, decided it (docs/OBSERVABILITY.md)."""
    from uccl_tpu.obs import counters as _obsc

    capacity = expert_capacity(t, num_selected, num_experts, capacity_factor)
    _obsc.gauge(
        "ep_expert_capacity",
        "resolved per-expert queue rows of the last traced EP layer",
    ).set(capacity, what="moe_layer")
    bounded = _obsc.counter(
        "ep_capacity_bounded_total",
        "traced EP layers whose expert queues were bounded at the source's "
        "token count instead of capacity_factor's share",
    )
    if _capacity_by_factor(t, num_selected, num_experts,
                           capacity_factor) > t:
        bounded.inc()
    return capacity


def resolve_chunks(n_chunks: int, wire: str, world: int, capacity: int,
                   e_local: int, hidden: int, itemsize: int,
                   wire_dtype=None) -> int:
    """Effective chunk count for the pipelined EP layer. ``0`` = auto: the
    :class:`~uccl_tpu.collective.plan.CollectivePlanner` picks the depth
    off its cost model (2 — the minimum that buys dispatch/compute/combine
    overlap — growing to 4/8 once the modeled wire time of one exchange
    dwarfs the per-launch gamma) on the pallas wire when the world and
    capacity can chunk, else 1. Any request
    collapses to 1 off the pallas wire (XLA owns the lax schedule), at world
    1 (no wire), or when the pipeline's resident
    footprint — 4 send+recv chunk pairs: two airborne kernels in EACH of
    the dispatch and combine families — is over budget. All of these are
    the automatic fallback to the unchunked wire. Every downgrade of an
    EXPLICITLY requested chunk pipeline (n_chunks > 1 on the pallas wire)
    is recorded on the ``ep_wire_fallback_total`` counter with its reason
    (docs/OBSERVABILITY.md) — ``0`` (auto) resolving to 1 on an
    unchunkable config is the correct auto answer, not a downgrade, and
    stays silent (the budget gate still counts either way: there a
    RESOLVED pipeline was pushed back). The resolved depth — including a
    downgraded 1 — lands on the ``ep_chunk_depth`` gauge AND on the plan
    counter (``collective_plan_total{algo="ep_a2a", chunks, wire_dtype}``)
    so benches label their chunk arms off the real resolution, not the
    requested knob."""
    n = _resolve_chunks_value(n_chunks, wire, world, capacity, e_local,
                              hidden, itemsize)
    from uccl_tpu.collective import plan as _plan
    from uccl_tpu.obs import counters as _obsc

    _obsc.gauge(
        "ep_chunk_depth",
        "resolved chunk-pipeline depth of the last traced EP layer",
    ).set(n, what="moe_layer")
    _plan.get_planner().record_ep_chunks(n, wire=wire,
                                         wire_dtype=wire_dtype,
                                         auto=(n_chunks == 0))
    return n


def _resolve_chunks_value(n_chunks, wire, world, capacity, e_local, hidden,
                          itemsize) -> int:
    requested = n_chunks > 1 and wire == "pallas"
    if wire != "pallas" or world <= 1 or capacity < 2:
        if requested:
            _dma.record_fallback(
                "ep_moe_chunked",
                "world_size" if world <= 1 else "capacity",
                detail=(world, capacity),
            )
        return 1
    if n_chunks == 0:
        # auto: the planner's cost model picks the depth from the modeled
        # wire time of ONE exchange vs the per-launch gamma
        from uccl_tpu.collective import plan as _plan

        n_chunks = _plan.get_planner().ep_auto_depth(
            world * e_local * capacity * hidden * itemsize, capacity
        )
    n_chunks = max(1, min(int(n_chunks), capacity))
    if n_chunks > 1:
        cs = _dma.pad_capacity(capacity, n_chunks) // n_chunks
        if not _dma.chunk_budget(world, e_local * cs * hidden, itemsize,
                                 "ep_moe_chunked", resident_kernels=4):
            n_chunks = 1  # chunk_budget already counted + logged the reason
    return n_chunks


def _expert_gemms(xe, w_gate, w_up, w_down):
    """The SwiGLU expert GEMMs with their checkpoint_name tags — ONE copy
    shared by the phased and chunk-pipelined layers so the remat="mlp"
    policy (which matches these exact tags) can never diverge between them.
    checkpoint_name tags let a remat policy pin exactly the expert-GEMM
    operands/results (see flagship._remat_wrap mode "mlp"): with these
    saved, the backward pass re-runs NO forward expert GEMM — the policy
    lever dots_with_no_batch_dims misses, because these einsums carry the
    `e` batch dim and are therefore excluded from it. (Keeping the
    BATCHED einsum form is deliberate: unrolling to per-expert 2-D dots
    measured 1.65x faster in isolation on v5e — scripts/
    expert_gemm_probe.py — but in the fused model context the end-to-end
    gain was <1%, and the unrolled dots lose their `e` batch dim, which
    silently drags every expert GEMM into the remat="dots" saved set and
    OOMs the documented-working B=32 dots config.)

    Who keeps this batched form, which reads every expert's weights
    whatever the routing: the trainer (``models/flagship.py``; the tags
    above), the chunk-pipelined layer, every prefill program (a chunk of
    64-128 rows x top-k reaches the experts held or nearly, so there is
    nothing to skip), the one-shot ``MoEServer.generate`` / ``decode_step``,
    any layer over an exchange (``world > 1``) and the ``ll`` / ``dense``
    forms. Only a caller that names the rows that count (``moe_ffn(...,
    rows=)``: the slot pool's decode and verify programs) gets
    :func:`_expert_gemms_reached` in its place."""
    with jax.named_scope("moe.experts"):
        xe = checkpoint_name(xe, _XE)
        h_gate = checkpoint_name(
            jnp.einsum("ebh,ehf->ebf", xe, w_gate), _HG)
        h_up = checkpoint_name(jnp.einsum("ebh,ehf->ebf", xe, w_up), _HU)
        act = jax.nn.silu(h_gate) * h_up
        return checkpoint_name(
            jnp.einsum("ebf,efh->ebh", act, w_down), _YE)


def _expert_gemms_reached(xe, w_gate, w_up, w_down, layer, counts):
    """:func:`_expert_gemms` over the experts whose queue holds a row, and
    over no other expert's weights: ``counts`` [E] are the queues' kept
    rows; the experts with ``counts > 0`` come first in ``ids`` and a loop
    of ``n_reached`` trips (traced: the routing sets it every step) takes
    expert ``ids[i]`` — its queue ``[1, C, H]`` out of ``xe``, its three
    matrices out of the leaves WHERE THEY LIE, the same three products and
    SwiGLU, written into a zero ``ye [E, C, H]`` at its place. Same
    contraction, dtype and precision a row as the batched einsum, so a
    row's result does not change with who else is computed; an expert
    nobody reached keeps its zero rows, which no slot gathers.

    The leaves come in as stored and are cast AFTER the slice: a cast of
    a whole leaf in front of the loop is a loop-invariant copy of every
    expert in the activations' dtype. ``layer`` (an int, or None) says the
    leaves are a group's stack ``[L, E, ...]`` and this is layer ``layer``
    of it: the slice is taken out of the stack inside the body, because a
    ``stack[layer]`` in front of the loop is an operand of the ``while``
    and the compiler materialises it (1.2 GB a layer at GLM's widths in
    the rehearsal compile). Returns (ye, n_reached int32)."""
    reached = counts > 0
    n_reached = jnp.sum(reached, dtype=jnp.int32)
    ids = jnp.argsort(~reached, stable=True).astype(jnp.int32)

    def expert(w, e, shape=None):
        """``shape`` of expert ``e``'s matrix from its corner (all of it:
        ``[1, H, F]``), where it lies in the leaf."""
        shape = (1,) + (w.shape[-2:] if shape is None else shape)
        if layer is None:
            return lax.dynamic_slice(w, (e, 0, 0), shape).astype(xe.dtype)
        return lax.dynamic_slice(
            w, (layer, e, 0, 0), (1,) + shape)[0].astype(xe.dtype)

    def body(i, ye):
        e = lax.dynamic_index_in_dim(ids, i, keepdims=False)
        x1 = lax.dynamic_slice_in_dim(xe, e, 1, 0)
        h_gate = jnp.einsum("ebh,ehf->ebf", x1, expert(w_gate, e))
        h_up = jnp.einsum("ebh,ehf->ebf", x1, expert(w_up, e))
        act = jax.nn.silu(h_gate) * h_up
        y1 = jnp.einsum("ebf,efh->ebh", act, expert(w_down, e))
        # One number of each leaf enters the result in the activations'
        # precision, times zero. Without it the TPU compiler sees float32
        # leaves whose every reader is a product at the MXU's default
        # precision, and converts the WHOLE leaves to bfloat16 in front of
        # the loop, every step (Mixtral's: 5.6 GB read, 2.8 GB of
        # temporaries written, in the rehearsal compile); with it the
        # products read the float32 expert where it lies and convert in
        # the fusion, as the batched einsum's do.
        keep = sum(expert(w, e, (1, 1)) for w in (w_gate, w_up, w_down))
        y1 = y1 + keep * jnp.zeros((), xe.dtype)
        return lax.dynamic_update_slice_in_dim(ye, y1, e, 0)

    with jax.named_scope("moe.experts"):
        return lax.fori_loop(0, n_reached, body, jnp.zeros_like(xe)), \
            n_reached


def _moe_ffn_sort_chunked(
    x, plan: SlotPlan, weights, w_gate, w_up, w_down, axis,
    num_experts: int, capacity: int, n_chunks: int,
    wire_fp8: bool, quant_group: int, wire_dtype=None,
):
    """The chunk-pipelined sorted MoE step on the device-initiated wire.

    The capacity/slot axis is split into ``n_chunks`` (padded with empty
    slots by the shared ``dma.pad_capacity`` rule — drop semantics are those
    of the UNCHUNKED layer, always), and each chunk runs dispatch-a2a →
    expert GEMM → combine-a2a as its own dependency chain: chunk c's GEMM
    depends only on chunk c's dispatch, and the per-chunk Pallas kernels
    rotate 2-parity collective ids (dispatch {2,3}, combine {4,5}), so the
    remote DMA of dispatch chunk c+1 and the combine return of chunk c-1
    are free to fly while chunk c sits on the MXU — XLA's latency-hiding
    scheduler has both the dataflow freedom and the non-aliased semaphores
    it needs to hide the wire under compute. Slot rows are independent
    through the SwiGLU GEMMs and the a2a is position-preserving, so the
    result is numerically identical to the unchunked layer; the final
    token gather/weighted-sum runs once on the reassembled buffer (it is
    O(T·K·H) arithmetic XLA fuses into the consumer, not wire time)."""
    w = lax.axis_size(axis)
    e_local = num_experts // w
    t, h = x.shape
    tfs_chunks = plan.chunk_token_for_slot(num_experts, n_chunks, t)
    cs = tfs_chunks.shape[-1]
    recv_chunks, y_chunks = [], []
    for c in range(n_chunks):
        with jax.named_scope("moe.dispatch"):
            buf = jnp.take(x, tfs_chunks[c].reshape(-1), axis=0,
                           mode="fill", fill_value=0)
            buf = buf.reshape(w, e_local, cs, h)
            # launch-granularity credit (dma.tie_chunk): chunk c's wire
            # waits on chunk c-2's — its collective-id parity twin — so at
            # most two kernels per family are airborne, matching the 2-id
            # rotation and the 2-resident-pair budget charge
            buf = _dma.tie_chunk(
                buf, recv_chunks[c - 2] if c >= 2 else None
            )
            buf = _wire_all_to_all(
                buf, axis, wire_fp8, quant_group, x.dtype, "pallas",
                collective_id=_dma.chunk_collective_id(
                    _dma.CID_EP_DISPATCH, c),
                wire_dtype=wire_dtype,
            )
            xe = buf.transpose(1, 0, 2, 3).reshape(e_local, w * cs, h)
        recv_chunks.append(xe)
        ye = _expert_gemms(xe, w_gate, w_up, w_down)
        with jax.named_scope("moe.combine"):
            back = ye.reshape(e_local, w, cs, h).transpose(1, 0, 2, 3)
            back = _dma.tie_chunk(
                back, y_chunks[c - 2] if c >= 2 else None
            )
            back = _wire_all_to_all(
                back, axis, wire_fp8, quant_group, ye.dtype, "pallas",
                collective_id=_dma.chunk_collective_id(
                    _dma.CID_EP_COMBINE, c),
                wire_dtype=wire_dtype,
            )
        y_chunks.append(back.reshape(num_experts, cs, h))
    # reassemble the expert-major [E, C, H] buffer (chunks are contiguous
    # slices of each expert's padded capacity), drop the wire-only padding,
    # then ONE token gather + weighted sum — same math as combine_sorted
    with jax.named_scope("moe.combine"):
        y = jnp.concatenate(y_chunks, axis=1)[:, :capacity]
        y = y.reshape(num_experts * capacity, h)
        yk = jnp.take(y, plan.slot, axis=0, mode="fill", fill_value=0)
        return jnp.einsum("tk,tkh->th", weights.astype(yk.dtype), yk)


def _moe_ffn_held(x, router_logits, w_gate, w_up, w_down, axis, held: int,
                  first: int, num_selected: int, capacity_factor: float,
                  impl: str, gate: str, gate_bias, routed_scale: float,
                  rows=None, layer=None):
    """One member's share of an expert layer whose other experts live on
    chips that are not here. The gate is the whole layer's: every token is
    scored over all ``E`` experts, its ``k`` chosen and their weights
    renormalised over all ``k``. Of the (token, choice) pairs, those that
    land on the ``held`` experts ``[first, first + held)`` are queued and
    computed — ``held`` queues, not ``E`` — and each weighted by its gate
    weight; the rest are other members' work and add nothing here. The
    result is this member's part of the layer's sum (the parts of all
    ``E / held`` members add up to the layer: tests/test_hybrid_moe_serving).
    No exchange, and nothing stands in for one.

    Queues: ``capacity`` is :func:`expert_capacity` over the router's ``E``
    — ``capacity_factor`` still counts in balanced shares ``T k / E`` of ONE
    expert, whoever holds it — bounded by ``T``; drop-free (serving) is
    ``capacity_factor * k >= E``, which gives every held queue ``T`` rows.
    A pair for an absent expert is sent to a queue past the held ones
    (id ``held``) that is never gathered, so it neither takes a held queue's
    row nor counts as a drop.

    ``rows`` ([T] bool, ``impl`` "sort"; :func:`moe_ffn`): the rows that
    count. A pair of a row that does not goes where a pair for an
    absent expert goes, and the GEMMs run over the held experts that a row
    which counts reached (:func:`_expert_gemms_reached`; ``layer`` as
    there, the leaves as stored); the result then carries ``n_reached``.
    ``held`` may be all ``E`` here: the whole layer on one member."""
    from uccl_tpu.obs import counters as _obsc

    t, _ = x.shape
    e = router_logits.shape[-1]
    capacity = _resolve_capacity(t, num_selected, e, capacity_factor)
    if held != e:
        _obsc.gauge(
            "ep_experts_held",
            "experts resident on this member in the last traced EP layer "
            "that held a share (their queues: ep_expert_capacity rows each)",
        ).set(held, what="moe_layer")
    with jax.named_scope("moe.route"):
        vals, idx, aux_loss, z_loss = _gate_topk(
            router_logits, num_selected, True, gate, gate_bias, routed_scale)
        local = idx - first
        here = (local >= 0) & (local < held)
        if rows is not None:
            here = here & rows[:, None]
        local = jnp.where(here, local, held)
        if impl == "sort":
            # held + 1 queues, the last the absent experts': its slots lie
            # past the held buffer, so they gather nothing and return zero
            tfs, slot, kept = sorted_from_topk(local, held + 1, capacity)
            tfs = tfs[:held * capacity]
        elif impl == "dense":
            # an id past the held experts is an all-zero one-hot row
            d_mask, c_weights, _ = masks_from_topk(local, vals, held,
                                                   capacity)
        else:
            raise ValueError(f"unknown moe impl {impl!r} for a held share")
    with jax.named_scope("moe.dispatch"):
        xe = dispatch_sorted(x, tfs, held, capacity, axis) \
            if impl == "sort" else dispatch(x, d_mask, axis)
    if rows is None:
        ye = _expert_gemms(xe, w_gate, w_up, w_down)
    else:
        ye, n_reached = _expert_gemms_reached(xe, w_gate, w_up, w_down,
                                              layer, kept[:held])
    with jax.named_scope("moe.combine"):
        out = combine_sorted(ye, slot, vals, axis) if impl == "sort" \
            else combine(ye, c_weights, axis)
    out = out.astype(x.dtype)
    if rows is None:
        return out, aux_loss, z_loss
    return out, aux_loss, z_loss, n_reached


def moe_ffn(
    x: jax.Array,
    router_logits: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    axis: Axis,
    *,
    num_selected: int = 2,
    capacity_factor: float = 1.25,
    wire_fp8: bool = False,
    impl: str = "sort",
    wire: str = "lax",
    n_chunks: int = 1,
    wire_dtype=None,
    gate: str = "softmax",
    gate_bias=None,
    routed_scale: float = 1.0,
    experts_held: Optional[int] = None,
    first_expert: int = 0,
    rows: Optional[jax.Array] = None,
    layer: Optional[int] = None,
) -> Tuple[jax.Array, ...]:
    """Full per-shard MoE layer: route → dispatch → SwiGLU experts → combine.

    x: [T, H]; router_logits: [T, E]; expert weights are the *local* shard:
    w_gate/w_up: [E_local, H, F], w_down: [E_local, F, H].
    impl: "sort" (ragged fast path, default), "dense" (mask-einsum oracle),
    or "ll" (packed low-latency path: grouped GEMMs over receive counts, no
    padded FLOPs — :mod:`uccl_tpu.ep.ll`; capacity_factor maps to its
    pair_capacity_factor bound).
    capacity_factor: "sort" and "dense" queue :func:`expert_capacity` rows
    per (source, expert): the factor's share of ``T*k/E``, never more than
    ``T`` — an ample factor (``capacity_factor * k >= E``, serving) is
    drop-free at exactly ``T`` rows, a training factor drops as before.
    wire: "lax" (XLA collectives) or "pallas" (device-initiated remote-DMA
    all-to-all, :mod:`uccl_tpu.ep.pallas_a2a`); for impl="ll" the value maps
    onto that path's wire form ("pallas" selects its dense-chunk layout on
    the Pallas wire, anything else keeps its own auto resolution).
    n_chunks: chunk-pipeline depth on the pallas wire (0 = auto, 1 = strictly
    phased). With impl="sort" and n_chunks > 1 the layer runs the
    chunk-pipelined step (:func:`_moe_ffn_sort_chunked`: dispatch chunk c+1
    and combine chunk c-1 overlap the expert GEMM of chunk c); impl="ll"
    chunks its wire exchanges; the dense oracle ignores it.
    wire_dtype: block-scale quantize the dispatch/combine wire payloads
    ("fp8" | "int8"; the shared :mod:`uccl_tpu.ops.quant` codec —
    ``wire_fp8=True`` is the legacy spelling of "fp8"). Chunking composes
    bit-identically (blocks run along the hidden dim, untouched by the
    capacity split).
    gate / gate_bias / routed_scale: the gate every impl routes by
    (:func:`_gate_topk`): "softmax" (default) or "sigmoid_bias" with its
    per-expert choice bias [E]; ``routed_scale`` multiplies the weights.
    experts_held / first_expert: this member holds experts ``[first_expert,
    first_expert + experts_held)`` of the ``E`` the router scores — ONE
    member's share of a wider deployment (:func:`_moe_ffn_held`); the
    weights are then ``[experts_held, ...]`` and the result the part of the
    layer's sum its own experts give.
    rows / layer: ``rows`` [T] bool names the rows that COUNT — what the
    slot pool's decode and verify programs hand down on one shard: a
    decoding slot's rows, and not the dummy token of an idle slot, whose
    result the caller discards. EP world 1 and impl "sort" only (the whole
    layer or a held share; anything else is refused, as a held share over
    an exchange is). A (token, choice) pair of a row that does not count
    goes to a queue past the last one, never gathered and no drop, and the
    GEMMs loop over the experts a row which counts reached, reading no
    other expert's weights (:func:`_expert_gemms_reached`) — one algorithm
    whose trip count the routing sets every step; at full reach it reads
    what the batched einsum reads. A row that counts keeps its queue, its
    weights and its arithmetic; the others' output rows are not meaningful.
    The weights then come AS STORED — the loop slices an expert out and
    casts the slice — and ``layer`` (an int) says they are a stack ``[L,
    E_local, ...]`` of which this is layer ``layer`` (None: the layer's own
    ``[E_local, ...]``). The layer then also returns ``n_reached`` (int32,
    traced): how many experts' weights it read. ``rows`` absent (the
    trainer, every prefill program, the one-shot paths, any layer over an
    exchange, "ll" and "dense"): the batched layer as it always was.
    Returns (out [T, H], aux_loss, z_loss), and ``n_reached`` after them
    where ``rows`` was given.
    """
    t, h = x.shape
    gating = dict(gate=gate, gate_bias=gate_bias, routed_scale=routed_scale)
    e = router_logits.shape[-1]
    w = lax.axis_size(axis)
    wire_dtype = resolve_wire_dtype(wire_fp8, wire_dtype)
    if rows is not None:
        if w > 1 or impl != "sort":
            raise ValueError(
                f"rows that count are one member's, looped over without an "
                f"exchange: EP world {w}, impl {impl!r} (want world 1, "
                f"'sort'); hand no rows and the batched layer runs")
        return _moe_ffn_held(
            x, router_logits, w_gate, w_up, w_down, axis,
            experts_held or e, first_expert, num_selected, capacity_factor,
            impl, **gating, rows=rows, layer=layer)
    if experts_held is not None and experts_held != e:
        if w > 1 or impl == "ll":
            raise ValueError(
                f"a held share ({experts_held} of {e} experts) is one "
                f"member's, computed without an exchange: EP world {w}, "
                f"impl {impl!r} (want world 1, 'sort' or 'dense')")
        return _moe_ffn_held(
            x, router_logits, w_gate, w_up, w_down, axis, experts_held,
            first_expert, num_selected, capacity_factor, impl, **gating)
    if impl == "ll":
        from uccl_tpu.ep.ll import ll_moe_ffn

        return ll_moe_ffn(
            x, router_logits, w_gate, w_up, w_down, axis,
            num_selected=num_selected,
            pair_capacity_factor=capacity_factor,
            wire="pallas" if wire == "pallas" else "auto",
            wire_dtype=wire_dtype,
            n_chunks=n_chunks,
            **gating,
        )
    capacity = _resolve_capacity(t, num_selected, e, capacity_factor)
    if impl == "sort":
        with jax.named_scope("moe.route"):
            rs = route_topk_sorted(router_logits, num_selected, capacity,
                                   **gating)
        n_chunks = resolve_chunks(
            n_chunks, wire, w, capacity, e // w, h,
            wire_itemsize(wire_fp8, h, x.dtype, wire_dtype=wire_dtype),
            wire_dtype=wire_dtype,
        )
        if n_chunks > 1:
            plan = SlotPlan(rs.token_for_slot, rs.slot, rs.counts)
            out = _moe_ffn_sort_chunked(
                x, plan, rs.weights, w_gate, w_up, w_down, axis, e,
                capacity, n_chunks, False, 128, wire_dtype=wire_dtype,
            )
            return out.astype(x.dtype), rs.aux_loss, rs.z_loss
        with jax.named_scope("moe.dispatch"):
            xe = dispatch_sorted(
                x, rs.token_for_slot, e, capacity, axis, wire=wire,
                wire_dtype=wire_dtype,
            )
        aux_loss, z_loss = rs.aux_loss, rs.z_loss
    elif impl == "dense":
        with jax.named_scope("moe.route"):
            r = route_topk(router_logits, num_selected, capacity, **gating)
        with jax.named_scope("moe.dispatch"):
            xe = dispatch(x, r.dispatch_mask, axis, wire=wire,
                          wire_dtype=wire_dtype)
        aux_loss, z_loss = r.aux_loss, r.z_loss
    else:
        raise ValueError(
            f"unknown moe impl {impl!r} (want 'sort', 'dense', or 'll')"
        )
    # tagged SwiGLU GEMMs shared with the chunked layer (the tags and the
    # batched einsum form are load-bearing for remat — see _expert_gemms)
    ye = _expert_gemms(xe, w_gate, w_up, w_down)
    with jax.named_scope("moe.combine"):
        if impl == "sort":
            out = combine_sorted(ye, rs.slot, rs.weights, axis, wire=wire,
                                 wire_dtype=wire_dtype)
        else:
            out = combine(ye, r.combine_weights, axis, wire=wire,
                          wire_dtype=wire_dtype)
        out = out.astype(x.dtype)
    return out, aux_loss, z_loss
