"""Low-latency expert-parallel path: packed no-padding dispatch/combine.

This is the TPU-native re-design of the reference's low-latency EP mode
(ep/src/internode_ll.cu:62 dispatch / :747 combine; python contract
ep/bench/buffer.py:285-454): per-expert *packed* fp8 payloads sized by
``num_max_dispatch_tokens_per_rank``, per-expert **receive counts** returned to
the caller, and expert compute that never touches a padded slot. Where the
reference packs token messages in CUDA warp-groups and RDMA-writes them via a
CPU proxy, here:

* the *layout kernel* (ep/src/layout.cu) is one stable argsort by global
  expert id — because each EP member owns a contiguous expert range, expert
  order IS destination-rank-major order, so one sort yields both the wire
  packing and the per-expert receive grouping;
* the *wire* is ``lax.ragged_all_to_all`` (TPU/GPU): only actual rows move,
  fp8 values + per-group scales, like internode_ll's fp8+scales messages. On
  backends without ragged collectives (XLA:CPU) a dense-chunked
  ``lax.all_to_all`` carries the same packed layout inside fixed-size per-pair
  chunks (padding on the wire, still none on the MXU) — and that path is
  fully differentiable, making it the training-grade ragged MoE. A third
  form, ``wire="pallas"``, keeps the dense-chunk layout but issues the
  exchange as device-initiated remote DMAs from ONE Pallas kernel
  (:mod:`uccl_tpu.ep.pallas_a2a` — the TPU analog of internode_ll's
  proxy-posted RDMA writes, selected via ``Buffer(..., wire="pallas")``);
* the *grouped GEMM* is ``lax.ragged_dot`` over the receive counts
  (megablocks-style): FLOPs proportional to real tokens, not capacity.

Contracts (per-shard, inside ``shard_map`` over the EP axis):

``ll_dispatch(x[T,H], topk_idx[T,K], ...)`` (``topk_idx`` entries of ``-1``
    mean "no expert" — DeepEP-supported; they claim no wire slot and combine
    to zero) →
    ``(recv_x [R_max, H], group_sizes [E_local], state)`` with ``recv_x``
    packed group-major (rows of local expert 0 first, then 1, ...; zeros past
    ``sum(group_sizes)``) — DeepEP's packed_recv_x + packed_recv_count.
``ll_combine(expert_out [R_max, H], state, axis)`` → ``[T, H]`` weighted
    per-token sums (dropped assignments contribute zero).

``num_max_dispatch_tokens_per_rank`` (``M``) bounds tokens sent by one rank
(DeepEP's meaning, ep/bench/buffer.py:285); the static receive bound is then
``R_max = W * M * min(K, E_local)`` rows. Rows past a violated bound drop
tail-first per destination (tested; the lossless default never drops).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from uccl_tpu.collective import dma as _dma
from uccl_tpu.ep.ops import MOE_CHECKPOINT_NAMES
from uccl_tpu.ep.ops import counts_exchange as _counts_exchange
from uccl_tpu.ops.quant import (
    dequantize_block,
    paying_block,
    quantize_block,
)

Axis = Union[str, Tuple[str, ...]]


def _exclusive_cumsum(x):
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def wire_supports_ragged() -> bool:
    """ragged-all-to-all lowers on TPU/GPU; XLA:CPU has no thunk for it."""
    return jax.default_backend() in ("tpu", "gpu")


# ONE scale/payoff rule everywhere: the LL wire's block adaption is the
# shared codec's (uccl_tpu.ops.quant.paying_block — formerly a private
# duplicate of ops._adapt_quant_group + the >= 8 payoff margin here).
_adapt_group = paying_block


def _resolve_quant(h: int, wire_fp8: bool, wire_dtype,
                   quant_group: int):
    """The LL wire's quantization decision: (wire_dtype, adapted group) or
    None. A requested wire dtype that would not pay (only blocks < 8
    divide h) ships raw — counted on the shared fallback counter like
    every quantized→full-precision downgrade, never silent."""
    from uccl_tpu.ep.ops import resolve_wire_dtype

    wire_dtype = resolve_wire_dtype(wire_fp8, wire_dtype)
    if wire_dtype is None:
        return None
    g = _adapt_group(h, quant_group)
    if g is None:
        _dma.record_fallback(
            "ep_wire_quant", "block_too_small", detail=(h, quant_group),
            msg=f"ll wire_dtype={wire_dtype!r}: hidden {h} only admits "
                f"blocks < 8 (requested {quant_group}); shipping full "
                "precision",
        )
        return None
    return (wire_dtype, g)


def resolve_ll_chunks(n_chunks: int, wire: str, world: int,
                      per_pair: int) -> int:
    """Effective chunk-pipeline depth for the LL dense-chunk wire (shared
    with the Buffer verbs so the handle records exactly what dispatch ran):
    1 off the pallas wire or at world 1; 0 = auto (2 when the per-pair slot
    axis can split); clamped to per_pair. An explicitly-requested depth
    (> 1) that gets downgraded is recorded on the shared fallback counter
    (docs/OBSERVABILITY.md); auto (0) resolving to 1 stays silent."""
    if wire != "pallas" or world <= 1:
        if n_chunks > 1 and wire == "pallas":
            from uccl_tpu.collective import dma as _dma

            _dma.record_fallback("ep_ll_chunked", "world_size", detail=world)
        return 1
    if n_chunks == 0:
        n_chunks = 2 if per_pair >= 2 else 1
    return max(1, min(int(n_chunks), per_pair))


class LLState(NamedTuple):
    """Per-shard layout saved by ll_dispatch for ll_combine (the handle)."""

    send_slot: jax.Array  # [T, K] int32 wire-buffer row per assignment
    #   (sentinel = send-buffer size ⇒ dropped)
    weights: jax.Array  # [T, K] f32 gate weights
    send_mat: jax.Array  # [W, E_local] int32 rows I send per (dst, expert)
    recv_mat: jax.Array  # [W, E_local] int32 rows received per (src, expert)
    regroup: jax.Array  # [R_max] int32 perm: grouped row i ← wire row
    src_in_offsets: jax.Array  # [W] int32 where my chunk sat in each source's
    #   send buffer (ragged-wire reverse path; zeros on dense wire)
    wire: str  # "ragged" | "dense" | "pallas"
    n_chunks: int = 1  # pallas-wire chunk-pipeline depth (static; combine
    #   retraces dispatch's chunking without re-resolving)


class LLDispatchResult(NamedTuple):
    recv_x: jax.Array  # [R_max, H] group-major packed tokens
    group_sizes: jax.Array  # [E_local] int32 recv_count per local expert
    state: LLState


def ll_bounds(
    t: int,
    k: int,
    e_local: int,
    w: int,
    m: Optional[int],
    pair_capacity_factor: Optional[float] = None,
) -> Tuple[int, int]:
    """Static buffer bounds: (per_pair, r_max). m bounds tokens one rank
    dispatches (default t); one source aims ≤ m·min(k, e_local) rows at one
    destination (a token repeats an expert at most once and a destination owns
    e_local experts) — the lossless bound. ``pair_capacity_factor`` trades
    losslessness for economy: per_pair shrinks to ceil(cf·t·k/w) (the expected
    per-destination row count under balanced routing, scaled), and rows past
    it drop tail-first — the moral twin of capacity_factor on the padded
    path, and of DeepEP's caller-guaranteed num_max_dispatch_tokens_per_rank
    sizing (ep/bench/buffer.py:285)."""
    m = t if m is None else m
    per_pair = min(m * min(k, e_local), t * k)
    if pair_capacity_factor is not None:
        per_pair = min(
            per_pair, max(1, -(-int(pair_capacity_factor * t * k) // w))
        )
    return per_pair, w * per_pair


def _layout(topk_idx, num_experts: int, e_local: int, per_pair: int, wire: str):
    """One stable argsort = the layout kernel (ep/src/layout.cu analog).

    Returns (sorted_t, slot_sorted, send_slot [T,K], send_mat [W,E_local],
    sent_rows): slot positions are in the WIRE layout — packed ("ragged",
    sentinel T*K) or per-dest chunks of ``per_pair`` ("dense", sentinel
    W*per_pair)."""
    t, k = topk_idx.shape
    tk = t * k
    w = num_experts // e_local
    flat_e = topk_idx.T.reshape(tk)  # k-major: earlier k-slots win on drops
    flat_t = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    # DeepEP's contract admits -1 "no expert" assignments
    # (ep/bench/buffer.py:285). Map them to a sort-last sentinel id so they
    # never claim a wire slot or shift the packed positions of real rows.
    valid = flat_e >= 0
    key_e = jnp.where(valid, flat_e, num_experts).astype(jnp.int32)
    order = jnp.argsort(key_e, stable=True)
    sorted_e = key_e[order]
    sorted_t = flat_t[order]
    is_real = sorted_e < num_experts
    dest = jnp.where(is_real, sorted_e // e_local, 0).astype(jnp.int32)

    counts_e = jnp.bincount(key_e, length=num_experts + 1)[:num_experts]
    dest_sizes = counts_e.reshape(w, e_local).sum(-1)
    dest_start = _exclusive_cumsum(dest_sizes)
    pos_in_dest = (
        jnp.arange(tk, dtype=jnp.int32) - dest_start[dest].astype(jnp.int32)
    )
    keep = is_real & (pos_in_dest < per_pair)  # drop dest-tail + no-expert

    kept_e = jax.ops.segment_sum(
        keep.astype(jnp.int32), sorted_e, num_segments=num_experts
    )
    send_mat = kept_e.reshape(w, e_local)

    if wire == "ragged":
        # kept rows are per-dest prefixes of the sorted order, so the packed
        # position is simply the row's rank among kept rows
        slot_sorted = jnp.where(
            keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, tk
        ).astype(jnp.int32)
        sentinel = tk
    else:
        slot_sorted = jnp.where(
            keep, dest * per_pair + pos_in_dest, w * per_pair
        ).astype(jnp.int32)
        sentinel = w * per_pair
    send_slot = (
        jnp.full((tk,), sentinel, jnp.int32)
        .at[order]
        .set(slot_sorted)
        .reshape(k, t)
        .T
    )
    return sorted_t, slot_sorted, send_slot, send_mat


def _regroup_perm(recv_mat, per_pair: int, wire: str):
    """Permutation taking the wire receive layout → local-expert-major packing.

    Wire layout: rows from source s occupy, in expert order, either the packed
    range starting at cumsum(recv_sizes)[s] ("ragged") or the chunk starting
    at s*per_pair ("dense"). Grouped row i gathers wire row regroup[i];
    invalid rows point past the buffer (gather with fill=0)."""
    w, e_local = recv_mat.shape
    r_max = w * per_pair
    recv_sizes = recv_mat.sum(-1)
    if wire == "ragged":
        chunk_start = _exclusive_cumsum(recv_sizes)
    else:
        chunk_start = jnp.arange(w, dtype=jnp.int32) * per_pair
    src_of = jnp.repeat(
        jnp.arange(w, dtype=jnp.int32), per_pair, total_repeat_length=r_max
    )
    off_in_chunk = jnp.arange(r_max, dtype=jnp.int32) - src_of * per_pair
    seg_end = jnp.cumsum(recv_mat, axis=-1)  # [W, E_local]
    le_of = jnp.sum(off_in_chunk[:, None] >= seg_end[src_of], axis=-1)
    valid = off_in_chunk < recv_sizes[src_of]
    wire_row = jnp.where(
        valid, chunk_start[src_of].astype(jnp.int32) + off_in_chunk, r_max
    )
    key = jnp.where(valid, le_of, e_local)
    grouped_order = jnp.argsort(key, stable=True)
    return wire_row[grouped_order].astype(jnp.int32)


class _RaggedSpec(NamedTuple):
    in_offsets: jax.Array  # [W] chunk starts in my send buffer
    send_sizes: jax.Array  # [W]
    out_offsets: jax.Array  # [W] where my chunk lands in each DEST's output
    recv_sizes: jax.Array  # [W]


def _ragged_exchange(rows, out_rows: int, spec: _RaggedSpec, axis):
    out = jnp.zeros((out_rows,) + rows.shape[1:], rows.dtype)
    return lax.ragged_all_to_all(
        rows,
        out,
        spec.in_offsets.astype(jnp.int32),
        spec.send_sizes.astype(jnp.int32),
        spec.out_offsets.astype(jnp.int32),
        spec.recv_sizes.astype(jnp.int32),
        axis_name=axis,
    )


def _dense_exchange(rows, w: int, axis):
    """Fixed-chunk all_to_all of a [W*per_pair, ...] buffer."""
    shape = rows.shape
    return lax.all_to_all(
        rows.reshape(w, shape[0] // w, *shape[1:]), axis, 0, 0, tiled=True
    ).reshape(shape)


def _pallas_exchange(rows, w: int, axis, *, n_chunks=1, collective_id=None):
    """The dense-chunk layout on the device-initiated wire: same [W*per_pair,
    ...] contract as :func:`_dense_exchange`, but the member-major exchange is
    the Pallas remote-DMA all-to-all kernel (uccl_tpu.ep.pallas_a2a) instead
    of an XLA collective. ``n_chunks > 1`` splits the per-pair slot axis into
    that many double-buffered chunk kernels on rotated collective ids."""
    from uccl_tpu.ep import pallas_a2a

    shape = rows.shape
    return pallas_a2a.all_to_all(
        rows.reshape(w, shape[0] // w, *shape[1:]), axis,
        n_chunks=n_chunks, chunk_axis=1, collective_id=collective_id,
    ).reshape(shape)


def _send_payload(send_rows, out_rows, w, spec, wire, axis, quant_spec,
                  dtype, *, n_chunks=1, collective_id=None):
    """Move a row payload across the wire, optionally block-quantized
    (``quant_spec`` = (wire_dtype, group) or None — values + scale sidecar,
    the shared ops.quant codec)."""

    def exchange(rows, cid_off=0):
        if wire == "ragged":
            return _ragged_exchange(rows, out_rows, spec, axis)
        if wire == "dense":
            return _dense_exchange(rows, w, axis)
        cid = None if collective_id is None else collective_id + cid_off
        return _pallas_exchange(rows, w, axis, n_chunks=n_chunks,
                                collective_id=cid)

    if quant_spec is not None:
        wire_dtype, group = quant_spec
        q, scale = quantize_block(send_rows, wire_dtype, group)
        return dequantize_block(
            exchange(q), exchange(scale, _dma.CID_SCALE_OFFSET),
            group, dtype=dtype,
        )
    return exchange(send_rows)


def ll_dispatch(
    x: jax.Array,
    topk_idx: jax.Array,
    topk_weights: Optional[jax.Array],
    num_experts: int,
    axis: Axis,
    *,
    num_max_dispatch_tokens_per_rank: Optional[int] = None,
    pair_capacity_factor: Optional[float] = None,
    wire: str = "auto",
    wire_fp8: bool = True,
    quant_group: int = 128,
    n_chunks: int = 1,
    wire_dtype: Optional[str] = None,
) -> LLDispatchResult:
    """Packed low-latency dispatch (per-shard). See module docstring.

    ``n_chunks`` (pallas wire only; 0 = auto) splits the per-pair slot axis
    of the dense-chunk exchange into double-buffered chunk kernels — the LL
    grouped GEMM regroups across sources, so here chunking pipelines the
    WIRE itself (and whatever compute XLA schedules beside it), not a
    per-chunk GEMM like the sorted layer's pipelined step.

    ``wire_dtype`` picks the quantized wire payload ("fp8" | "int8");
    ``wire_fp8=True`` is the legacy spelling of "fp8"."""
    w = lax.axis_size(axis)
    t, h = x.shape
    k = topk_idx.shape[-1]
    if num_experts % w:
        raise ValueError(f"experts {num_experts} not divisible by world {w}")
    e_local = num_experts // w
    per_pair, r_max = ll_bounds(
        t, k, e_local, w, num_max_dispatch_tokens_per_rank,
        pair_capacity_factor,
    )
    if wire == "auto":
        wire = "ragged" if wire_supports_ragged() else "dense"
    if wire not in ("ragged", "dense", "pallas"):
        raise ValueError(
            f"unknown LL wire {wire!r} (want 'auto', 'ragged', 'dense', or "
            "'pallas')"
        )
    n_chunks = resolve_ll_chunks(n_chunks, wire, w, per_pair)
    if topk_weights is None:
        topk_weights = jnp.full((t, k), 1.0 / k, jnp.float32)
    quant_spec = _resolve_quant(h, wire_fp8, wire_dtype, quant_group)

    sorted_t, slot_sorted, send_slot, send_mat = _layout(
        topk_idx, num_experts, e_local, per_pair, wire
    )
    recv_mat = _counts_exchange(send_mat, axis)

    send_buf_rows = t * k if wire == "ragged" else w * per_pair
    send_rows = (
        jnp.zeros((send_buf_rows, h), x.dtype)
        .at[slot_sorted]
        .set(x[sorted_t], mode="drop")
    )

    if wire == "ragged":
        send_sizes = send_mat.sum(-1).astype(jnp.int32)
        recv_sizes = recv_mat.sum(-1).astype(jnp.int32)
        in_offsets = _exclusive_cumsum(send_sizes)
        recv_start = _exclusive_cumsum(recv_sizes)
        # each source needs where its chunk lands in MY output, and the
        # reverse path later needs where my chunk sat in each source's input
        out_offsets = _counts_exchange(recv_start[:, None], axis)[:, 0]
        src_in_offsets = _counts_exchange(in_offsets[:, None], axis)[:, 0]
        spec = _RaggedSpec(in_offsets, send_sizes, out_offsets, recv_sizes)
    else:
        spec = None
        src_in_offsets = jnp.zeros((w,), jnp.int32)

    recv_rows = _send_payload(
        send_rows, r_max, w, spec, wire, axis, quant_spec, x.dtype,
        n_chunks=n_chunks, collective_id=_dma.CID_EP_DISPATCH,
    )

    regroup = _regroup_perm(recv_mat, per_pair, wire)
    recv_x = jnp.take(recv_rows, regroup, axis=0, mode="fill", fill_value=0)
    group_sizes = recv_mat.sum(0).astype(jnp.int32)
    state = LLState(
        send_slot, topk_weights, send_mat, recv_mat, regroup,
        src_in_offsets, wire, n_chunks,
    )
    return LLDispatchResult(recv_x, group_sizes, state)


def ll_combine(
    expert_out: jax.Array,
    state: LLState,
    axis: Axis,
    *,
    wire_fp8: bool = True,
    quant_group: int = 128,
    wire_dtype: Optional[str] = None,
) -> jax.Array:
    """Packed low-latency combine (per-shard): ungroup → reverse wire →
    weighted per-token sum. expert_out: [R_max, H] group-major."""
    w = lax.axis_size(axis)
    r_max, h = expert_out.shape
    per_pair = r_max // w
    t, k = state.send_slot.shape
    quant_spec = _resolve_quant(h, wire_fp8, wire_dtype, quant_group)

    # grouped → wire layout (inverse of the regroup gather)
    wire_rows = (
        jnp.zeros((r_max, h), expert_out.dtype)
        .at[state.regroup]
        .set(expert_out, mode="drop")
    )

    if state.wire == "ragged":
        # send back what was received: my chunk from source s sits at
        # cumsum(recv_sizes)[s]; it lands where s originally packed it
        send_sizes = state.recv_mat.sum(-1).astype(jnp.int32)
        recv_sizes = state.send_mat.sum(-1).astype(jnp.int32)
        spec = _RaggedSpec(
            _exclusive_cumsum(send_sizes),
            send_sizes,
            state.src_in_offsets.astype(jnp.int32),
            recv_sizes,
        )
        out_rows = t * k
    else:
        spec, out_rows = None, r_max

    back = _send_payload(
        wire_rows, out_rows, w, spec, state.wire, axis, quant_spec,
        expert_out.dtype,
        n_chunks=state.n_chunks, collective_id=_dma.CID_EP_COMBINE,
    )

    yk = jnp.take(
        back, state.send_slot, axis=0, mode="fill", fill_value=0
    )  # [T, K, H]
    return jnp.einsum("tk,tkh->th", state.weights.astype(yk.dtype), yk)


def grouped_ffn(
    recv_x: jax.Array,
    group_sizes: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
) -> jax.Array:
    """SwiGLU expert FFN over packed rows: three grouped GEMMs via
    ``lax.ragged_dot`` — FLOPs ∝ sum(group_sizes), not capacity (the
    megablocks-style economy the reference gets from per-expert packed
    messages, internode_ll.cu:62). recv_x: [R, H]; w_gate/w_up: [E_local, H,
    F]; w_down: [E_local, F, H]."""
    # Same checkpoint_name tags as the sort/dense path (ep.ops.moe_ffn):
    # remat="mlp" (flagship._remat_wrap) saves these, so backward re-runs
    # no grouped GEMM regardless of which moe impl is selected.
    xe_tag, hg_tag, hu_tag, ye_tag = MOE_CHECKPOINT_NAMES
    recv_x = checkpoint_name(recv_x, xe_tag)
    gate = checkpoint_name(lax.ragged_dot(recv_x, w_gate, group_sizes),
                           hg_tag)
    up = checkpoint_name(lax.ragged_dot(recv_x, w_up, group_sizes), hu_tag)
    act = jax.nn.silu(gate) * up
    return checkpoint_name(
        lax.ragged_dot(act, w_down, group_sizes), ye_tag
    )


def ll_moe_ffn(
    x: jax.Array,
    router_logits: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    axis: Axis,
    *,
    num_selected: int = 2,
    num_max_dispatch_tokens_per_rank: Optional[int] = None,
    pair_capacity_factor: Optional[float] = None,
    wire: str = "auto",
    wire_fp8: bool = False,
    renormalize: bool = True,
    n_chunks: int = 1,
    wire_dtype: Optional[str] = None,
    gate: str = "softmax",
    gate_bias=None,
    routed_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full MoE layer on the low-latency path: route → packed dispatch →
    grouped GEMMs over counts → packed combine. Drop-free by default (the
    packed path has no per-expert capacity), so it is also the *lossless*
    alternative to the capacity-dropping sorted/dense paths. Differentiable
    end to end on the dense wire; the ragged wire targets decode (DeepEP LL's
    use case). Returns (out [T, H], aux_loss, z_loss)."""
    from uccl_tpu.ep.ops import _gate_topk

    e = router_logits.shape[-1]
    with jax.named_scope("moe.route"):
        topk_vals, topk_idx, aux_loss, z_loss = _gate_topk(
            router_logits, num_selected, renormalize, gate, gate_bias,
            routed_scale,
        )
    with jax.named_scope("moe.dispatch"):
        r = ll_dispatch(
            x, topk_idx, topk_vals, e, axis,
            num_max_dispatch_tokens_per_rank=num_max_dispatch_tokens_per_rank,
            pair_capacity_factor=pair_capacity_factor,
            wire=wire, wire_fp8=wire_fp8, n_chunks=n_chunks,
            wire_dtype=wire_dtype,
        )
    with jax.named_scope("moe.experts"):
        y = grouped_ffn(
            r.recv_x, r.group_sizes,
            w_gate.astype(r.recv_x.dtype),
            w_up.astype(r.recv_x.dtype),
            w_down.astype(r.recv_x.dtype),
        )
    with jax.named_scope("moe.combine"):
        out = ll_combine(y, r.state, axis, wire_fp8=wire_fp8,
                         wire_dtype=wire_dtype)
        out = out.astype(x.dtype)
    return out, aux_loss, z_loss
