"""Device-level ring collectives: Pallas remote-DMA kernels on the ICI torus.

This is the layer the reference's value proposition lives in: UCCL beats the
vendor stack by owning the transport under an unchanged API — its engine hot
loop schedules chunks onto 32 UC QPs itself (collective/rdma/transport.cc:443,
chunk spraying :2186) and the next-gen ukernel executes chunk graphs with
persistent device workers (experimental/ukernel/src/ccl/executor.h:26-60).
The TPU analog of "owning the wire" is issuing the inter-chip DMAs from
inside a kernel instead of letting XLA schedule a collective: each hop is a
``pltpu.make_async_remote_copy`` between neighbor chips, double-buffered,
with credit-based flow control — no per-step XLA dispatch, payload resident
in VMEM, and both ICI ring directions drivable concurrently from one kernel
(the torus form of multipath spraying).

Three per-shard entry points (used inside ``shard_map`` like their
:mod:`uccl_tpu.collective.plan` counterparts, which remain the lax.ppermute
lowering of the same schedules):

* :func:`ring_all_gather`   — chunks circulate; direct buf→buf remote DMA.
* :func:`ring_reduce_scatter` — partials circulate via staging buffers.
* :func:`ring_all_reduce`   — RS phase + AG phase in ONE kernel launch,
  optionally bidirectional (payload halved over counter-rotating rings).

Synchronization design (the part that must be right):

* Neighbor barrier at kernel entry (and between the RS and AG phases of the
  fused allreduce): a remote DMA may not target a neighbor's scratch before
  that neighbor's kernel is live (or, at the phase boundary, before its
  sends from the target slot have drained).
* Write-once slots (AG): each buf slot is written exactly once, so data can
  never be clobbered; semaphores count arrivals.
* Credit flow control: ring skew is bounded only by data dependencies — with
  every device but one making progress, the upstream neighbor can run up to
  n-1 steps ahead, overrunning a 2-deep buffer/semaphore rotation. Each
  consumer therefore grants its upstream neighbor an explicit credit
  (``semaphore_signal`` of the sender's ack semaphore) after consuming a
  slot; senders wait for a credit from step 2 on (two slots start free).
  Signals and waits are balanced so every semaphore drains to zero.

Quantized wire (``wire_dtype="fp8"|"int8"`` — the EQuARX move, PAPERS.md:
quantize AllReduce payloads on the wire for ~2-4x fewer bytes with bounded
loss impact):

* every hop moves a block-scaled payload (one f32 scale per 128-lane row,
  the shared :mod:`uccl_tpu.ops.quant` codec) plus its scale sidecar on the
  ``collective_id + CID_SCALE_OFFSET`` lane;
* **reduce-scatter quantizes in the send path and dequantizes in the recv
  path BEFORE accumulating in the input precision** — partial sums are
  never stored in wire precision, so the error is one quantize round trip
  per hop (additive over the n-1 hops), never compounding;
* all-gather payloads are quantized ONCE and forwarded verbatim (write-once
  slots make forwarding exact), so every member pays exactly one round trip;
* the budget/addressability fallbacks ride a **bit-identical pure-lax
  mirror** of the same per-hop math (same codec calls, same slot
  arithmetic), counted on ``ep_wire_fallback_total`` like every transparent
  downgrade — a quantized collective is never silently full-precision and
  never silently off the kernel path. Non-float payloads downgrade to the
  full-precision wire with reason ``quant_dtype``.

Wire bytes are tallied at TRACE time (once per compiled program, the same
per-compile semantics as ``dma.record_fallback``) on the shared
``ep_bytes_total{verb,wire,wire_dtype}`` counter: per-shard bytes actually
sent over the wire for one call — quantized payload + scale sidecar, not
logical element bytes — so benches read effective bus bandwidth straight
off counter deltas (docs/QUANT_WIRE.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from uccl_tpu.collective import dma as _dma
from uccl_tpu.obs import counters as _obsc
from uccl_tpu.ops import quant as _quant
from uccl_tpu.utils.topology import ppermute_pairs

# Shared substrate (uccl_tpu.collective.dma) — also used by the EP
# all-to-all kernels (uccl_tpu.ep.pallas_a2a). The underscored aliases keep
# this module's long-standing surface (tests reset _MAX_VMEM_BYTES, etc.).
_LANES = _dma.LANES
_CHUNK_QUANTUM = _dma.CHUNK_QUANTUM
_MAX_VMEM_BYTES = _dma.MAX_VMEM_BYTES
_MAX_INTERP_BYTES = _dma.MAX_INTERP_BYTES
_MESH = _dma.MESH
_pad_chunks = _dma.pad_chunks
_interpret_default = _dma.interpret_default
_resolve_interpret = _dma.resolve_interpret
_interp = _dma.interp
_neighbors = _dma.neighbors
_mesh_id = _dma.mesh_id
_barrier = _dma.ring_barrier

# the same family ep.buffer's verbs count on — get-or-create by name
# returns the one shared registry family
_WIRE_BYTES = _obsc.counter(
    "ep_bytes_total",
    "actual wire bytes moved by EP verbs and ring collectives (quantized "
    "payload + f32 scale sidecar when a wire_dtype applies, raw element "
    "bytes otherwise), by verb, wire, and wire_dtype",
)


def _count_wire_bytes(verb: str, wire: str, wire_dtype, nbytes: int) -> None:
    """Tally one call's per-shard wire bytes at trace time (per-compile
    semantics — a jit cache hit re-runs the traced exchange without
    re-counting; benches diff around a compiling call)."""
    _WIRE_BYTES.inc(nbytes, verb=verb, wire=wire,
                    wire_dtype=wire_dtype or "none")


def _ring_wire_dtype(x: jax.Array, wire_dtype, what: str):
    """Validate a ring's wire_dtype and downgrade non-float payloads to the
    full-precision wire — counted, never silent."""
    wire_dtype = _quant.resolve_wire_dtype(wire_dtype)
    if wire_dtype is not None and not jnp.issubdtype(
        jnp.dtype(x.dtype), jnp.floating
    ):
        _dma.record_fallback(
            what, "quant_dtype", detail=jnp.dtype(x.dtype).name,
            msg=f"pallas {what}: wire_dtype={wire_dtype!r} needs a float "
                f"payload, got {jnp.dtype(x.dtype).name}; shipping full "
                "precision",
        )
        return None
    return wire_dtype


def _hop_wire_bytes(m: int, itemsize: int, wire_dtype) -> int:
    """Bytes ONE ring hop of an m-element chunk moves: raw payload, or the
    1-byte quantized payload + packed f32 row-scale sidecar."""
    if wire_dtype is None:
        return m * itemsize
    srows = _dma.scale_rows(m // _LANES)
    return m + srows * _LANES * 4


def _quantize_rows(chunk, wire_dtype):
    """Per-row block quantization of a [..., rows, LANES] chunk — the rings'
    block rule (block = one 128-lane row). Returns (q same shape, scales
    [..., rows, 1] f32) via the shared codec."""
    return _quant.quantize_block(chunk, wire_dtype, _LANES)


def _dequantize_rows(q, scales, dtype):
    """Inverse of :func:`_quantize_rows` (scales [..., rows, 1])."""
    return _quant.dequantize_block(q, scales, _LANES, dtype)


def _ag_phase(axis, n, dirs, buf_ref, send_sem, recv_sem, ack_sem):
    """All-gather rings on ``buf_ref[:, h]`` for each stream h (one ring per
    direction in ``dirs``, all DMAs of a step issued before any wait): n-1
    steps of direct buf→buf remote DMA — chunk j lives at slot j on every
    member, so the destination slot equals the source slot and every slot is
    write-once."""
    nbrs = [_neighbors(axis, n, d) for d in dirs]

    def step(s, _):
        descs = []
        for h, d in enumerate(dirs):
            r, right, _left = nbrs[h]
            send_slot = lax.rem(r - d * s + s * n + n, n)

            @pl.when(s >= 2)
            def _(h=h):  # credit from downstream: slot s%2 consumed
                pltpu.semaphore_wait(ack_sem.at[h], 1)

            sl = lax.rem(s, 2)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf_ref.at[send_slot, h],
                dst_ref=buf_ref.at[send_slot, h],
                send_sem=send_sem.at[h, sl],
                recv_sem=recv_sem.at[h, sl],
                **_dma.remote_kwargs(axis, right),
            )
            rdma.start()
            descs.append(rdma)
        for h, d in enumerate(dirs):
            _r, _right, left = nbrs[h]
            descs[h].wait_recv()  # slot (r - d(s+1)) arrived

            @pl.when(s <= n - 4)
            def _(h=h, left=left):  # grant upstream its step-(s+2) send
                pltpu.semaphore_signal(
                    ack_sem.at[h], inc=1,
                    **_dma.remote_kwargs(axis, left),
                )

        for rdma in descs:
            rdma.wait_send()
        return 0

    lax.fori_loop(0, n - 1, step, 0)


def _rs_phase(axis, n, dirs, buf_ref, stage_ref, send_sem, recv_sem,
              ack_sem):
    """Reduce-scatter rings on ``buf_ref[:, h]`` per stream: partial sums
    circulate through 2-slot staging; member r ends holding slot r fully
    reduced. Slot arithmetic matches plan.plan_reduce_scatter
    (send_off=-(s+1), recv_off=-(s+2))."""
    nbrs = [_neighbors(axis, n, d) for d in dirs]

    def step(s, _):
        descs = []
        for h, d in enumerate(dirs):
            r, right, _left = nbrs[h]
            send_slot = lax.rem(r - d * (s + 1) + (s + 1) * n + n, n)

            @pl.when(s >= 2)
            def _(h=h):  # credit: downstream consumed staging slot s%2
                pltpu.semaphore_wait(ack_sem.at[h], 1)

            sl = lax.rem(s, 2)
            rdma = pltpu.make_async_remote_copy(
                src_ref=buf_ref.at[send_slot, h],
                dst_ref=stage_ref.at[h, sl],
                send_sem=send_sem.at[h, sl],
                recv_sem=recv_sem.at[h, sl],
                **_dma.remote_kwargs(axis, right),
            )
            rdma.start()
            descs.append(rdma)
        sl = lax.rem(s, 2)
        for h, d in enumerate(dirs):
            r, _right, left = nbrs[h]
            recv_slot = lax.rem(r - d * (s + 2) + (s + 2) * n + n, n)
            descs[h].wait_recv()
            # fold the arrived partial into the slot sent next step
            buf_ref[recv_slot, h] = (
                buf_ref[recv_slot, h] + stage_ref[h, sl]
            )

            @pl.when(s <= n - 4)
            def _(h=h, left=left):  # staging consumed — grant step s+2
                pltpu.semaphore_signal(
                    ack_sem.at[h], inc=1,
                    **_dma.remote_kwargs(axis, left),
                )

        for rdma in descs:
            rdma.wait_send()
        return 0

    lax.fori_loop(0, n - 1, step, 0)


def _rs_phase_q(axis, n, dirs, buf_ref, qsend_ref, ssend_ref, qstage_ref,
                sstage_ref, send_sem, recv_sem, ssend_sem, srecv_sem,
                ack_sem, wire_dtype, rows, srows, dtype):
    """The quantized-wire reduce-scatter phase: identical slot/credit
    schedule to :func:`_rs_phase`, but each hop's send path quantizes the
    partial sum into a wire-dtype scratch + packed row scales (TWO remote
    DMAs per hop per stream — payload and scale sidecar, no data dependency
    between them) and the recv path dequantizes BEFORE accumulating into
    ``buf_ref`` in the input precision. Partial sums never live in wire
    precision (the EQuARX error-bounding rule): the error is one quantize
    round trip per hop. The payload and scale staging slots of a step are
    consumed together, so ONE ack credit per stream gates both — the
    credit-window arithmetic is untouched."""
    nbrs = [_neighbors(axis, n, d) for d in dirs]

    def step(s, _):
        descs = []
        for h, d in enumerate(dirs):
            r, right, _left = nbrs[h]
            send_slot = lax.rem(r - d * (s + 1) + (s + 1) * n + n, n)

            @pl.when(s >= 2)
            def _(h=h):  # credit: downstream consumed staging slot s%2
                pltpu.semaphore_wait(ack_sem.at[h], 1)

            # quantize the send path: wire payload + packed row scales
            q, sc = _quantize_rows(buf_ref[send_slot, h], wire_dtype)
            qsend_ref[h] = q
            ssend_ref[h] = _dma.pack_row_scales(sc[..., 0], srows)
            sl = lax.rem(s, 2)
            rq = pltpu.make_async_remote_copy(
                src_ref=qsend_ref.at[h],
                dst_ref=qstage_ref.at[h, sl],
                send_sem=send_sem.at[h, sl],
                recv_sem=recv_sem.at[h, sl],
                **_dma.remote_kwargs(axis, right),
            )
            rs_ = pltpu.make_async_remote_copy(
                src_ref=ssend_ref.at[h],
                dst_ref=sstage_ref.at[h, sl],
                send_sem=ssend_sem.at[h, sl],
                recv_sem=srecv_sem.at[h, sl],
                **_dma.remote_kwargs(axis, right),
            )
            rq.start()
            rs_.start()
            descs.append((rq, rs_))
        sl = lax.rem(s, 2)
        for h, d in enumerate(dirs):
            r, _right, left = nbrs[h]
            recv_slot = lax.rem(r - d * (s + 2) + (s + 2) * n + n, n)
            rq, rs_ = descs[h]
            rq.wait_recv()
            rs_.wait_recv()
            # dequantize, THEN accumulate in the input precision
            sc = _dma.unpack_row_scales(sstage_ref[h, sl], rows)
            deq = _dequantize_rows(qstage_ref[h, sl], sc[..., None], dtype)
            buf_ref[recv_slot, h] = buf_ref[recv_slot, h] + deq

            @pl.when(s <= n - 4)
            def _(h=h, left=left):  # staging consumed — grant step s+2
                pltpu.semaphore_signal(
                    ack_sem.at[h], inc=1,
                    **_dma.remote_kwargs(axis, left),
                )

        for rq, rs_ in descs:
            rq.wait_send()
            rs_.wait_send()
        return 0

    lax.fori_loop(0, n - 1, step, 0)


def _scratch(n_streams, rows, dtype, with_staging):
    shapes = [
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # send
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # recv
        pltpu.SemaphoreType.REGULAR((n_streams,)),  # ack credits
    ]
    if with_staging:
        shapes.insert(
            0, pltpu.VMEM((n_streams, 2, rows, _LANES), dtype)
        )
    return shapes


def _quant_scratch(n_streams, rows, srows, wire_dtype):
    """Wire scratch + semaphores of the quantized RS phase: send/stage pairs
    for the payload (wire dtype) and the packed row scales (f32), payload
    DMA sems, scale DMA sems, and the shared ack credits."""
    wdt = _quant.wire_payload_dtype(wire_dtype)
    return [
        pltpu.VMEM((n_streams, rows, _LANES), wdt),  # qsend
        pltpu.VMEM((n_streams, srows, _LANES), jnp.float32),  # ssend
        pltpu.VMEM((n_streams, 2, rows, _LANES), wdt),  # qstage
        pltpu.VMEM((n_streams, 2, srows, _LANES), jnp.float32),  # sstage
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # payload send
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # payload recv
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # scale send
        pltpu.SemaphoreType.DMA((n_streams, 2)),  # scale recv
        pltpu.SemaphoreType.REGULAR((n_streams,)),  # ack credits (shared)
    ]


_check_budget = _dma.check_budget


# ---------------------------------------------------------------------------
# Pure-lax mirrors of the quantized schedules. These are the budget /
# addressability fallbacks of the quantized entries and MUST stay
# bit-identical to the kernels: same codec calls (uccl_tpu.ops.quant), same
# slot arithmetic (plan.py offsets), same accumulate-in-input-precision
# order. tests/test_quant_wire.py pins kernel == mirror exactly.


def _mirror_rs_hops(buf, axis, n, d, wire_dtype, dtype):
    """n-1 quantized reduce-scatter hops on ``buf`` [n, rows, LANES]:
    send_off −(s+1), recv_off −(s+2) (plan.plan_reduce_scatter), each hop
    quantize→ppermute(payload, scales)→dequantize→accumulate."""
    pairs = ppermute_pairs(n, d)
    r = lax.axis_index(axis)
    for s in range(n - 1):
        send_slot = jnp.mod(r - d * (s + 1), n)
        recv_slot = jnp.mod(r - d * (s + 2), n)
        chunk = lax.dynamic_index_in_dim(buf, send_slot, 0, keepdims=False)
        q, sc = _quantize_rows(chunk, wire_dtype)
        qg = lax.ppermute(q, axis, pairs)
        sg = lax.ppermute(sc, axis, pairs)
        deq = _dequantize_rows(qg, sg, dtype)
        cur = lax.dynamic_index_in_dim(buf, recv_slot, 0, keepdims=False)
        buf = lax.dynamic_update_index_in_dim(buf, cur + deq, recv_slot, 0)
    return buf


def _mirror_ag_hops(buf, axis, n, d):
    """n-1 verbatim all-gather hops on ``buf`` [n, ...] (send_off −s,
    recv_off −(s+1), plan.plan_all_gather) — payload dtype untouched, so a
    quantized buffer is forwarded exactly like the kernel's write-once
    slots."""
    pairs = ppermute_pairs(n, d)
    r = lax.axis_index(axis)
    for s in range(n - 1):
        send_slot = jnp.mod(r - d * s, n)
        recv_slot = jnp.mod(r - d * (s + 1), n)
        chunk = lax.dynamic_index_in_dim(buf, send_slot, 0, keepdims=False)
        got = lax.ppermute(chunk, axis, pairs)
        buf = lax.dynamic_update_index_in_dim(buf, got, recv_slot, 0)
    return buf


def _mirror_quant_ar_stream(buf, axis, n, d, wire_dtype, dtype):
    """One stream of the quantized allreduce in pure lax: quantized RS hops
    (input-precision accumulator), quantize the reduced slot ONCE, verbatim
    AG of payload + scales, dequantize every slot. buf: [n, rows, LANES]."""
    buf = _mirror_rs_hops(buf, axis, n, d, wire_dtype, dtype)
    r = lax.axis_index(axis)
    mine = lax.dynamic_index_in_dim(buf, r, 0, keepdims=False)
    q, sc = _quantize_rows(mine, wire_dtype)
    qbuf = jnp.zeros((n,) + q.shape, q.dtype)
    qbuf = lax.dynamic_update_index_in_dim(qbuf, q, r, 0)
    sbuf = jnp.zeros((n,) + sc.shape, sc.dtype)
    sbuf = lax.dynamic_update_index_in_dim(sbuf, sc, r, 0)
    qbuf = _mirror_ag_hops(qbuf, axis, n, d)
    sbuf = _mirror_ag_hops(sbuf, axis, n, d)
    return _dequantize_rows(qbuf, sbuf, dtype)


def _ag_ring(chunk, axis, n, *, direction, interpret, collective_id):
    """One write-once all-gather ring kernel on a [1, rows, LANES] chunk of
    any dtype → [n, 1, rows, LANES]. The payload core of ring_all_gather,
    reused verbatim for the quantized wire's payload and scale exchanges
    (forwarding is dtype-agnostic)."""
    rows = chunk.shape[1]

    def kernel(x_ref, buf_ref, send_sem, recv_sem, ack_sem):
        r, right, left = _neighbors(axis, n, direction)
        _barrier(axis, left, right)
        buf_ref[r, 0] = x_ref[0]
        _ag_phase(axis, n, (direction,), buf_ref, send_sem, recv_sem,
                  ack_sem)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, 1, rows, _LANES), chunk.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_scratch(1, rows, chunk.dtype, with_staging=False),
        compiler_params=_dma.compiler_params(collective_id),
        interpret=_interp(interpret),
    )(chunk)


def ring_all_gather(x: jax.Array, axis, *, direction: int = 1,
                    interpret=None, collective_id: int = 0,
                    wire_dtype=None, count: bool = True) -> jax.Array:
    """Per-shard ``[k, ...] -> [n*k, ...]`` ring all-gather as one Pallas
    kernel (n-1 neighbor DMA hops). Falls back to the plan lowering when the
    gathered buffer exceeds the VMEM budget.

    ``wire_dtype``: quantize the payload once (shared block codec, one f32
    scale per 128-lane row) and circulate payload + scale sidecar — every
    member dequantizes the same wire bytes, so the result is identical on
    all members and one quantize round trip from the input.

    ``count=False`` suppresses the ``ep_bytes_total`` tally — for callers
    that compose this ring into a larger schedule and count the WHOLE
    schedule's bytes under their own verb (scatter_ag_broadcast), so no
    byte is ever counted on two series."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "all_gather")
    k = x.shape[0]
    flat = x.reshape(-1)
    chunk, _, m = _pad_chunks(flat, 1)  # [1, rows, 128]
    rows = m // _LANES
    itemsize = x.dtype.itemsize
    hop_bytes = _hop_wire_bytes(m, itemsize, wire_dtype)

    if wire_dtype is None:
        if not _check_budget(n * x.size * itemsize, "all_gather",
                             interpret):
            from uccl_tpu.collective import plan

            if count:
                _count_wire_bytes("ring_all_gather", "lax", None,
                                  (n - 1) * hop_bytes)
            return plan.ring_all_gather(x, axis)
        if count:
            _count_wire_bytes("ring_all_gather", "pallas", None,
                              (n - 1) * hop_bytes)
        buf = _ag_ring(chunk, axis, n, direction=direction,
                       interpret=interpret,
                       collective_id=collective_id)
        out = buf.reshape(n, m)[:, : flat.size]
        return out.reshape((n * k,) + x.shape[1:])

    # quantized wire: quantize ONCE, gather payload + packed scales
    srows = _dma.scale_rows(rows)
    q, sc = _quantize_rows(chunk, wire_dtype)  # [1,rows,128], [1,rows,1]
    if not _check_budget(n * hop_bytes, "all_gather", interpret):
        from uccl_tpu.collective import plan

        if count:
            _count_wire_bytes("ring_all_gather", "lax", wire_dtype,
                              (n - 1) * hop_bytes)
        qg = plan.ring_all_gather(q, axis)  # [n, rows, 128]
        sg = plan.ring_all_gather(sc, axis)  # [n, rows, 1]
        out = _dequantize_rows(qg, sg, x.dtype)
    else:
        if count:
            _count_wire_bytes("ring_all_gather", "pallas", wire_dtype,
                              (n - 1) * hop_bytes)
        sp = _dma.pack_row_scales(sc[..., 0], srows)  # [1, srows, 128]
        qbuf = _ag_ring(q, axis, n, direction=direction,
                        interpret=interpret,
                        collective_id=collective_id)
        sbuf = _ag_ring(sp, axis, n, direction=direction,
                        interpret=interpret,
                        collective_id=collective_id + _dma.CID_SCALE_OFFSET)
        scg = _dma.unpack_row_scales(sbuf, rows)  # [n, 1, rows]
        out = _dequantize_rows(qbuf, scg[..., None], x.dtype)
    out = out.reshape(n, m)[:, : flat.size]
    return out.reshape((n * k,) + x.shape[1:])


def ring_reduce_scatter(x: jax.Array, axis, *, direction: int = 1,
                        interpret=None, collective_id: int = 0,
                        wire_dtype=None) -> jax.Array:
    """Per-shard ``[n*k, ...] -> [k, ...]``: member r keeps reduced slot r
    (sum), matching plan.ring_reduce_scatter.

    ``wire_dtype``: every hop's partial sum crosses the wire block-quantized
    (payload + row-scale sidecar) and is dequantized before accumulating in
    the input precision — one quantize round trip of error per hop."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    # validate BEFORE the budget fallback: an over-budget indivisible
    # payload must raise, not silently misalign in the plan lowering
    if x.shape[0] % n:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by {n}")
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "reduce_scatter")
    k = x.shape[0] // n
    chunks, per, m = _pad_chunks(x.reshape(-1), n)  # [n, rows, 128]
    rows = m // _LANES
    itemsize = x.dtype.itemsize
    hop_bytes = _hop_wire_bytes(m, itemsize, wire_dtype)

    if wire_dtype is None:
        if not _check_budget(rs_charge(x.size, itemsize, n, None, interpret),
                             "reduce_scatter", interpret):
            from uccl_tpu.collective import plan

            _count_wire_bytes("ring_reduce_scatter", "lax", None,
                              (n - 1) * hop_bytes)
            return plan.ring_reduce_scatter(x, axis)
        _count_wire_bytes("ring_reduce_scatter", "pallas", None,
                          (n - 1) * hop_bytes)
        chunks = chunks.reshape(n, 1, rows, _LANES)

        def kernel(x_ref, out_ref, buf_ref, stage_ref, send_sem, recv_sem,
                   ack_sem):
            r, right, left = _neighbors(axis, n, direction)
            _barrier(axis, left, right)
            buf_ref[...] = x_ref[...]
            _rs_phase(axis, n, (direction,), buf_ref, stage_ref, send_sem,
                      recv_sem, ack_sem)
            out_ref[...] = buf_ref[r, 0]

        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, _LANES), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((n, 1, rows, _LANES), x.dtype)]
            + _scratch(1, rows, x.dtype, with_staging=True),
            compiler_params=_dma.compiler_params(collective_id),
            interpret=_interp(interpret),
        )(chunks)
        return out.reshape(-1)[:per].reshape((k,) + x.shape[1:])

    # quantized wire: accumulator stays input precision; the wire scratches
    # (send + 2-slot staging for payload and scales) ride on top
    srows = _dma.scale_rows(rows)
    charge = rs_charge(x.size, itemsize, n, wire_dtype, interpret)
    if not _check_budget(charge, "reduce_scatter", interpret):
        _count_wire_bytes("ring_reduce_scatter", "lax", wire_dtype,
                          (n - 1) * hop_bytes)
        buf = _mirror_rs_hops(chunks, axis, n, direction, wire_dtype,
                              x.dtype)
        r = lax.axis_index(axis)
        out = lax.dynamic_index_in_dim(buf, r, 0, keepdims=False)
        return out.reshape(-1)[:per].reshape((k,) + x.shape[1:])
    _count_wire_bytes("ring_reduce_scatter", "pallas", wire_dtype,
                      (n - 1) * hop_bytes)
    chunks = chunks.reshape(n, 1, rows, _LANES)

    def kernel(x_ref, out_ref, buf_ref, qsend, ssend, qstage, sstage,
               send_sem, recv_sem, ssend_sem, srecv_sem, ack_sem):
        r, right, left = _neighbors(axis, n, direction)
        _barrier(axis, left, right)
        buf_ref[...] = x_ref[...]
        _rs_phase_q(axis, n, (direction,), buf_ref, qsend, ssend, qstage,
                    sstage, send_sem, recv_sem, ssend_sem, srecv_sem,
                    ack_sem, wire_dtype, rows, srows, x.dtype)
        out_ref[...] = buf_ref[r, 0]

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((n, 1, rows, _LANES), x.dtype)]
        + _quant_scratch(1, rows, srows, wire_dtype),
        compiler_params=_dma.compiler_params(collective_id),
        interpret=_interp(interpret),
    )(chunks)
    return out.reshape(-1)[:per].reshape((k,) + x.shape[1:])


def ring_all_reduce(x: jax.Array, axis, *, bidirectional: bool = True,
                    direction: int = 1, interpret=None,
                    collective_id: int = 0, wire_dtype=None) -> jax.Array:
    """Per-shard allreduce (sum) as ONE kernel: reduce-scatter phase, phase
    barrier, all-gather phase. With ``bidirectional=True`` the payload is
    split over two counter-rotating rings whose DMAs are issued back to back
    each step — both ICI directions of the axis carry traffic concurrently
    (the torus form of UCCL's multipath spraying, transport.cc:2186), from
    inside a single kernel rather than two serialized collectives.
    ``direction`` rotates the single ring when ``bidirectional=False`` —
    the stream primitive :func:`bidir_all_reduce` pairs a +1 and a -1 ring
    as separate concurrently-airborne kernels.

    ``wire_dtype="fp8"|"int8"`` quantizes the wire (module docstring): the
    RS phase quantizes each hop's send and dequantizes before accumulating
    in input precision; the reduced slot is then quantized ONCE and the AG
    phase forwards wire bytes verbatim (payload on the RS semaphores after
    the phase barrier, scales on their own semaphore set). Total error:
    n-1 per-hop round trips into the sum, plus one on the gathered copy."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "all_reduce")
    n_streams = 2 if bidirectional else 1
    dirs = (1, -1) if bidirectional else (direction,)
    shape = x.shape
    flat = x.reshape(-1)
    # [n*S, rows, 128], slot-major then stream
    view, k, m = _pad_chunks(flat, n * n_streams)
    rows = m // _LANES
    view = view.reshape(n, n_streams, rows, _LANES)
    itemsize = x.dtype.itemsize
    hop_bytes = _hop_wire_bytes(m, itemsize, wire_dtype)
    wire_total = 2 * (n - 1) * n_streams * hop_bytes

    if wire_dtype is None:
        if not _check_budget(x.size * itemsize, "all_reduce", interpret):
            from uccl_tpu.collective import plan

            _count_wire_bytes("ring_all_reduce", "lax", None, wire_total)
            return plan.ring_all_reduce(x, axis,
                                        bidirectional=bidirectional,
                                        direction=direction)
        _count_wire_bytes("ring_all_reduce", "pallas", None, wire_total)

        def kernel(x_ref, buf_ref, stage_ref, send_sem, recv_sem, ack_sem):
            r = lax.axis_index(axis)
            right = lax.rem(r + 1, n)
            left = lax.rem(r - 1 + n, n)
            _barrier(axis, left, right)
            buf_ref[...] = x_ref[...]
            _rs_phase(axis, n, dirs, buf_ref, stage_ref, send_sem,
                      recv_sem, ack_sem)
            # Phase barrier: my AG write into a neighbor's buf slot must
            # land after that neighbor's RS sends from it have drained (its
            # RS loop waits every send_sem, so "RS done" implies the reads
            # completed).
            _barrier(axis, left, right)
            _ag_phase(axis, n, dirs, buf_ref, send_sem, recv_sem, ack_sem)

        buf = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, n_streams, rows, _LANES),
                                           x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=_scratch(n_streams, rows, x.dtype,
                                    with_staging=True),
            compiler_params=_dma.compiler_params(collective_id),
            interpret=_interp(interpret),
        )(view)
        out = buf.reshape(n * n_streams, m)[:, :k]
        return out.reshape(-1)[: flat.size].reshape(shape)

    # quantized wire: input-precision accumulator + wire-dtype AG buffers
    # + PER-STREAM send/2-slot-staging wire scratch (_quant_scratch)
    srows = _dma.scale_rows(rows)
    charge = (x.size * itemsize + n * n_streams * hop_bytes
              + n_streams * 3 * hop_bytes)
    if not _check_budget(charge, "all_reduce", interpret):
        _count_wire_bytes("ring_all_reduce", "lax", wire_dtype, wire_total)
        streams = [
            _mirror_quant_ar_stream(view[:, h], axis, n, d, wire_dtype,
                                    x.dtype)
            for h, d in enumerate(dirs)
        ]
        buf = jnp.stack(streams, axis=1)  # [n, S, rows, LANES]
        out = buf.reshape(n * n_streams, m)[:, :k]
        return out.reshape(-1)[: flat.size].reshape(shape)
    _count_wire_bytes("ring_all_reduce", "pallas", wire_dtype, wire_total)
    wdt = _quant.wire_payload_dtype(wire_dtype)

    def kernel(x_ref, buf_ref, qsend, ssend, qstage, sstage, send_sem,
               recv_sem, ssend_sem, srecv_sem, ack_sem, qbuf, sbuf,
               sack_sem):
        r = lax.axis_index(axis)
        right = lax.rem(r + 1, n)
        left = lax.rem(r - 1 + n, n)
        _barrier(axis, left, right)
        buf_ref[...] = x_ref[...]
        _rs_phase_q(axis, n, dirs, buf_ref, qsend, ssend, qstage, sstage,
                    send_sem, recv_sem, ssend_sem, srecv_sem, ack_sem,
                    wire_dtype, rows, srows, x.dtype)
        # Phase barrier: the payload AG reuses the RS payload semaphores —
        # an early AG signal must not race a neighbor still in its RS loop.
        _barrier(axis, left, right)
        # quantize the reduced slot ONCE; AG forwards wire bytes verbatim
        # (write-once slots), every member dequantizing the same bytes
        for h in range(n_streams):
            q, sc = _quantize_rows(buf_ref[r, h], wire_dtype)
            qbuf[r, h] = q
            sbuf[r, h] = _dma.pack_row_scales(sc[..., 0], srows)
        _ag_phase(axis, n, dirs, qbuf, send_sem, recv_sem, ack_sem)
        # the scale AG rides the scale semaphores + its own credits —
        # disjoint from the payload AG's set, so no barrier between them
        _ag_phase(axis, n, dirs, sbuf, ssend_sem, srecv_sem, sack_sem)
        scg = _dma.unpack_row_scales(sbuf[...], rows)  # [n, S, rows]
        buf_ref[...] = _dequantize_rows(qbuf[...], scg[..., None], x.dtype)

    buf = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, n_streams, rows, _LANES),
                                       x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_quant_scratch(n_streams, rows, srows, wire_dtype)
        + [
            pltpu.VMEM((n, n_streams, rows, _LANES), wdt),  # qbuf (AG)
            pltpu.VMEM((n, n_streams, srows, _LANES), jnp.float32),  # sbuf
            pltpu.SemaphoreType.REGULAR((n_streams,)),  # scale-AG credits
        ],
        compiler_params=_dma.compiler_params(collective_id),
        interpret=_interp(interpret),
    )(view)
    out = buf.reshape(n * n_streams, m)[:, :k]
    return out.reshape(-1)[: flat.size].reshape(shape)


# ---------------------------------------------------------------------------
# The bidir allreduce: paired counter-rotating ring KERNELS (FlexLink move)
#
# ring_all_reduce(bidirectional=True) drives both ICI directions from inside
# ONE kernel — its two streams share the kernel's entry barrier, phase
# barrier and fori_loop, so the slower direction gates the faster every
# step. bidir_all_reduce generalizes pallas_a2a's fwd/bwd stream pairing to
# rings at LAUNCH granularity instead: two unidirectional ring kernels on
# paired collective ids (dma.CID_RING_BIDIR / +1 — Mosaic's entry-barrier
# semaphore is keyed by id, so distinct ids are what lets both kernels be
# airborne at once), each carrying half the payload, with no data
# dependency between them — XLA issues both and each ring runs at its own
# pace over its own ICI direction (FlexLink's ~2x link utilization,
# PAPERS.md). It composes with wire_dtype like any ring, and its budget
# fallback is the bit-identical lax mirror of the same directed schedules —
# counted on ep_wire_fallback_total AND collective_plan_total
# (outcome="fallback"), never silent.


def _directed_ar_mirror(hx, axis, n, d, wire_dtype):
    """The pure-lax mirror of ONE directed allreduce ring on a flat payload
    ``hx``: the plan lowering (full precision) or the quantized stream
    mirror — exactly what the directed kernel computes, bit for bit."""
    if wire_dtype is None:
        from uccl_tpu.collective import plan

        return plan.ring_all_reduce(hx, axis, bidirectional=False,
                                    direction=d)
    chunks, k, m = _pad_chunks(hx.reshape(-1), n)  # [n, rows, 128]
    buf = _mirror_quant_ar_stream(chunks, axis, n, d, wire_dtype, hx.dtype)
    return buf.reshape(n, m)[:, :k].reshape(-1)[: hx.size]


def bidir_pair_charge(nelems: int, itemsize: int, n: int, wire_dtype,
                      interpret) -> int:
    """VMEM charge of the bidir kernel pair on a flat ``nelems`` payload
    over a world of ``n`` — THE arithmetic :func:`bidir_all_reduce`'s
    budget gate charges AND the planner's quiet eligibility probe
    (``CollectivePlanner._bidir_budget_ok``) checks, shared so auto can
    never plan a pair the gate would immediately downgrade."""
    half = nelems // 2
    halves = (half, nelems - half)

    def _charge(ne: int) -> int:
        m = _dma.padded_chunk_elems(-(-ne // n))
        if wire_dtype is None:
            return ne * itemsize
        hb = _hop_wire_bytes(m, itemsize, wire_dtype)
        # accumulator + wire-dtype AG buffers + send/2-slot staging scratch
        return ne * itemsize + n * hb + 3 * hb

    charges = [_charge(h) for h in halves]
    # Both kernels are airborne CONCURRENTLY by design, so the VMEM charge
    # is their sum; under the interpreter kernels run sequentially and the
    # ceiling is per-buffer deadlock avoidance — charge the larger half.
    return max(charges) if interpret else sum(charges)


def bidir_all_reduce(x: jax.Array, axis, *, interpret=None,
                     collective_id=None, wire_dtype=None) -> jax.Array:
    """Per-shard allreduce (sum) over TWO counter-rotating ring kernels on
    paired collective ids: the payload is split in half, the first half
    rings forward (+1), the second backward (-1), both kernels airborne
    concurrently (docstring above). ``wire_dtype`` quantizes each ring's
    wire exactly like :func:`ring_all_reduce`'s (scale sidecars ride
    ``collective_id + CID_SCALE_OFFSET`` per ring)."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "all_reduce_bidir")
    if collective_id is None:
        collective_id = _dma.CID_RING_BIDIR
    shape = x.shape
    flat = x.reshape(-1)
    half = flat.size // 2
    if half == 0:  # nothing to split: one directed ring carries it
        return ring_all_reduce(x, axis, bidirectional=False,
                               interpret=interpret,
                               collective_id=collective_id,
                               wire_dtype=wire_dtype)
    halves = (flat[:half], flat[half:])
    itemsize = x.dtype.itemsize
    pair_charge = bidir_pair_charge(flat.size, itemsize, n, wire_dtype,
                                    interpret)
    if not _check_budget(pair_charge, "all_reduce_bidir", interpret):
        # Counted pair-level downgrade: BOTH rings ride their bit-identical
        # lax mirrors as a unit (a half-kernel half-mirror split would tie
        # the surviving kernel to the mirror's XLA schedule — the concurrency
        # the pairing exists for would be gone, silently).
        from uccl_tpu.collective import plan

        plan.PLAN_TOTAL.inc(algo="bidir", chunks=2,
                            wire_dtype=wire_dtype or "none",
                            outcome="fallback")
        wire_total = sum(
            2 * (n - 1) * _hop_wire_bytes(
                _dma.padded_chunk_elems(-(-h.size // n)), itemsize,
                wire_dtype)
            for h in halves
        )
        _count_wire_bytes("ring_all_reduce_bidir", "lax", wire_dtype,
                          wire_total)
        outs = [
            _directed_ar_mirror(h, axis, n, d, wire_dtype)
            for h, d in zip(halves, (1, -1))
        ]
        return jnp.concatenate(outs).reshape(shape)
    # The pair gate passing implies each half passes its own kernel gate
    # (half charge <= pair charge <= limit), so neither inner call can
    # secretly downgrade — the pair flies as a pair or falls as a pair.
    fwd = ring_all_reduce(halves[0], axis, bidirectional=False, direction=1,
                          interpret=interpret, collective_id=collective_id,
                          wire_dtype=wire_dtype)
    bwd = ring_all_reduce(halves[1], axis, bidirectional=False,
                          direction=-1, interpret=interpret,
                          collective_id=collective_id + 1,
                          wire_dtype=wire_dtype)
    return jnp.concatenate([fwd, bwd]).reshape(shape)


# ---------------------------------------------------------------------------
# Broadcast / all-gather as first-class planned verbs (ISSUE 14).
#
# The other half of the collective layer: serving fleets replicate one
# buffer to N peers constantly (replica spin-up, warm spares, RL weight
# refresh), and the bandwidth-optimal form is the scatter-allgather
# decomposition (Network-Offloaded Bandwidth-Optimal Broadcast and
# Allgather, PAPERS.md): the root scatters S/N chunks — (N-1)/N of the
# payload leaves the root exactly ONCE — and a counter-rotating all-gather
# pair completes every member's copy, vs the legacy masked full-payload
# psum that ships the whole buffer through a reduction plus world-1 adds of
# zeros. Everything below reuses the ring substrate verbatim: write-once AG
# slots, credit rotation, wire_dtype quantize-once-forward-verbatim, paired
# collective ids, counted budget fallbacks onto bit-identical lax mirrors.


def rs_charge(nelems: int, itemsize: int, n: int, wire_dtype,
              interpret) -> int:
    """VMEM charge of ONE reduce-scatter ring kernel on a flat ``nelems``
    payload: the full-precision accumulator, plus — when the wire is
    quantized — the send + 2-slot staging wire scratches. EXACTLY what
    ring_reduce_scatter's own gate charges, shared with the planner's
    quiet probe (``CollectivePlanner._rs_budget_ok``)."""
    del interpret  # per-kernel charge; the limit differs, not the charge
    if wire_dtype is None:
        return nelems * itemsize
    m = _dma.padded_chunk_elems(-(-nelems // n))
    return nelems * itemsize + 3 * _hop_wire_bytes(m, itemsize, wire_dtype)


def ag_charge(nelems: int, itemsize: int, n: int, wire_dtype,
              interpret) -> int:
    """VMEM charge of ONE all-gather ring kernel on a flat ``nelems``
    payload: the gathered buffer (full precision) or the gathered wire
    payload + scale sidecar (quantized) — EXACTLY what ring_all_gather's
    own gate charges, shared with the planner's quiet probe."""
    del interpret  # per-kernel charge; the limit differs, not the charge
    if wire_dtype is None:
        return n * nelems * itemsize
    m = _dma.padded_chunk_elems(nelems)
    return n * _hop_wire_bytes(m, itemsize, wire_dtype)


def ag_pair_charge(nelems: int, itemsize: int, n: int, wire_dtype,
                   interpret) -> int:
    """Charge of the counter-rotating all-gather PAIR (bidir_all_gather):
    both kernels airborne concurrently → sum of the halves; under the
    interpreter kernels run sequentially and the ceiling is per-buffer —
    charge the larger half (the bidir_pair_charge convention)."""
    half = nelems // 2
    halves = (half, nelems - half) if half else (nelems,)
    charges = [ag_charge(h, itemsize, n, wire_dtype, interpret)
               for h in halves]
    return max(charges) if interpret else sum(charges)


def bcast_pair_charge(nelems: int, itemsize: int, n: int, wire_dtype,
                      interpret) -> int:
    """Charge of the scatter-allgather broadcast on a flat ``nelems``
    payload: the AG pair over ONE padded S/n chunk (the scatter leg is
    lax ppermutes — no kernel residency)."""
    m = _dma.padded_chunk_elems(-(-nelems // n))
    return ag_pair_charge(m, itemsize, n, wire_dtype, interpret)


def _ag_lax_mirror(x, axis, n, wire_dtype):
    """The pure-lax mirror of one all-gather ring on per-shard ``x``
    [k, ...]: the plan lowering of the same write-once schedule. With a
    wire dtype: quantize ONCE (same padded chunk view as the kernel),
    gather payload + scales verbatim, dequantize — bit-identical to the
    kernel, because forwarding moves bytes verbatim and every member
    dequantizes the same bytes. Without one, pure data movement — exact
    by construction (and direction-independent, so one mirror covers
    both rings of a pair)."""
    from uccl_tpu.collective import plan

    if wire_dtype is None:
        return plan.ring_all_gather(x, axis)
    k = x.shape[0]
    flat = x.reshape(-1)
    chunk, _, m = _pad_chunks(flat, 1)  # [1, rows, 128] — the kernel's view
    q, sc = _quantize_rows(chunk, wire_dtype)
    qg = plan.ring_all_gather(q, axis)  # [n, rows, 128]
    sg = plan.ring_all_gather(sc, axis)  # [n, rows, 1]
    out = _dequantize_rows(qg, sg, x.dtype)
    out = out.reshape(n, m)[:, : flat.size]
    return out.reshape((n * k,) + x.shape[1:])


def _ag_pair_lax_mirror(flat, axis, n, wire_dtype):
    """The pure-lax mirror of the counter-rotating AG PAIR on a flat
    payload: the same half split, per-half :func:`_ag_lax_mirror`, and
    block-wise reassembly to ``[n, flat.size]`` — THE one fallback the
    bidir all-gather and the scatter-allgather broadcast both ride, so
    the two cannot drift."""
    half = flat.size // 2
    outs = [_ag_lax_mirror(flat[:half], axis, n, wire_dtype),
            _ag_lax_mirror(flat[half:], axis, n, wire_dtype)]
    return jnp.concatenate(
        [outs[0].reshape(n, half), outs[1].reshape(n, flat.size - half)],
        axis=1,
    )


def bidir_all_gather(x: jax.Array, axis, *, interpret=None,
                     collective_id=None, wire_dtype=None,
                     count: bool = True) -> jax.Array:
    """Per-shard ``[k, ...] -> [n*k, ...]`` all-gather over TWO
    counter-rotating ring kernels on paired collective ids (the FlexLink
    pairing of :func:`bidir_all_reduce`, applied to the write-once AG
    schedule): the flat payload is split in half, the first half rings
    forward, the second backward, each kernel carrying half the serial
    volume concurrently. ``wire_dtype`` quantizes each half once at the
    source and forwards wire bytes verbatim (one round trip of error,
    members identical). The budget fallback rides the bit-identical lax
    mirror as a pair — counted on ``ep_wire_fallback_total`` AND
    ``collective_plan_total{verb="all_gather", outcome="fallback"}``."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "all_gather_bidir")
    if collective_id is None:
        collective_id = _dma.CID_AG_BIDIR
    k = x.shape[0]
    shape = x.shape
    flat = x.reshape(-1)
    half = flat.size // 2
    if half == 0:  # nothing to split: one directed ring carries it
        return ring_all_gather(x, axis, interpret=interpret,
                               collective_id=collective_id,
                               wire_dtype=wire_dtype, count=count)
    halves = (flat[:half], flat[half:])
    itemsize = x.dtype.itemsize
    pair_charge = ag_pair_charge(flat.size, itemsize, n, wire_dtype,
                                 interpret)
    if not _check_budget(pair_charge, "all_gather_bidir", interpret):
        from uccl_tpu.collective import plan

        plan.PLAN_TOTAL.inc(algo="bidir", chunks=2,
                            wire_dtype=wire_dtype or "none",
                            outcome="fallback", verb="all_gather")
        if count:
            wire_total = sum(
                (n - 1) * _hop_wire_bytes(_dma.padded_chunk_elems(h.size),
                                          itemsize, wire_dtype)
                for h in halves
            )
            _count_wire_bytes("ring_all_gather", "lax", wire_dtype,
                              wire_total)
        out = _ag_pair_lax_mirror(flat, axis, n, wire_dtype)  # [n, S]
    else:
        # pair gate passing implies each half passes its own ring gate
        # (half charge <= pair charge <= limit): the pair flies as a pair
        outs = [
            ring_all_gather(halves[0], axis, direction=1,
                            interpret=interpret,
                            collective_id=collective_id,
                            wire_dtype=wire_dtype, count=count),
            ring_all_gather(halves[1], axis, direction=-1,
                            interpret=interpret,
                            collective_id=collective_id + 1,
                            wire_dtype=wire_dtype, count=count),
        ]
        # outs[i]: [n * half_i] — member j's half at block j; reassemble
        # so block j is member j's FULL flat payload
        out = jnp.concatenate(
            [outs[0].reshape(n, half), outs[1].reshape(n, flat.size - half)],
            axis=1,
        )
    return out.reshape((n * k,) + shape[1:])


def _scatter_from_root(chunks, axis, n, root):
    """Per-shard rooted scatter on a ``[n, ...]`` chunk view: member r
    ends holding ROOT's chunk r (the root keeps its own). Direct
    (root → j) ppermutes — (n-1)/n of the payload leaves the root exactly
    once, and the selects are pure (no adds), so every received chunk is
    bit-identical to the root's bytes."""
    r = lax.axis_index(axis)
    my_chunk = lax.dynamic_index_in_dim(chunks, r, 0, keepdims=False)
    for j in range(n):
        if j == root:
            continue
        got = lax.ppermute(chunks[j], axis, [(root, j)])
        my_chunk = jnp.where(r == j, got, my_chunk)
    return my_chunk


def _bcast_wire_bytes(n: int, m: int, itemsize: int, wire_dtype) -> int:
    """Counter-audited per-member wire bytes of one scatter-allgather
    broadcast: the root's (n-1) scatter chunks amortized over the world
    (only the root sends that leg) + the AG pair's (n-1) hops per half.
    The scatter leg ships full precision (raw chunk ppermutes); the AG
    legs ship the wire dtype."""
    scatter = -(-(n - 1) * m * itemsize // n)
    h1 = m // 2
    ag = sum(
        (n - 1) * _hop_wire_bytes(_dma.padded_chunk_elems(h), itemsize,
                                  wire_dtype)
        for h in ((h1, m - h1) if h1 else (m,))
    )
    return scatter + ag


def scatter_ag_broadcast(x: jax.Array, axis, root: int = 0, *,
                         interpret=None, collective_id=None,
                         wire_dtype=None) -> jax.Array:
    """Per-shard rooted broadcast: every member returns the ROOT's ``x``,
    as the bandwidth-optimal scatter-allgather decomposition — the root
    scatters S/n chunks (direct ppermutes, (n-1)/n·S leaves the root
    once), then the counter-rotating pallas all-gather pair completes
    every member's copy (~(n-1)/n·S per member vs the masked psum's full
    reduction volume). Full precision is BIT-exact (pure data movement);
    ``wire_dtype`` quantizes the AG legs once per chunk — one round trip
    of error, every member identical. Budget fallback: the bit-identical
    lax mirror (same scatter, the pair's AG mirror), counted on
    ``ep_wire_fallback_total{what="broadcast"}`` AND
    ``collective_plan_total{verb="broadcast", outcome="fallback"}``."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    interpret = _resolve_interpret(interpret)
    wire_dtype = _ring_wire_dtype(x, wire_dtype, "broadcast")
    if collective_id is None:
        collective_id = _dma.CID_BCAST
    shape = x.shape
    flat = x.reshape(-1)
    chunks, kk, m = _pad_chunks(flat, n)  # [n, rows, 128]
    itemsize = x.dtype.itemsize
    wire_total = _bcast_wire_bytes(n, m, itemsize, wire_dtype)
    pair_charge = bcast_pair_charge(flat.size, itemsize, n, wire_dtype,
                                    interpret)
    kernel_ok = _check_budget(pair_charge, "broadcast", interpret)
    if not kernel_ok:
        from uccl_tpu.collective import plan

        plan.PLAN_TOTAL.inc(algo="scatter_ag", chunks=2,
                            wire_dtype=wire_dtype or "none",
                            outcome="fallback", verb="broadcast")
    # the WHOLE schedule's bytes (scatter leg + both AG legs) land once,
    # here, under verb="bcast" — the composed all-gather runs count=False
    # so no byte is ever tallied on two series, and kernel and fallback
    # report identically
    _count_wire_bytes("bcast", "pallas" if kernel_ok else "lax",
                      wire_dtype, wire_total)
    my_chunk = _scatter_from_root(chunks, axis, n, root)  # [rows, 128]
    if kernel_ok:
        gathered = bidir_all_gather(
            my_chunk, axis, interpret=interpret,
            collective_id=collective_id, wire_dtype=wire_dtype,
            count=False,
        )  # [n*rows, 128]
    else:
        gathered = _ag_pair_lax_mirror(my_chunk.reshape(-1), axis, n,
                                       wire_dtype)  # [n, m]
    out = gathered.reshape(n, m)[:, :kk]
    return out.reshape(-1)[: flat.size].reshape(shape)


def scatter_gather_broadcast_lax(x: jax.Array, axis,
                                 root: int = 0) -> jax.Array:
    """The planned ``xla`` broadcast lowering (per-shard): the same
    scatter-allgather schedule in pure lax — direct root→j chunk
    ppermutes + one plan.ring_all_gather — replacing the legacy
    psum-of-zeros (which shipped the full payload through a reduction
    plus world-1 adds of zeros). Bit-exact (pure data movement); wire
    bytes counted on ``ep_bytes_total{verb="bcast", wire="xla"}`` so the
    reduction vs the psum baseline is a counter delta, not model math."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    chunks, kk, m = _pad_chunks(flat, n)
    itemsize = x.dtype.itemsize
    scatter = -(-(n - 1) * m * itemsize // n)
    _count_wire_bytes("bcast", "xla", None,
                      scatter + (n - 1) * m * itemsize)
    from uccl_tpu.collective import plan

    my_chunk = _scatter_from_root(chunks, axis, n, root)
    gathered = plan.ring_all_gather(my_chunk, axis)  # [n*rows, 128]
    out = gathered.reshape(n, m)[:, :kk]
    return out.reshape(-1)[: flat.size].reshape(shape)
