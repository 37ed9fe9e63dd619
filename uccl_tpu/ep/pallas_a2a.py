"""Device-initiated EP all-to-all: a Pallas remote-DMA kernel on the ICI.

EP dispatch/combine was the last pillar still riding XLA-scheduled
``lax`` collectives while the reference's whole EP story is *device-initiated*
transfer (ep/src/internode_ll.cu packs per-expert token messages and RDMAs
them via the IBGDA-replacement proxy, ep/src/proxy.cpp:701). This module is
the EP analog of what :mod:`uccl_tpu.collective.pallas_ccl` did for the ring
collectives: the all-to-all that moves routed token rows is issued as
``pltpu.make_async_remote_copy`` inter-chip DMAs from inside ONE kernel — no
per-step XLA dispatch, payload resident in VMEM, both ICI directions of the
axis carrying traffic concurrently.

Schedule (the all-to-all generalization of the ring kernels' design):

* Member ``r`` holds a send buffer of ``W`` destination chunks and a recv
  buffer of ``W`` source slots. Chunk ``r`` short-circuits locally; the
  remaining ``W-1`` exchanges run in ``S = ceil((W-1)/2)`` steps — at step
  ``s`` member ``r`` DMAs chunk ``r+s`` forward and chunk ``r-s`` backward
  (counter-rotating streams, the torus form of the reference's multipath
  chunk spraying, transport.cc:2186).
* **Write-once slots**: the sender addresses the destination's slot by its
  own rank, so every recv slot is written exactly once — data can never be
  clobbered, and the arrival semaphore for a slot carries exactly that
  source's payload count.
* **Full-peer entry barrier**: unlike a ring (where neighbor liveness bounds
  skew transitively), the first all-to-all DMA may target ANY peer's buffer,
  so kernel entry synchronizes with every member of the axis.
* **Credit-granted slot rotation** (generalized from ``pallas_ccl``): each
  stream rotates 2 semaphore parities. With only data dependencies, a peer
  could run ahead and alias a parity slot two steps early; so after consuming
  its step-``s`` arrival, a member grants an explicit credit
  (``semaphore_signal``) to the peer that targets it at step ``s+2``, and
  senders wait for a credit from step 3 on (two parities start free).
  Signals and waits are balanced per stream, so every semaphore drains.

The per-source arrival counts (how many routed rows each source actually
sent) ride the same counts exchange both lax wire paths already use
(:func:`uccl_tpu.ep.ops.counts_exchange` — a [W, E_local] int32 side channel
that is launch-latency-only next to the payload); the payload slots
themselves are fixed-size per pair, which is exactly the dense-chunk LL wire
layout (:mod:`uccl_tpu.ep.ll` ``wire="dense"``) and the sorted path's
capacity layout.

Combine-side note: the *wire* (the reverse all-to-all of expert outputs) is
device-initiated here; the weighted per-token reduction applies immediately
on the received buffer in the same jit (a [T, K]-row gather + weighted sum —
XLA fuses it into the kernel's consumer). The gather itself stays outside
the kernel by design: Mosaic has no dynamic vector gather, and the reduction
is arithmetic XLA already fuses well — the pillar gap was who issues the
DMAs, not who multiplies the weights.

Chunk pipelining (``n_chunks > 1``): the capacity/slot axis splits into
independent per-chunk kernels rotating 2-parity ``collective_id`` pairs
(:func:`uccl_tpu.collective.dma.chunk_collective_id`) so two chunk kernels
can be in flight at once — the double buffering that lets a consumer (the
chunk-pipelined MoE layer, :func:`uccl_tpu.ep.ops.moe_ffn`) hide dispatch
chunk c+1 and combine chunk c-1 under the expert GEMM of chunk c. Identical
numerics to the unchunked exchange; the 2-deep VMEM residency is charged up
front (``dma.chunk_budget``).

Fallback: payloads over the VMEM budget (or the interpreter's single-core
ceiling), chunk pipelines over the 2x double-buffer budget, worlds of 1,
fall back to the unchunked kernel and ultimately ``lax.all_to_all`` with
identical semantics — the ``wire="pallas"`` surface is transparent either way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from uccl_tpu.collective import dma as _dma


def _lax_fallback(x: jax.Array, axis) -> jax.Array:
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


def _a2a_kernel(axis, n: int):
    """Build the kernel body for an n-member all-to-all over ``axis``."""
    s_fwd = (n - 1 + 1) // 2  # fwd stream steps: dsts r+1 .. r+S
    s_bwd = (n - 1) // 2  # bwd stream steps: dsts r-1 .. r-S'

    def stream_step(x_ref, out_ref, send_sem, recv_sem, ack_sem, r, s, h,
                    d, last):
        """One direction's DMA at step s: d=+1 fwd / -1 bwd; ``last`` is the
        stream's static step count (credit window arithmetic)."""
        dst = lax.rem(r + d * s + s * n, n)

        @pl.when(s >= 3)
        def _():  # credit: my step-(s-2) parity slot drained downstream
            pltpu.semaphore_wait(ack_sem.at[h], 1)

        sl = lax.rem(s, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst],
            # write-once: my rows land in the destination's slot ``r`` —
            # the sender's rank IS the per-source slot index
            dst_ref=out_ref.at[r],
            send_sem=send_sem.at[h, sl],
            recv_sem=recv_sem.at[h, sl],
            **_dma.remote_kwargs(axis, dst),
        )
        rdma.start()
        return rdma

    def stream_finish(ack_sem, rdma, r, s, h, d, last):
        rdma.wait_recv()  # slot (r - d*s) arrived

        @pl.when(s <= last - 2)
        def _():  # grant the peer that targets me at step s+2
            pltpu.semaphore_signal(
                ack_sem.at[h], inc=1,
                **_dma.remote_kwargs(
                    axis, lax.rem(r - d * (s + 2) + (s + 2) * n, n)
                ),
            )

    def kernel(x_ref, out_ref, send_sem, recv_sem, ack_sem):
        r = lax.axis_index(axis)
        _dma.all_barrier(axis, n)
        out_ref[r] = x_ref[r]  # local chunk short-circuits

        def step(s, _):
            descs = []
            for h, (d, last) in enumerate(((1, s_fwd), (-1, s_bwd))):
                descs.append(
                    stream_step(x_ref, out_ref, send_sem, recv_sem,
                                ack_sem, r, s, h, d, last)
                )
            for h, (d, last) in enumerate(((1, s_fwd), (-1, s_bwd))):
                stream_finish(ack_sem, descs[h], r, s, h, d, last)
            for rdma in descs:
                rdma.wait_send()
            return 0

        lax.fori_loop(1, s_bwd + 1, step, 0)
        if s_fwd > s_bwd:  # even n: the antipodal chunk, fwd stream only
            # traced like the loop counter, so pl.when sees the same types
            s = jnp.int32(s_fwd)
            rdma = stream_step(x_ref, out_ref, send_sem, recv_sem, ack_sem,
                               r, s, 0, 1, s_fwd)
            stream_finish(ack_sem, rdma, r, s, 0, 1, s_fwd)
            rdma.wait_send()

    return kernel


def _all_to_all_chunked(x, axis, n: int, interpret: bool,
                        collective_id: int, n_chunks: int, chunk_axis: int):
    """Split ``chunk_axis`` into ``n_chunks`` independent per-chunk kernels.

    The slot axis is padded to a multiple of ``n_chunks`` with empty rows
    (``dma.pad_capacity`` — the shared rounding rule — so routing/drop
    semantics are untouched by the chunking) and each chunk rides its own
    Pallas all-to-all with a 2-parity rotated ``collective_id``: chunk c and
    chunk c+1 never share barrier/credit semaphores, so two chunk kernels
    can be in flight at once — the double buffering that lets a consumer's
    compute for chunk c hide under the wire of chunk c+1. The budget gate
    charges that 2-deep footprint (2 resident send+recv pairs); over budget
    (or unchunkable shapes) returns None and the caller falls back to the
    unchunked wire."""
    if x.ndim <= chunk_axis:
        return None
    size = x.shape[chunk_axis]
    if size == 0:
        return None
    n_chunks = min(n_chunks, size)
    if n_chunks <= 1:
        return None
    padded = _dma.pad_capacity(size, n_chunks)
    cs = padded // n_chunks
    chunk_elems_per_peer = x.size // size * cs // n
    if not _dma.chunk_budget(n, chunk_elems_per_peer, x.dtype.itemsize,
                             "ep_all_to_all_chunked", interpret):
        return None
    if padded != size:
        pad = [(0, 0)] * x.ndim
        pad[chunk_axis] = (0, padded - size)
        x = jnp.pad(x, pad)
    outs = []
    for c in range(n_chunks):
        sl = [slice(None)] * x.ndim
        sl[chunk_axis] = slice(c * cs, (c + 1) * cs)
        # launch-granularity credit: chunk c waits on chunk c-2 (its id
        # parity twin), so at most two chunk kernels are ever in flight
        xc = _dma.tie_chunk(x[tuple(sl)],
                            outs[c - 2] if c >= 2 else None)
        outs.append(
            all_to_all(
                xc, axis, interpret=interpret,
                collective_id=_dma.chunk_collective_id(collective_id, c),
            )
        )
    out = jnp.concatenate(outs, axis=chunk_axis)
    if padded != size:
        sl = [slice(None)] * x.ndim
        sl[chunk_axis] = slice(0, size)
        out = out[tuple(sl)]
    return out


def all_to_all(
    x: jax.Array,
    axis,
    *,
    interpret=None,
    collective_id=None,
    n_chunks: int = 1,
    chunk_axis: int = 1,
) -> jax.Array:
    """Per-shard ``[W, ...] -> [W, ...]`` all-to-all as ONE Pallas kernel.

    Chunk ``d`` of my buffer lands in slot *my-rank* of member ``d``'s
    output — the exact contract of ``lax.all_to_all(x, axis, 0, 0,
    tiled=True)``, which is also the fallback lowering when the payload
    exceeds the VMEM budget. Use inside ``shard_map`` over the EP axis.

    ``n_chunks > 1`` splits ``chunk_axis`` (a trailing axis — the
    capacity/slot axis of the EP layouts; never 0, the member axis) into
    that many independent per-chunk kernels on 2-parity rotated collective
    ids, so a consumer can overlap chunk c's compute with chunk c±1's wire
    (see :func:`_all_to_all_chunked`). Identical numerics to the unchunked
    exchange; falls back to it when the 2x double-buffer footprint exceeds
    the budget or the shape cannot chunk."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if x.shape[0] != n:
        raise ValueError(
            f"all_to_all leading dim {x.shape[0]} != axis size {n}"
        )
    if collective_id is None:
        collective_id = _dma.CID_A2A  # the generic lane ({6,7} when chunked)
    interpret = _dma.resolve_interpret(interpret)
    if n_chunks > 1:
        if chunk_axis == 0:
            raise ValueError("chunk_axis 0 is the member axis; chunk a "
                             "trailing (slot) axis instead")
        out = _all_to_all_chunked(x, axis, n, interpret, collective_id,
                                  n_chunks, chunk_axis)
        if out is not None:
            return out
    view, k, m = _dma.pad_chunks(x.reshape(-1), n)  # [n, m//128, 128]
    # both the send and recv buffers are VMEM-resident for the kernel's
    # lifetime, so the budget is charged for the padded pair
    if not _dma.check_budget(2 * n * m * x.dtype.itemsize, "ep_all_to_all",
                             interpret):
        return _lax_fallback(x, axis)
    rows = m // _dma.LANES

    buf = pl.pallas_call(
        _a2a_kernel(axis, n),
        out_shape=jax.ShapeDtypeStruct((n, rows, _dma.LANES), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2, 2)),  # send, per stream x parity
            pltpu.SemaphoreType.DMA((2, 2)),  # recv
            pltpu.SemaphoreType.REGULAR((2,)),  # ack credits, per stream
        ],
        compiler_params=_dma.compiler_params(collective_id),
        interpret=_dma.interp(interpret),
    )(view)
    out = buf.reshape(n, m)[:, :k]
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# Scheduled (contention-aware) wire: one Pallas kernel per Birkhoff round
#
# The unscheduled kernel above ships every (src, dst) pair on two fixed
# counter-rotating streams; under skewed routing the hottest link serializes
# while cold links idle. The scheduled wire drives the SAME one-sided
# write-once DMAs in a different ORDER: the host scheduler
# (uccl_tpu.ep.a2a_sched.wire_schedule) decomposes the traffic matrix into
# contention-free full-permutation rounds (heaviest flows first), and each
# round runs as its own small kernel — every member sends exactly one chunk
# and receives exactly one chunk per round, so no ICI port ever carries two
# transfers at once. Exactness is structural: the same per-pair capacity
# chunks cross the wire exactly once each (shadow duplicates are never read
# back), merely reordered, so the assembled result is bit-identical to the
# unscheduled kernel and to lax.all_to_all.
#
# Rounds must be FULL permutations (self-loops allowed — a self-DMA is a
# local copy): full rounds keep the entry barrier and semaphore accounting
# uniform across members.


def _sched_round_kernel(axis, n: int):
    """One permutation round: member ``r`` DMAs its chunk for ``pi[r]`` into
    that member's single round-output slot. Write-once per kernel (every
    member receives exactly one chunk), so no credit protocol is needed —
    cross-round airborne discipline is the launch-level 2-id rotation +
    tie_chunk, exactly like the chunk pipeline."""

    def kernel(pi_ref, x_ref, out_ref, send_sem, recv_sem):
        r = lax.axis_index(axis)
        _dma.all_barrier(axis, n)
        dst = pi_ref[r]
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref.at[dst],
            dst_ref=out_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            **_dma.remote_kwargs(axis, dst),
        )
        rdma.start()
        rdma.wait_send()
        rdma.wait_recv()

    return kernel


def _run_rounds(view, axis, n: int, perms, interpret, base_cid: int,
                launch_seq: list):
    """Launch one round kernel per permutation over ``view`` ([n, rows,
    LANES]). ``launch_seq`` is the GLOBAL launch list shared across chunks:
    kernel i ties to kernel i-2's output and takes id parity i&1, so the
    whole scheduled exchange is one linear sequence with at most two
    kernels airborne — the invariant that makes the {base, base+1} id
    rotation sound across chunk AND round boundaries."""
    rows = view.shape[1]
    kern = _sched_round_kernel(axis, n)
    outs = []
    for pi in perms:
        i = len(launch_seq)
        v = _dma.tie_chunk(view, launch_seq[i - 2] if i >= 2 else None)
        out = pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((rows, _dma.LANES), view.dtype),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA(()),  # send
                pltpu.SemaphoreType.DMA(()),  # recv
            ],
            compiler_params=_dma.compiler_params(
                _dma.chunk_collective_id(base_cid, i)
            ),
            interpret=_dma.interp(interpret),
        )(jnp.asarray(pi, jnp.int32), v)
        launch_seq.append(out)
        outs.append(out)
    return outs


def _assemble_rounds(view, round_outs, k_mat, axis, n: int):
    """Gather each source's slot from its designated round and overwrite
    the diagonal with the local chunk. ``k_mat`` is the static [W, W]
    designated-round matrix; on member ``r`` the needed column is
    ``k_mat[:, r]`` — a dynamic slice of a constant by the traced rank."""
    r = lax.axis_index(axis)
    stacked = jnp.stack(round_outs)  # [R, rows, LANES]
    col = lax.dynamic_index_in_dim(
        jnp.asarray(k_mat, jnp.int32), r, axis=1, keepdims=False
    )  # [n]: designated round per source
    gathered = jnp.take(stacked, col, axis=0)  # [n, rows, LANES]
    local = lax.dynamic_index_in_dim(view, r, axis=0, keepdims=False)
    return lax.dynamic_update_index_in_dim(gathered, local, r, axis=0)


def _normalize_schedule(schedule, n: int):
    """Accept (rounds, K) from a2a_sched.wire_schedule (Round objects or
    raw permutation tuples) and return (perm tuples, K) validated against
    the axis size."""
    rounds, k_mat = schedule
    perms = []
    for rnd in rounds:
        perm = tuple(getattr(rnd, "perm", rnd))
        if sorted(perm) != list(range(n)):
            raise ValueError(
                f"scheduled a2a round {perm} is not a permutation of "
                f"range({n})"
            )
        perms.append(perm)
    import numpy as _np

    k_arr = _np.asarray(k_mat, _np.int32)
    if k_arr.shape != (n, n):
        raise ValueError(
            f"designated-round matrix is {k_arr.shape}, want {(n, n)}"
        )
    if perms and (k_arr.max() >= len(perms) or k_arr.min() < 0):
        raise ValueError("designated-round matrix indexes a missing round")
    for s in range(n):
        for d in range(n):
            if s != d and perms and perms[k_arr[s, d]][s] != d:
                raise ValueError(
                    f"round {k_arr[s, d]} does not carry pair ({s}, {d})"
                )
    return perms, k_arr


def _scheduled_chunked(x, axis, n: int, perms, k_mat, interpret,
                       collective_id: int, n_chunks: int, chunk_axis: int):
    """Chunk-pipelined scheduled exchange: the capacity axis splits exactly
    like :func:`_all_to_all_chunked`, each chunk runs the full round
    schedule, and ALL (chunk, round) kernels share one global launch
    sequence (see :func:`_run_rounds`) so two are airborne at most. Returns
    None past the double-buffer budget (caller falls back unchunked)."""
    if x.ndim <= chunk_axis:
        return None
    size = x.shape[chunk_axis]
    if size == 0:
        return None
    n_chunks = min(n_chunks, size)
    if n_chunks <= 1:
        return None
    padded = _dma.pad_capacity(size, n_chunks)
    cs = padded // n_chunks
    chunk_elems_per_peer = x.size // size * cs // n
    if not _dma.chunk_budget(n, chunk_elems_per_peer, x.dtype.itemsize,
                             "ep_a2a_sched_chunked", interpret):
        return None
    if padded != size:
        pad = [(0, 0)] * x.ndim
        pad[chunk_axis] = (0, padded - size)
        x = jnp.pad(x, pad)
    launch_seq: list = []
    outs = []
    for c in range(n_chunks):
        sl = [slice(None)] * x.ndim
        sl[chunk_axis] = slice(c * cs, (c + 1) * cs)
        xc = x[tuple(sl)]
        cshape = xc.shape
        view, kc, mc = _dma.pad_chunks(xc.reshape(-1), n)
        round_outs = _run_rounds(view, axis, n, perms, interpret,
                                 collective_id, launch_seq)
        buf = _assemble_rounds(view, round_outs, k_mat, axis, n)
        outs.append(buf.reshape(n, mc)[:, :kc].reshape(cshape))
    out = jnp.concatenate(outs, axis=chunk_axis)
    if padded != size:
        sl = [slice(None)] * x.ndim
        sl[chunk_axis] = slice(0, size)
        out = out[tuple(sl)]
    return out


def scheduled_all_to_all(
    x: jax.Array,
    axis,
    schedule,
    *,
    interpret=None,
    collective_id=None,
    n_chunks: int = 1,
    chunk_axis: int = 1,
) -> jax.Array:
    """Per-shard ``[W, ...] -> [W, ...]`` all-to-all driven one contention-
    free permutation round at a time.

    ``schedule`` is the host-built ``(rounds, K)`` pair from
    :func:`uccl_tpu.ep.a2a_sched.wire_schedule`: load-ordered full
    permutations plus the designated-round matrix. Same tiled contract —
    and bit-identical output — as :func:`all_to_all` and
    ``lax.all_to_all``: the rounds are a pure reordering of the same
    write-once per-pair DMAs, reassembled by designated round. Composes
    with ``n_chunks`` pipelining exactly like the unscheduled wire (one
    global launch sequence keeps at most two kernels airborne on the
    rotated {22, 23} id pair). Falls back to the unscheduled kernel — and
    transitively to lax — past the VMEM budget or on meshes the kernel
    cannot address."""
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if x.shape[0] != n:
        raise ValueError(
            f"all_to_all leading dim {x.shape[0]} != axis size {n}"
        )
    interpret = _dma.resolve_interpret(interpret)
    perms, k_mat = _normalize_schedule(schedule, n)
    if not perms:  # empty schedule: nothing crosses the wire at n > 1
        raise ValueError("scheduled a2a needs at least one round at n > 1")
    if collective_id is None:
        collective_id = _dma.CID_SCHED
    if n_chunks > 1:
        if chunk_axis == 0:
            raise ValueError("chunk_axis 0 is the member axis; chunk a "
                             "trailing (slot) axis instead")
        out = _scheduled_chunked(x, axis, n, perms, k_mat, interpret,
                                 collective_id, n_chunks, chunk_axis)
        if out is not None:
            return out
    view, k, m = _dma.pad_chunks(x.reshape(-1), n)  # [n, m//128, 128]
    # resident per round kernel: the [n, ...] send view + one round slot,
    # two kernels airborne (the global tie_chunk sequence)
    if not _dma.check_budget(2 * (n + 1) * m * x.dtype.itemsize,
                             "ep_a2a_sched", interpret):
        return all_to_all(x, axis, interpret=interpret)
    launch_seq: list = []
    round_outs = _run_rounds(view, axis, n, perms, interpret, collective_id,
                             launch_seq)
    buf = _assemble_rounds(view, round_outs, k_mat, axis, n)
    out = buf.reshape(n, m)[:, :k]
    return out.reshape(x.shape)
