"""Shared building blocks for the device-level Pallas remote-DMA kernels.

Factored out of :mod:`uccl_tpu.collective.pallas_ccl` (the ring collectives)
so the EP all-to-all kernels (:mod:`uccl_tpu.ep.pallas_a2a`) reuse the exact
machinery the rings proved on the real v5e: chunk padding to VPU tiles,
MESH-coordinate neighbor addressing, the interpret-mode resolution and its
single-core-host payload ceiling, the VMEM budget gate, and the entry
barriers. The synchronization *design* (write-once slots, 2-deep semaphore
rotation, credit-granted flow control) lives with each kernel — the slot
arithmetic differs between a ring and an all-to-all — but the primitives and
constants here are the common substrate.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from uccl_tpu.utils import config as _config
from uccl_tpu.utils import device as _device
from uccl_tpu.obs import counters as _obsc

LANES = 128
# Pad each chunk to a multiple of 8x128 elements (one f32 sublane tile;
# Mosaic masks the partial tile for narrower dtypes). Kept small on purpose:
# the TPU interpreter backing the CPU tests deadlocks when a single
# interpret-mode buffer reaches ~128 KiB on a 1-core host (XLA:CPU runs the
# buffer-init callback on the same starved pool a blocking semaphore-wait
# callback occupies — measured threshold between 96 and 128 KiB), so small
# payloads must not be padded into that range.
CHUNK_QUANTUM = 8 * LANES

MAX_VMEM_BYTES = _config.param(
    "PALLAS_CCL_MAX_BYTES",
    8 << 20,
    int,
    "per-shard payload ceiling for the VMEM-resident pallas remote-DMA"
    " kernels (ring collectives and the EP all-to-all); larger buffers fall"
    " back to the XLA collective lowering",
)
MAX_INTERP_BYTES = _config.param(
    "PALLAS_CCL_INTERP_MAX_BYTES",
    64 << 10,
    int,
    "payload ceiling when running under the TPU interpreter (CPU tests): "
    "single-core hosts deadlock interpret-mode buffers around 128 KiB, so "
    "bigger payloads fall back to the XLA lowering there",
)


def _pltpu():
    """``jax.experimental.pallas.tpu``, imported when a kernel is built and
    not with this module: the EP layer imports this module for its gates
    and counters, and a process that only ever takes the lax wire (every
    serving engine on one chip) should not pay most of a second of start-up
    for kernels it never builds."""
    from jax.experimental.pallas import tpu

    return tpu


def __getattr__(name):
    if name == "MESH":  # the kernels' device addressing mode
        return _pltpu().DeviceIdType.MESH
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

# Every transparent pallas-wire downgrade (chunked → unchunked → lax)
# increments this counter with its site (`what`) and `reason` — benches and
# the /metrics surface read it instead of re-deriving the gate arithmetic
# (the old `pallas_wire_active` heuristic). Declared at import so the
# series exists (as 0) before the first fallback. Increments happen at
# TRACE time — once per compiled program, the granularity at which the
# wire decision is actually made; a jit cache hit re-runs the traced
# choice without re-counting.
WIRE_FALLBACK = _obsc.counter(
    "ep_wire_fallback_total",
    "transparent pallas-wire downgrades (chunked->unchunked->lax) by "
    "site (what) and reason",
)
_fallback_logged = set()  # (what, reason, detail): log once per shape


def record_fallback(what: str, reason: str, detail=None, msg=None) -> None:
    """Count a transparent wire downgrade and log it ONCE per
    (what, reason, detail) — ``detail`` carries the shape/bytes that made
    this occurrence distinct, so a new shape logs again but a hot loop
    doesn't spam."""
    WIRE_FALLBACK.inc(what=what, reason=reason)
    key = (what, reason, detail)
    if key in _fallback_logged:
        return
    _fallback_logged.add(key)
    from uccl_tpu.utils.logging import log

    log("INFO", "CCL",
        msg or f"pallas {what}: falling back ({reason}, {detail})")

# collective_id allocation for kernels that may be IN FLIGHT concurrently.
# Mosaic's entry-barrier semaphore is keyed by collective_id, so two kernels
# sharing one id must never overlap; the chunk pipeline deliberately keeps
# dispatch chunk c+1 and combine chunk c-1 airborne while chunk c computes,
# so each family rotates its own 2-parity id pair (the launch-granularity
# form of the kernels' internal 2-parity slot rotation), and tie_chunk()
# orders chunk c after chunk c-2 so at most TWO same-family kernels are
# ever in flight — the invariant that makes a 2-id rotation (and the
# 2-resident-pair chunk_budget charge) sound at any n_chunks. fp8 wire
# payloads ride two exchanges (values + scales) with no data dependency
# between them, so scales ride the value id shifted by CID_SCALE_OFFSET.
# Allocation: 0 = the ring collectives (pallas_ccl default),
# {2,3}/{4,5}/{6,7} = dispatch/combine/generic-a2a value lanes,
# {10,11}/{12,13}/{14,15} = their scale lanes, {16,17} = the bidir
# allreduce's paired fwd/bwd ring kernels (airborne CONCURRENTLY by
# design — the FlexLink counter-rotating pair — so they must never share
# an id), {24,25} = their scale lanes.
CID_EP_DISPATCH = 2  # dispatch chunks rotate {2, 3}
CID_EP_COMBINE = 4  # combine chunks rotate {4, 5}
CID_A2A = 6  # the generic/unchunked EP all-to-all lane, rotating {6, 7}
CID_SCALE_OFFSET = 8  # fp8 scale exchange = value id + 8
CID_RING_BIDIR = 16  # bidir allreduce: fwd ring 16, bwd ring 17
# bidir all-gather pair {18, 19} (scales {26, 27}) and the broadcast's
# counter-rotating AG pair {20, 21} (scales {28, 29}) — same concurrency
# rationale as CID_RING_BIDIR: the paired kernels are airborne at once, so
# they must never share a barrier id, and a broadcast overlapping a
# standalone all-gather must not alias either.
CID_AG_BIDIR = 18
CID_BCAST = 20
# scheduled EP a2a: Birkhoff permutation rounds rotate {22, 23} (one round
# kernel per permutation, globally tie_chunk'd at depth 2 across chunks AND
# rounds — one linear launch sequence, so the 2-id rotation stays sound);
# scale lanes {30, 31} via CID_SCALE_OFFSET. A scheduled combine may be
# airborne while a scheduled dispatch is still draining (same rationale as
# the unscheduled {2,3}/{4,5} split), so it gets its own pair {32, 33}
# (scales {40, 41}).
CID_SCHED = 22
CID_SCHED_COMBINE = 32


def chunk_collective_id(base: int, chunk: int) -> int:
    """2-deep rotation: chunk kernels alternate ``base``/``base+1`` so chunk
    c+1 can enter while chunk c-1 drains, without sharing barrier/credit
    semaphores — the double-buffer discipline at kernel-launch granularity.
    Sound only together with :func:`tie_chunk`, which keeps chunk c and the
    id-sharing chunk c-2 from ever being airborne at once."""
    return base + (chunk & 1)


def tie_chunk(x, prev):
    """The launch-granularity credit of the chunk pipeline: order chunk c's
    kernel input after chunk c-2's OUTPUT, so the two chunks sharing a
    collective id parity can never be in flight together (and no more than
    two chunk kernels — the 2 resident pairs chunk_budget charges — ever
    are). ``prev`` is chunk c-2's result (or None for c < 2); the tie is a
    real dataflow edge (``lax.optimization_barrier``), not a host sync, so
    chunk c+1 still overlaps chunk c freely."""
    if prev is None:
        return x
    x, _ = lax.optimization_barrier((x, prev))
    return x


def pad_capacity(cap: int, n_chunks: int) -> int:
    """Round a capacity/slot count up to a multiple of ``n_chunks`` — the ONE
    rounding rule for every chunked EP pipeline (the device-level chunked
    wire pads its slot axis with empty slots by this rule; the host-level
    cross-pod pipeline sizes its per-pod capacity with it), so the two
    pipelines cannot drift on drop semantics."""
    n_chunks = max(1, int(n_chunks))
    if cap % n_chunks:
        cap += n_chunks - cap % n_chunks
    return cap


def chunk_budget(world: int, chunk_elems_per_peer: int, itemsize: int,
                 what: str, interpret=None, resident_kernels: int = 2,
                 quiet: bool = False) -> bool:
    """Budget gate for the double-buffered chunk pipeline:
    ``resident_kernels`` chunk kernels are resident at once, each holding a
    send+recv pair of ``[world, m]`` padded slots. A single chunked
    exchange keeps 2 (the 2-deep rotation); the fully pipelined MoE layer
    keeps 4 — tie_chunk bounds each FAMILY (dispatch, combine) to two in
    flight, and both families are airborne while a chunk's GEMM runs.
    Charged up front so the pipeline falls back to the unchunked wire as a
    whole instead of degrading mid-flight.

    Under the interpreter the residency multiplier does NOT apply: that
    ceiling exists to keep any single interpret-mode buffer below the
    1-core deadlock threshold (see CHUNK_QUANTUM), chunk kernels run
    sequentially there, and chunking SHRINKS per-kernel buffers — charging
    residency would perversely gate the chunked wire harder than the
    unchunked one it falls back to."""
    m = padded_chunk_elems(chunk_elems_per_peer)
    interpret = resolve_interpret(interpret)
    pair = 2 * world * m * itemsize
    return check_budget(pair if interpret else resident_kernels * pair,
                        what, interpret, quiet=quiet)


def scale_rows(rows: int) -> int:
    """Rows of the packed per-row scale buffer a quantized-wire kernel
    DMAs beside its payload: one f32 scale per 128-lane payload row
    (the rings' block rule), packed LANES scales per buffer row —
    ``ceil(rows / LANES)``."""
    return -(-rows // LANES)


def pack_row_scales(s: jax.Array, srows: int) -> jax.Array:
    """[..., rows] per-row f32 scales → the [..., srows, LANES] wire buffer
    (zero-padded tail; a zero scale dequantizes padding to exact zeros —
    ops.quant's guard). Pure layout: values are untouched, so kernel and
    lax-mirror stay bit-identical through a pack/unpack round trip."""
    *lead, rows = s.shape
    pad = srows * LANES - rows
    if pad:
        s = jnp.pad(s, [(0, 0)] * len(lead) + [(0, pad)])
    return s.reshape(*lead, srows, LANES)


def unpack_row_scales(sp: jax.Array, rows: int) -> jax.Array:
    """Inverse of :func:`pack_row_scales`: [..., srows, LANES] → [..., rows]."""
    *lead, srows, lanes = sp.shape
    return sp.reshape(*lead, srows * lanes)[..., :rows]


def pad_chunks(flat: jax.Array, parts: int) -> Tuple[jax.Array, int, int]:
    """Split ``flat`` into ``parts`` equal chunks of k elements (tail
    zero-padded), then pad EACH chunk to m (a CHUNK_QUANTUM multiple) — the
    chunk boundaries are semantic (DMA slots), so padding must be per-chunk,
    not appended to the buffer tail. Returns ([parts, m//128, 128], k, m)."""
    k = -(-flat.size // parts)
    m = -(-k // CHUNK_QUANTUM) * CHUNK_QUANTUM
    tail = parts * k - flat.size
    if tail:
        flat = jnp.concatenate([flat, jnp.zeros((tail,), flat.dtype)])
    x2 = flat.reshape(parts, k)
    if m > k:
        x2 = jnp.pad(x2, ((0, 0), (0, m - k)))
    return x2.reshape(parts, m // LANES, LANES), k, m


def interpret_default() -> bool:
    """The one compile-vs-interpret rule (utils.device.pallas_interpret):
    Mosaic on a TPU backend, the TPU interpreter (which simulates remote
    DMAs and semaphores on host devices) on the CPU, an error elsewhere."""
    return _device.pallas_interpret()


def resolve_interpret(interpret) -> bool:
    return interpret_default() if interpret is None else bool(interpret)


def interp(interpret: bool):
    """Value for ``pl.pallas_call(interpret=...)``: the TPU interpreter
    (simulates remote DMAs, semaphores and barriers on host devices) or
    ``False`` for real Mosaic lowering."""
    return _pltpu().InterpretParams() if interpret else False


def compiler_params(collective_id: int = 0):
    return _pltpu().CompilerParams(
        has_side_effects=True, collective_id=collective_id
    )


def neighbors(axis, n: int, d: int):
    r = lax.axis_index(axis)
    right = lax.rem(r + d + n, n)
    left = lax.rem(r - d + n, n)
    return r, right, left


def mesh_id(axis, idx):
    """Address a peer by mesh coordinate on the collective axis only — the
    other mesh axes default to this device's own coordinates, so kernels work
    on any axis of any mesh (the sub-axis case of a pp×dp×cp×tp mesh). A
    tuple axis (e.g. the EP world over ("dp", "cp")) decomposes the flat
    index row-major, matching lax.axis_index's linearization."""
    if isinstance(axis, (tuple, list)):
        out = {}
        rem = idx
        for a in reversed(axis):
            s = lax.axis_size(a)
            out[a] = lax.rem(rem, s)
            rem = rem // s
        return out
    return {axis: idx}


def remote_kwargs(axis, idx) -> dict:
    """device_id kwargs for make_async_remote_copy / semaphore_signal:
    MESH coordinates, so kernels are sub-axis safe."""
    return dict(device_id=mesh_id(axis, idx),
                device_id_type=_pltpu().DeviceIdType.MESH)


def ring_barrier(axis, left, right):
    """Neighbor barrier: both ring neighbors' kernels are live (skew along
    the ring is then bounded transitively by the data dependencies)."""
    pltpu = _pltpu()
    sem = pltpu.get_barrier_semaphore()
    for peer in (left, right):
        pltpu.semaphore_signal(sem, inc=1, **remote_kwargs(axis, peer))
    pltpu.semaphore_wait(sem, 2)


def all_barrier(axis, n: int):
    """Full-peer barrier: every member's kernel is live. The all-to-all
    pattern needs this stronger form — its very first DMA may target ANY
    peer's buffers, so neighbor liveness (transitive, eventually) is not
    enough at the moment the DMA issues."""
    pltpu = _pltpu()
    sem = pltpu.get_barrier_semaphore()
    r = lax.axis_index(axis)
    for i in range(1, n):
        pltpu.semaphore_signal(
            sem, inc=1, **remote_kwargs(axis, lax.rem(r + i, n)))
    pltpu.semaphore_wait(sem, n - 1)


def budget_limit(interpret: bool) -> int:
    """The effective payload ceiling (no logging): the VMEM budget, further
    clamped by the interpreter's per-buffer deadlock ceiling under interpret
    mode. Exposed so observers (benches labeling which transport actually
    carried an arm) share the gate's arithmetic instead of mirroring it."""
    limit = MAX_VMEM_BYTES.get()
    if interpret:
        limit = min(limit, MAX_INTERP_BYTES.get())
    return limit


def padded_chunk_elems(elems_per_peer: int) -> int:
    """Elements per peer after the CHUNK_QUANTUM padding pad_chunks applies
    — the m in the kernels' [world, m] slot layout."""
    return -(-elems_per_peer // CHUNK_QUANTUM) * CHUNK_QUANTUM


def check_budget(nbytes: int, what: str, interpret: bool,
                 quiet: bool = False) -> bool:
    """``quiet`` suppresses the fallback counter AND log — for observers
    asking what the gate WOULD decide, not taking the fallback (a quiet
    probe must not inflate the fallback series the benches now read)."""
    limit = budget_limit(interpret)
    if nbytes > limit:
        if not quiet:
            record_fallback(
                what,
                "interpret_budget" if interpret else "vmem_budget",
                detail=nbytes,
                msg=(f"pallas {what}: {nbytes}B exceeds "
                     f"{'interpreter' if interpret else 'VMEM'} budget "
                     f"{limit}B; falling back to the XLA collective "
                     "lowering"),
            )
        return False
    return True
