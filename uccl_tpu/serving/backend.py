"""The slot backend: what runs the serving engine's programs on a KV pool.

One class, :class:`SlotBackend`, stands between the engine loop
(serving/engine.py, scheduling only) and the one definition of the slot
programs (``models/inference.py::{prefill,verify}_slots``). It owns the
params, the pool, the chunked prefill's rungs, the one ``stage -> launch ->
fetch`` sequence every call goes through — in two halves, ``stage -> launch``
and ``fetch``, which the engine's chunk step calls apart (both its programs
launched before either is read) and every other caller in turn — and the
slot-row shims the prefix cache and the disagg stream use. The dense and the
MoE stack are two constructors of it (:class:`DenseBackend`,
:class:`MoEBackend`): they differ in what they hand it — how the pool is
born, how a flat per-slot array is laid out for the programs, the three
compiled callables, the rungs — and in no method.

Sizing of a compiled-program LRU (``DensePrograms._fns``, ``MoEServer._fns``;
utils/lru.py): a chunked engine's steady set is its prefill programs (the
compact and the whole-pool form; the MoE server keys the two compact row
counts apart, jit keeps them under one dense function) and its decode OR
verify program, times the four sampled x adapted variants a fully featured
server meets — 12 entries dense, 16 MoE — so 16 holds either without one
evicting another.
"""

from __future__ import annotations

import copy
from functools import partial
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from uccl_tpu import obs
from uccl_tpu.models.inference import (
    SlotKVCache, _flat_extra, _split_extra, decode_step_slots, pool_traits,
    prefill_slots, verify_slots,
)
from uccl_tpu.utils.lru import LRUFnCache

_SAMPLING_DTYPES = (np.int32, np.int32, np.float32, np.float32, np.int32)

_POOL_IN_PLACE = obs.counter(
    "serving_pool_in_place_total",
    "slot-program calls that consumed the pool they were handed (donated, "
    "written in place, handed back as the same buffers; labels: program = "
    "prefill | decode | verify) — equal to the calls made, or a program "
    "copies its pool",
)


_EXPERTS_READ = obs.counter(
    "ep_experts_read_total",
    "experts whose weights the decode / verify programs' expert GEMMs read, "
    "summed over expert layers, members and steps (the program's own "
    "output, fetched with its tokens)",
)
_EXPERTS_HELD = obs.counter(
    "ep_experts_held_total",
    "experts held x expert layers, summed over the same steps: what the "
    "programs would read if they skipped nothing — read / held is 1.0 where "
    "the GEMMs ran over every expert, and the reached share where they loop "
    "over the experts the decoding rows reached",
)


def _consumed(pool) -> bool:
    """Whether a program took every array of the pool it was handed (the
    (k, v) pair, or the pair of each cache group)."""
    return all(a.is_deleted() for a in jax.tree.leaves((pool.k, pool.v)))


def prefill_rung(n: int, n_slots: int) -> int:
    """Rows of the chunked-prefill program for ``n >= 1`` prefilling slots
    of a pool of ``n_slots``: one, two, or the whole pool. Three rungs and
    not every power of two, because a rung's price is one more program
    traced, lowered and loaded at start-up, which grows with the model's
    depth (the layers are unrolled); most chunk steps carry one prefilling
    slot and the next most two; and in a burst the program is bound by
    reading the expert weights whatever its rows, so looping a small rung
    would lose to the whole-pool program."""
    return n if n <= min(2, n_slots) else n_slots


def prefill_rungs(n_slots: int) -> tuple:
    """Every value :func:`prefill_rung` takes over a pool of ``n_slots``
    (deduplicated: a pool of one or two slots has fewer than three)."""
    return tuple(sorted({prefill_rung(n, n_slots) for n in (1, 2, 3)}))


class Programs(NamedTuple):
    """The three compiled callables of a backend (any object with these
    attributes serves). ``prefill(params, tokens, lens, mask, pool, start=,
    ...)``, ``decode(params, tokens, active, pool, ...)`` and ``verify(...)``
    as decode; each takes ``sampling=``, ``adapters=``, ``adapter_ids=``
    (prefill also ``slots=``) and returns its outputs, then the new pool.
    They are pure in params (nothing baked but shapes) and CONSUME the pool
    handed in: it is donated, written in place and handed back as the same
    buffers, so the caller drops every reference to the one it passed (the
    backend's ``_launch`` does, in the statement of the call). Backends of one
    shape share one of these — a replica set costs one warm-up, not N."""
    prefill: Callable
    decode: Callable
    verify: Callable


class DensePrograms:
    """The dense stack's :class:`Programs`: jit of the one definition, one
    program per shape and sampled x adapted variant, in an LRU (sizing: the
    module docstring)."""

    def __init__(self, cfg, fns: Optional[LRUFnCache] = None):
        self.cfg = cfg
        self._fns = fns if fns is not None else LRUFnCache(16)

    def compiled(self, kind: str, s: int, sampled: bool, adapted: bool,
                 compact: bool = False):
        """The jitted callable of ``kind`` ("prefill" | "decode" | "verify")
        at window width ``s`` (decode has one width; pass 1)."""
        cfg = self.cfg

        def build():
            def uccl_dense_prefill_slots(p, tok, lens, mask, off, cache,
                                         *rest):
                slots = None
                if compact:
                    slots, rest = rest[0], rest[1:]
                samp, adp, ids = _split_extra(rest, sampled, adapted)
                return prefill_slots(
                    p, tok, lens, mask, cache, cfg, start=off, sampling=samp,
                    adapters=adp, adapter_ids=ids, slots=slots)

            def uccl_dense_decode_slots(p, tok, mask, cache, *rest):
                samp, adp, ids = _split_extra(rest, sampled, adapted)
                return decode_step_slots(
                    p, tok, mask, cache, cfg, sampling=samp, adapters=adp,
                    adapter_ids=ids)

            def uccl_dense_verify_slots(p, tok, mask, cache, *rest):
                samp, adp, ids = _split_extra(rest, sampled, adapted)
                return verify_slots(
                    p, tok, mask, cache, cfg, sampling=samp, adapters=adp,
                    adapter_ids=ids)

            # the pool (``cache``, by its position) is donated
            return jax.jit({"prefill": uccl_dense_prefill_slots,
                            "decode": uccl_dense_decode_slots,
                            "verify": uccl_dense_verify_slots}[kind],
                           donate_argnums=5 if kind == "prefill" else 3)

        return self._fns.get((kind, s, sampled, adapted, compact), build)

    def prefill(self, params, tokens, lens, mask, cache, *, start,
                sampling=None, adapters=None, adapter_ids=None, slots=None):
        fn = self.compiled("prefill", tokens.shape[1], sampling is not None,
                           adapters is not None, slots is not None)
        extra = _flat_extra(sampling, adapters, adapter_ids)
        if slots is not None:
            extra = [slots] + extra
        return fn(params, tokens, lens, mask, start, cache, *extra)

    def decode(self, params, tokens, active, cache, *, sampling=None,
               adapters=None, adapter_ids=None):
        fn = self.compiled("decode", 1, sampling is not None,
                           adapters is not None)
        return fn(params, tokens, active, cache,
                  *_flat_extra(sampling, adapters, adapter_ids))

    def verify(self, params, tokens, active, cache, *, sampling=None,
               adapters=None, adapter_ids=None):
        fn = self.compiled("verify", tokens.shape[1], sampling is not None,
                           adapters is not None)
        return fn(params, tokens, active, cache,
                  *_flat_extra(sampling, adapters, adapter_ids))


class SlotBackend:
    """Slot-pool serving of one model on one pool: the engine's whole view of
    the model (``prefill`` / ``decode`` / ``verify``, the halves of the
    first two — ``launch_prefill`` / ``launch_decode`` then ``fetch`` —, the
    three slot-row shims, ``n_slots``, ``max_seq``, ``prefill_rungs``).

    A call is ``backend.stage -> backend.launch -> backend.fetch`` (the
    spans ``chipbench/program_trace.py`` reads the device's idle time by),
    nested inside the engine's ``wire.*``; a chunk step that launches both
    its calls before it reads either has ``stage, launch, stage, launch,
    fetch`` inside its ``wire.prefill`` and the decode call's ``fetch``
    alone inside its ``wire.decode`` (docs/OBSERVABILITY.md). The pool has
    one holder, ``self.cache``, which each launch moves to the pool its
    program gives back: nothing else may hold the pool across a call, and
    with two launches in flight that holds launch by launch — the second
    takes what the first left there, a promise of the asynchronous dispatch
    — while the slot-row shims read ``self.cache`` as it is when called.

    ``programs`` are the compiled callables (:class:`Programs`);
    ``new_pool()`` bears a pool; ``world`` is how a flat per-slot array is
    laid out for the programs, always as host arrays, numpy straight into
    jit — ``None``: as it is; ``W``: reshaped ``[W, rows / W, ...]``, adapter
    tables broadcast ``[W, ...]``; ``rungs`` are the row counts the chunked
    prefill may be called at (:func:`prefill_rung`); ``experts_held`` is
    experts held x expert layers where the decode and verify programs
    return, last before the pool, how many experts' weights the step read
    (``[W]`` int32; the MoE stack's do), and 0 where they return no such
    count."""

    def __init__(self, params, cfg, programs: Programs, new_pool: Callable, *,
                 n_slots: int, max_seq: int, rungs: tuple,
                 world: Optional[int] = None, experts_held: int = 0):
        self.params = params
        self.cfg = cfg
        self.traits = pool_traits(cfg)
        self.programs = programs
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.prefill_rungs = rungs
        self.world = world
        self.experts_held = experts_held
        self._new_pool = new_pool
        self.cache = new_pool()
        self._rungs_built = set()  # (chunk, sampled, adapted) kinds

    def clone(self, params=None) -> "SlotBackend":
        """A same-shape backend with a pool of its own that shares this
        one's compiled programs (they are pure in params and keep nothing of
        a pool), so it costs zero new compiles; serving ``params`` if given:
        a pytree (or a weight-push snapshot's ``tree()``) of this backend's
        tree structure and leaf shapes, cast leaf by leaf to the dtypes
        served now — anything else is refused before it could serve a stale
        mix."""
        twin = copy.copy(self)
        twin.cache = self._new_pool()
        twin._rungs_built = set()
        if params is not None:
            if hasattr(params, "tree"):
                params = params.tree()
            want, want_def = jax.tree_util.tree_flatten(self.params)
            got, got_def = jax.tree_util.tree_flatten(params)
            if want_def != got_def or len(want) != len(got):
                raise ValueError(
                    f"pushed weight tree does not match the prototype's "
                    f"params (treedef {got_def} vs {want_def})"
                )
            for w, g in zip(want, got):
                if tuple(np.shape(w)) != tuple(np.shape(g)):
                    raise ValueError(
                        f"pushed weight leaf shape {np.shape(g)} != "
                        f"prototype {np.shape(w)}"
                    )
            twin.params = jax.tree_util.tree_map(
                lambda w, g: jnp.asarray(g, dtype=w.dtype), self.params,
                params)
        return twin

    # -- the one stage -> launch -> fetch sequence --------------------------
    def _lay(self, flat, dtype):
        """A flat per-row array as the programs take it: a HOST array of
        ``dtype``, ``[W, rows / W, ...]`` over a ``world``. The compiled
        call places all of a call's arguments itself, in one hand-over."""
        flat = np.asarray(flat, dtype)
        if self.world is None:
            return flat
        return flat.reshape((self.world, -1) + flat.shape[1:])

    def _launch(self, kind, per_row, sampling, adapters, **rows_kw) -> list:
        """The first half of one call of the program ``kind`` ("prefill" |
        "decode" | "verify"): stage its host arrays and launch it.
        ``per_row``: its (flat array, dtype) arguments in order;
        ``rows_kw``: per-row int32 keyword arguments (None = not passed).
        Returns every output but the pool, NOT YET READ (device arrays the
        asynchronous dispatch has promised; :meth:`_fetch` reads them). The
        per-row arguments go in as host arrays (:meth:`_lay`), one
        hand-over. The program consumes the pool it is handed and
        ``self.cache`` becomes the one it gives back, in one statement:
        nothing else may hold the pool across a call — and that holds
        launch by launch when two are in flight: a second launch before the
        first is read takes the pool the first left in ``self.cache`` (a
        promise too: no host wait), so the device runs the programs in the
        order they were launched, on one pool."""
        with obs.span("backend.stage", "wire"):
            kw = {}
            if sampling is not None:
                kw["sampling"] = tuple(
                    self._lay(a, dt)
                    for a, dt in zip(sampling, _SAMPLING_DTYPES))
            if adapters is not None:
                tables, ids = adapters
                if self.world is not None:
                    tables = {
                        t: tuple(jnp.broadcast_to(a, (self.world,) + a.shape)
                                 for a in ab)
                        for t, ab in tables.items()}
                kw["adapters"] = tables
                kw["adapter_ids"] = self._lay(ids, np.int32)
            args = [self._lay(a, dt) for a, dt in per_row]
            for name, a in rows_kw.items():
                if a is not None:
                    kw[name] = self._lay(a, np.int32)
        with obs.span("backend.launch", "wire"):
            pool = self.cache
            try:
                *out, self.cache = getattr(self.programs, kind)(
                    self.params, *args, pool, **kw)
            except Exception as e:
                if _consumed(pool):
                    raise RuntimeError(
                        f"the {kind} program failed after it had consumed "
                        f"the slot pool: this backend's cached rows are "
                        f"gone and every request holding a slot with them"
                    ) from e
                raise
            if _consumed(pool):
                _POOL_IN_PLACE.inc(program=kind)
        return out

    def _fetch(self, kind, out) -> list:
        """The second half: what :meth:`_launch` returned, read — one
        blocking read, every copy started before the first is waited for —
        and flat per row again; a decode / verify call's experts count
        taken off the end and counted."""
        with obs.span("backend.fetch", "wire"):
            out = jax.device_get(out)
            if self.world is not None:  # [W, rows / W, ...] -> [rows, ...]
                out = [o.reshape((-1,) + o.shape[2:]) for o in out]
        if self.experts_held and kind != "prefill":
            self._count_experts(int(out.pop().sum()))
        return out

    def _count_experts(self, read: int) -> None:
        """One decode / verify step's experts read, on the two counters and
        as the arguments of an ``ep.experts`` mark inside the step's
        ``wire.decode`` / ``wire.verify``, after the fetch that brought the
        count."""
        _EXPERTS_READ.inc(read)
        _EXPERTS_HELD.inc(self.experts_held)
        obs.mark("ep.experts", "wire", experts_read=read,
                 experts_held=self.experts_held)

    def prefill(self, tokens: np.ndarray, lens: np.ndarray,
                mask: np.ndarray,
                start: Optional[np.ndarray] = None,
                sampling=None, adapters=None,
                slots: Optional[np.ndarray] = None) -> np.ndarray:
        """One prefill program. Whole-pool form: every argument is
        [n_slots, ...] and row s is slot s. Compact form (``slots`` [R]
        given, a chunked call on a rung below the pool): every argument and
        the returned tokens are [R, ...] and row r is slot ``slots[r]``."""
        return self.fetch(self.launch_prefill(
            tokens, lens, mask, start, sampling, adapters, slots))

    def launch_prefill(self, tokens, lens, mask, start=None, sampling=None,
                       adapters=None, slots=None) -> tuple:
        """:meth:`prefill` as far as its launch: the call, for
        :meth:`fetch` to read. What the engine's chunk step calls apart, so
        that its decode program is launched before this one is read."""
        if start is None:
            start = np.zeros(tokens.shape[0], np.int32)
        else:  # a chunked call
            self._build_other_rungs(*tokens.shape, sampling, adapters)
        return self._launch_prefill(tokens, lens, mask, start, sampling,
                                    adapters, slots)

    def _launch_prefill(self, tokens, lens, mask, start, sampling, adapters,
                        slots) -> tuple:
        return "prefill", self._launch(
            "prefill",
            [(tokens, np.int32), (lens, np.int32), (mask, bool)],
            sampling, adapters, start=start, slots=slots)

    def fetch(self, call: tuple) -> np.ndarray:
        """The tokens of a launched prefill or decode call (the one
        blocking read of :meth:`_fetch`). Calls are read in the order they
        were launched."""
        return self._fetch(*call)[0]

    def _build_other_rungs(self, rows: int, chunk: int, sampling,
                           adapters) -> None:
        """All rungs are built when the first one is: before the first
        chunked call of a (chunk, sampled, adapted) kind runs its own rung
        (``rows``), run each OTHER rung once with an all-false mask on the
        live pool — a no-op on its contents — so a later change of
        occupancy finds its program traced, lowered and loaded. A warm-up
        that only ever has one slot prefilling then leaves nothing to
        compile in flight."""
        key = (chunk, sampling is not None, adapters is not None)
        if key in self._rungs_built:
            return
        self._rungs_built.add(key)
        for r in self.prefill_rungs:
            if r == rows:
                continue
            samp = adp = None
            if sampling is not None:
                samp = tuple(np.zeros(r, np.asarray(a).dtype)
                             for a in sampling)
            if adapters is not None:
                adp = (adapters[0], np.zeros(r, np.int32))
            self.fetch(self._launch_prefill(
                np.zeros((r, chunk), np.int32), np.ones(r, np.int32),
                np.zeros(r, bool), np.zeros(r, np.int32), samp, adp,
                # padding rows all: an index past the pool, dropped on the
                # way back; the pool rung is the ungathered program
                None if r == self.n_slots
                else np.full(r, self.n_slots, np.int32)))

    def decode(self, tokens: np.ndarray, active: np.ndarray,
               sampling=None, adapters=None) -> np.ndarray:
        return self.fetch(self.launch_decode(tokens, active, sampling,
                                             adapters))

    def launch_decode(self, tokens, active, sampling=None,
                      adapters=None) -> tuple:
        """:meth:`decode` as far as its launch (see
        :meth:`launch_prefill`)."""
        return "decode", self._launch(
            "decode",
            [(tokens, np.int32), (active, bool)], sampling, adapters)

    def verify(self, tokens: np.ndarray, active: np.ndarray,
               sampling=None, adapters=None):
        """One batched [n_slots, k+1] draft-verify window (spec decode):
        returns (target tokens [n_slots, k+1], n_accepted [n_slots]) —
        greedy argmaxes, or lockstep-keyed samples under ``sampling``."""
        return tuple(self._fetch("verify", self._launch(
            "verify",
            [(tokens, np.int32), (active, bool)], sampling, adapters)))

    # slot KV movement (prefix-cache hits + the disagg p2p stream) — thin
    # shims over the pool's export/import views, which take flat slot ids
    # (MoESlotCache maps them to its [W, B_loc] grid itself)
    def export_slot_kv(self, slot: int, lo: int, hi: int):
        return self.cache.export_rows(slot, lo, hi)

    def import_slot_kv(self, slot: int, k_rows, v_rows, *,
                       length: int) -> None:
        self.cache = self.cache.import_rows(slot, k_rows, v_rows,
                                            length=length)

    def copy_slot_prefix(self, dst: int, src: int, n: int) -> None:
        self.cache = self.cache.copy_prefix(dst, src, n)


class DenseBackend(SlotBackend):
    """The slot backend over the dense KV stack (models/inference.py).
    ``fns`` shares another backend's compiled-program cache (what
    :meth:`SlotBackend.clone` does without being asked)."""

    def __init__(self, params, cfg, *, n_slots: int, max_seq: int,
                 fns: Optional[LRUFnCache] = None):
        super().__init__(
            params, cfg, DensePrograms(cfg, fns),
            partial(SlotKVCache.empty, cfg, n_slots, max_seq),
            n_slots=n_slots, max_seq=max_seq, rungs=prefill_rungs(n_slots))


class MoEBackend(SlotBackend):
    """The slot backend over the EP-sharded MoE stack: slots are the
    [W, B_loc] rows of the server's pool (slot s <-> shard s // B_loc, row
    s % B_loc); prefill routes through the sorted EP path, decode through
    ``decode_impl`` (the packed LL path, the DeepEP decode regime, by
    default); the programs are those of ``server`` (a
    ``models.moe_inference.MoEServer``, which caches them by shape). On one
    shard the prefill runs at the three rungs; over
    ``world > 1`` shards the backend declares the whole-pool rung alone (a
    compact call there would have to be sized by the fullest shard and
    padded per shard; no cell runs it, so it keeps the program it had)."""

    def __init__(self, server, params, *, batch_local: int, max_seq: int,
                 decode_impl: str = "ll"):
        self.server = server
        n_slots = server.world * batch_local
        super().__init__(
            params, server.cfg,
            Programs(server.prefill_slots,
                     partial(server.decode_step_slots, impl=decode_impl),
                     server.verify_slots),
            partial(server.slot_cache, batch_local, max_seq),
            n_slots=n_slots, max_seq=max_seq, world=server.world,
            rungs=(prefill_rungs(n_slots) if server.world == 1
                   else (n_slots,)),
            experts_held=server.cfg.n_held * server.cfg.n_moe_layers)


def replicate_backend(backend, n: int, weights=None) -> List:
    """``n`` replica backends from one prototype — THE sharing rule for a
    replica set (serve.py and serving_bench both build through here, so
    it can't drift): every replica owns its KV pool and all share the
    prototype's compiled programs (:meth:`SlotBackend.clone`) — N replicas
    cost one warm-up.

    ``weights``: a fetched weight-push snapshot
    (:class:`uccl_tpu.p2p.weight_push.WeightSnapshot`) or a param pytree
    — every replica INCLUDING the prototype serves these params instead
    of the prototype's in-memory ones. This is the fleet spin-up path:
    replicas import the published version off the p2p wire (its bytes
    already counted on ``p2p_bytes_total{verb="weight_push"}``) rather
    than cloning untracked host references. The tree must match the
    prototype's params; a mismatch fails loudly (``clone``)."""
    if n < 1:
        raise ValueError(f"need n >= 1 replicas, got {n}")
    if weights is not None:
        backend = backend.clone(weights)
    return [backend] + [backend.clone() for _ in range(1, n)]
