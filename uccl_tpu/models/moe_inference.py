"""MoE serving: KV-cache prefill / decode / generate with EP-sharded experts.

DeepEP's low-latency mode exists for DECODE (reference ep/README — the LL
kernels target inference token-by-token latency, ep/src/internode_ll.cu).
This module puts the framework's EP paths into the serving loop they were
built for:

* **prefill** routes the whole prompt through the throughput path
  (``impl="sort"``: one argsort + capacity-bucketed all-to-all);
* **decode** runs each autoregressive step through the packed low-latency
  path (``impl="ll"``: per-expert packed rows + recv counts, grouped
  ``lax.ragged_dot`` — no padding on wire or MXU at batch-sized token
  counts, exactly the LL regime).

Experts shard over the mesh's ``dp`` axis (contiguous ownership: expert e
lives on shard ``e // E_local``, the layout both EP paths assume); the
batch shards with them and every array carries the Buffer-convention
leading shard dim. Attention/caches reuse the dense serving math
(:mod:`uccl_tpu.models.inference`).

Parity property (tested): the same weights served on a 1-shard mesh and a
W-shard mesh generate identical tokens — sharding is semantics-free.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models.inference import (
    KVCache, SlotKVCache, _forward_cached, _forward_slots,
    greedy_acceptance, spec_advance,
)
from uccl_tpu.models.sampling import (
    broadcast_params, sample_tokens, sample_window,
)
from uccl_tpu.utils.lru import LRUFnCache

_AXIS = "dp"  # the EP/serving axis of the mesh


@dataclass(frozen=True)
class MoEServeConfig:
    vocab: int = 512
    dim: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    moe_experts: int = 8
    moe_topk: int = 2
    moe_ffn: int = 256
    capacity_factor: float = 8.0  # ample by default: serving wants no drops
    moe_wire: str = "lax"  # "lax" | "pallas" (device-initiated a2a wire)
    moe_chunks: int = 0  # pallas chunk-pipeline depth (0 = auto: overlap
    # prefill's expert GEMMs with the dispatch/combine wire; no-op on lax)
    wire_dtype: Optional[str] = None  # None | "fp8" | "int8": block-scale
    # quantized EP wire payloads (shared ops.quant codec; one quantize
    # round trip of error per exchange — docs/QUANT_WIRE.md)


class MoEKVCache(NamedTuple):
    k: jax.Array  # [W, L, B_loc, S_max, Hkv, D]
    v: jax.Array
    length: jax.Array  # [W] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32) -> "MoEKVCache":
        shape = (world, cfg.n_layers, batch_local, max_seq,
                 cfg.n_kv_heads, cfg.head_dim)
        return MoEKVCache(
            jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.zeros((world,), jnp.int32),
        )


class MoESlotCache(NamedTuple):
    """Slot-pool KV cache: one length PER SLOT (not per shard) — the
    continuous-batching engine admits/frees [w, b_loc] rows independently."""

    k: jax.Array  # [W, L, B_loc, S_max, Hkv, D]
    v: jax.Array
    lengths: jax.Array  # [W, B_loc] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32) -> "MoESlotCache":
        shape = (world, cfg.n_layers, batch_local, max_seq,
                 cfg.n_kv_heads, cfg.head_dim)
        return MoESlotCache(
            jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
            jnp.zeros((world, batch_local), jnp.int32),
        )

    # -- slot KV export/import views (the disaggregation surface) ----------
    #
    # Mirrors inference.SlotKVCache: a flat slot id s maps to grid row
    # (w, b) = (s // B_loc, s % B_loc). Exports/imports go through host
    # numpy round-trips — np.asarray gathers a sharded pool, and the next
    # shard_mapped call re-shards the rebuilt arrays — which keeps the
    # surface correct on any mesh at the cost of a pool copy per call
    # (admission-rate work, not step-rate).

    def _loc(self, slot: int):
        b_loc = self.k.shape[2]
        return slot // b_loc, slot % b_loc

    def export_rows(self, slot: int, lo: int, hi: int):
        """Host copies of rows [lo, hi): (k, v) each [L, hi-lo, Hkv, D] —
        the same per-slot layout the dense cache exports, so the disagg
        wire format is stack-independent."""
        import numpy as np

        w, b = self._loc(slot)
        return (np.asarray(self.k[w, :, b, lo:hi]),
                np.asarray(self.v[w, :, b, lo:hi]))

    def import_rows(self, slot: int, k_rows, v_rows, *,
                    length: int) -> "MoESlotCache":
        import numpy as np

        w, b = self._loc(slot)
        n = k_rows.shape[1]
        # np.array (not asarray): device gathers come back read-only
        k = np.array(self.k)
        v = np.array(self.v)
        lengths = np.array(self.lengths)
        k[w, :, b, :n] = np.asarray(k_rows, k.dtype)
        v[w, :, b, :n] = np.asarray(v_rows, v.dtype)
        lengths[w, b] = length
        return MoESlotCache(jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(lengths))

    def copy_prefix(self, dst: int, src: int, n: int) -> "MoESlotCache":
        import numpy as np

        dw, db = self._loc(dst)
        sw, sb = self._loc(src)
        k = np.array(self.k)
        v = np.array(self.v)
        lengths = np.array(self.lengths)
        k[dw, :, db, :n] = k[sw, :, sb, :n]
        v[dw, :, db, :n] = v[sw, :, sb, :n]
        lengths[dw, db] = n
        return MoESlotCache(jnp.asarray(k), jnp.asarray(v),
                            jnp.asarray(lengths))


def init_params(key: jax.Array, cfg: MoEServeConfig) -> Dict[str, Any]:
    """Global parameter tree (experts carry the full [E, ...] axis)."""
    k = jax.random.split(key, 12)
    h, l, f, e = cfg.dim, cfg.n_layers, cfg.moe_ffn, cfg.moe_experts
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    s_in, s_f = 1.0 / math.sqrt(h), 1.0 / math.sqrt(f)

    def rnd(kk, shape, scale):
        return jax.random.normal(kk, shape, jnp.float32) * scale

    return {
        "embed": rnd(k[0], (cfg.vocab, h), 0.02),
        "blocks": {
            "ln1": jnp.ones((l, h), jnp.float32),
            "ln2": jnp.ones((l, h), jnp.float32),
            "wq": rnd(k[1], (l, h, qd), s_in),
            "wk": rnd(k[2], (l, h, kvd), s_in),
            "wv": rnd(k[3], (l, h, kvd), s_in),
            "wo": rnd(k[4], (l, qd, h), 1.0 / math.sqrt(qd)),
            "router": rnd(k[5], (l, h, e), s_in),
            "we_gate": rnd(k[6], (l, e, h, f), s_in),
            "we_up": rnd(k[7], (l, e, h, f), s_in),
            "we_down": rnd(k[8], (l, e, f, h), s_f),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd(k[9], (h, cfg.vocab), s_in),
    }


def _moe_block(cfg: MoEServeConfig, impl: str):
    """The EP MoE FFN as an :func:`inference._forward_cached`-style ``ffn``
    hook: route over the EP axis (sorted path for prefill throughput,
    packed LL for decode), experts being the LOCAL shard."""

    def moe_block(h2, lp):
        b, sq, hd = h2.shape
        flat = h2.reshape(b * sq, hd)
        with jax.named_scope("moe.router"):
            router_logits = flat.astype(jnp.float32) @ lp["router"]
        out, _, _ = ep_ops.moe_ffn(
            flat, router_logits,
            lp["we_gate"], lp["we_up"], lp["we_down"],
            _AXIS,
            num_selected=cfg.moe_topk,
            capacity_factor=cfg.capacity_factor,
            impl=impl,
            wire=cfg.moe_wire,
            n_chunks=cfg.moe_chunks,
            wire_dtype=cfg.wire_dtype,
        )
        return out.reshape(b, sq, hd)

    return moe_block


def _forward_shard(params, tokens, k_cache, v_cache, length,
                   cfg: MoEServeConfig, impl: str):
    """Per-shard cached forward: the dense serving loop
    (inference._forward_cached — attention/rope/KV updates exist exactly
    once) with the FFN block swapped for the EP MoE layer. Experts are the
    LOCAL shard ([E_local, ...]); the MoE FFN exchanges tokens over the EP
    axis (sorted path for prefill throughput, packed LL for decode)."""
    cache = KVCache(k_cache, v_cache, length)
    logits, cache = _forward_cached(
        params, tokens, cache, cfg, ffn=_moe_block(cfg, impl)
    )
    return logits, cache.k, cache.v, cache.length


def _forward_shard_slots(params, tokens, k_cache, v_cache, lengths, start,
                         write_mask, cfg: MoEServeConfig, impl: str,
                         adapters=None, adapter_ids=None):
    """Per-shard masked slot forward (the continuous-batching primitive):
    the dense slot-pool loop (inference._forward_slots — per-slot positions,
    write-gated KV, per-slot attention masks) with the EP MoE FFN. Idle
    slots' dummy tokens do route through the experts — harmless: expert
    GEMM rows are independent and the ample serving capacity_factor keeps
    the wire drop-free (every expert queue holds all T rows of its source,
    ``ep_ops.expert_capacity``, and not a row more), so active rows are
    bit-identical to a batch without the dummies.
    ``adapters``/``adapter_ids`` are the per-slot fused LoRA tables
    (inference._lora_delta) — the attention projections are dense-stack
    code, so the ONE fusion point serves both stacks."""
    cache = SlotKVCache(k_cache, v_cache, lengths)
    logits, cache = _forward_slots(
        params, tokens, cache, start, write_mask, cfg,
        ffn=_moe_block(cfg, impl),
        adapters=adapters, adapter_ids=adapter_ids,
    )
    return logits, cache.k, cache.v


def _strip_shard(p):
    """Drop the per-shard leading dim shard_map hands each member:
    replicated leaves carry it LEADING ([1, ...] broadcast slice); expert
    leaves carry it at axis 1 ([L, 1, E_local, ...] — the sharded W axis
    of shard_params)."""
    blocks = {}
    for name, leaf in p["blocks"].items():
        if name in ("we_gate", "we_up", "we_down"):
            blocks[name] = leaf[:, 0]
        else:
            blocks[name] = leaf[0]
    return {
        "embed": p["embed"][0],
        "blocks": blocks,
        "final_norm": p["final_norm"][0],
        "head": p["head"][0],
    }


class MoEServer:
    """Cached jitted prefill/decode over an EP mesh (one compile per shape).

    ``mesh`` must carry a ``dp`` axis; experts and batch shard over it.
    """

    def __init__(self, cfg: MoEServeConfig, mesh: Mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.world = mesh.shape[_AXIS]
        if cfg.moe_experts % self.world:
            raise ValueError(
                f"the dp world {self.world} must divide moe_experts "
                f"{cfg.moe_experts}"
            )
        # the shared LRU-bounded compiled-fn pattern (utils/lru.py): a
        # long-lived serving process sweeping shapes (prefill buckets,
        # several decode batch tiers, varying scan lengths) would
        # otherwise retain a compiled executable per shape forever
        self._fns = LRUFnCache(16)

    # -- parameter placement ------------------------------------------------
    def shard_params(self, params):
        """Place the global tree for serving, ONCE: expert [E, ...] axes
        become the Buffer-convention sharded [L, W, E_local, ...]; every
        replicated leaf gains a broadcast [W] leading dim. Done here (not
        per forward) so each decode step feeds the SAME arrays through the
        jit boundary instead of re-tiling params every token."""
        w = self.world
        e_local = self.cfg.moe_experts // w

        def place(name, leaf):
            if name in ("we_gate", "we_up", "we_down"):
                l = leaf.shape[0]
                return leaf.reshape((l, w, e_local) + leaf.shape[2:])
            return jnp.broadcast_to(leaf, (w,) + leaf.shape)

        blocks = {
            name: place(name, leaf)
            for name, leaf in params["blocks"].items()
        }
        return {
            "embed": jnp.broadcast_to(
                params["embed"], (w,) + params["embed"].shape
            ),
            "blocks": blocks,
            "final_norm": jnp.broadcast_to(
                params["final_norm"], (w,) + params["final_norm"].shape
            ),
            "head": jnp.broadcast_to(
                params["head"], (w,) + params["head"].shape
            ),
        }

    def _fn(self, key, build):
        return self._fns.get(key, build)

    @staticmethod
    def _param_specs():
        # replicated leaves shard their broadcast leading [W] dim;
        # expert leaves shard the [W] at axis 1 ([L, W, E_local, ...])
        def block_spec(name):
            if name in ("we_gate", "we_up", "we_down"):
                return P(None, _AXIS)
            return P(_AXIS)

        return {
            "embed": P(_AXIS),
            "blocks": {
                name: block_spec(name)
                for name in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                             "router", "we_gate", "we_up", "we_down")
            },
            "final_norm": P(_AXIS),
            "head": P(_AXIS),
        }

    def _shard_mapped(self, f, n_in, n_out):
        """jit(shard_map(f)) with params first, then n_in P(dp) arrays.
        The compiled program is named after ``f`` (``jit_<f.__name__>`` on
        the profiler's ``XLA Modules`` line, and part of the persistent
        compile cache's key), so each closure carries a name of its own."""
        return jax.jit(
            shard_map(
                f, mesh=self.mesh,
                in_specs=(self._param_specs(),) + (P(_AXIS),) * n_in,
                out_specs=(P(_AXIS),) * n_out,
                check_vma=False,
            )
        )

    def _forward(self, params, tokens, cache: MoEKVCache, impl: str):
        cfg = self.cfg

        def uccl_moe_forward(p, tok, kc, vc, ln):
            logits, nk, nv, nlen = _forward_shard(
                _strip_shard(p), tok[0], kc[0], vc[0], ln[0], cfg, impl
            )
            return logits[None], nk[None], nv[None], nlen[None]

        key = ("fwd", impl, tokens.shape, cache.k.shape)
        fn = self._fn(
            key, lambda: self._shard_mapped(uccl_moe_forward, 4, 4))
        logits, nk, nv, nlen = fn(params, tokens, cache.k, cache.v,
                                  cache.length)
        return logits, MoEKVCache(nk, nv, nlen)

    # -- public serving API -------------------------------------------------
    def prefill(self, params, tokens, max_seq: int):
        """tokens: [W, B_loc, S_prompt] → (last logits [W, B_loc, V], cache).
        Throughput path (sorted dispatch)."""
        w, b, s = tokens.shape
        if s > max_seq:
            raise ValueError(f"prompt {s} exceeds max_seq {max_seq}")
        cache = MoEKVCache.empty(self.cfg, w, b, max_seq)
        logits, cache = self._forward(params, tokens, cache, impl="sort")
        return logits[:, :, -1], cache

    def decode_step(self, params, token, cache: MoEKVCache,
                    impl: str = "ll"):
        """token: [W, B_loc] → (logits [W, B_loc, V], cache'). Low-latency
        packed EP path by default — the DeepEP LL decode regime."""
        logits, cache = self._forward(
            params, token[..., None], cache, impl=impl
        )
        return logits[:, :, 0], cache

    # -- slot-pool serving API (continuous batching) ------------------------
    def _check_drop_free(self):
        """The slot-serving oracle guarantee (bit-exact vs one-shot
        generate) requires the EP wire to be DROP-FREE for any routing:
        per-expert capacity = min(floor(cf·T·topk/E), T)
        (``ep_ops.expert_capacity``) must cover the worst case of all T
        tokens picking the same expert (topk experts are distinct per
        token, so one expert receives at most T rows — which is also why
        the queue stops at T) — i.e. cf·topk ≥ E. Otherwise idle-slot
        dummies and co-scheduled neighbors could crowd a request's tokens
        past capacity and change its output depending on who shares the
        batch."""
        cfg = self.cfg
        if cfg.capacity_factor * cfg.moe_topk < cfg.moe_experts:
            raise ValueError(
                f"slot serving needs a drop-free EP wire: capacity_factor "
                f"({cfg.capacity_factor}) * moe_topk ({cfg.moe_topk}) must "
                f"be >= moe_experts ({cfg.moe_experts}), or request "
                f"outputs would depend on batch composition"
            )

    def slot_cache(self, batch_local: int, max_seq: int) -> MoESlotCache:
        """The engine's fixed [W, B_loc, S_max] KV pool (per-slot lengths)."""
        self._check_drop_free()
        return MoESlotCache.empty(self.cfg, self.world, batch_local, max_seq)

    @staticmethod
    def _extra_args(sampling, adapters, adapter_ids):
        """Flatten the optional sampled/adapted arguments into the flat
        P(dp)-sharded arg list ``_shard_mapped`` expects: 5 gridded
        [W, B_loc] sampling arrays, then 4 broadcast [W, ...] adapter
        tables + gridded adapter ids. The caller grids/broadcasts; the
        shard fns strip the leading shard dim."""
        extra = []
        if sampling is not None:
            extra.extend(sampling)
        if adapters is not None:
            extra.extend([adapters["wq"][0], adapters["wq"][1],
                          adapters["wv"][0], adapters["wv"][1],
                          adapter_ids])
        return extra

    @staticmethod
    def _split_extra(rest, sampled: bool, adapted: bool):
        """Inverse of :meth:`_extra_args` inside a shard fn (leading shard
        dim stripped): returns (sampling tuple | None, adapters | None,
        adapter_ids | None)."""
        rest = list(rest)
        samp = None
        if sampled:
            samp = tuple(r[0] for r in rest[:5])
            rest = rest[5:]
        adp = ids = None
        if adapted:
            adp = {"wq": (rest[0][0], rest[1][0]),
                   "wv": (rest[2][0], rest[3][0])}
            ids = rest[4][0]
        return samp, adp, ids

    def prefill_slots(self, params, tokens, prompt_lens, new_mask,
                      cache: MoESlotCache, start=None, sampling=None,
                      adapters=None, adapter_ids=None):
        """Masked batched prefill of newly admitted slots (sorted EP path)
        — resumable, mirroring :func:`inference.prefill_slots`.

        tokens: [W, B_loc, S] right-padded prompt windows; prompt_lens (FULL
        prompt lengths)/new_mask: [W, B_loc]; start: [W, B_loc] int32
        per-slot offsets (None = zeros, the whole-prompt path). Row (w, b)
        carries prompt positions [start, start+S): KV is written only there,
        attention covers [0, start+S) — chunked prefill splits the same math
        along the sequence axis (the drop-free EP wire keeps expert rows
        independent), so resuming in chunks stays bit-exact. Slots outside
        ``new_mask`` keep their KV rows and lengths — mid-decode neighbors
        are untouched. Returns (greedy token [W, B_loc] — meaningful only
        for rows whose window reaches the prompt end — and cache with
        lengths set to min(start+S, prompt_lens) on admitted slots).

        ``sampling``: per-slot gridded [W, B_loc] ``(seeds, pos0, temp,
        top_p, top_k)`` arrays — the window-end token is then the
        lockstep-keyed sample instead of the argmax (mirrors
        :func:`inference.prefill_slots`). ``adapters``/``adapter_ids``
        fuse the per-slot LoRA delta (tables broadcast [W, ...],
        ids gridded [W, B_loc])."""
        self._check_drop_free()
        cfg = self.cfg
        s = tokens.shape[-1]
        if start is None:
            start = jnp.zeros_like(prompt_lens)
        sampled, adapted = sampling is not None, adapters is not None
        extra = self._extra_args(sampling, adapters, adapter_ids)

        def uccl_moe_prefill_slots(p, tok, lens, mask, off, kc, vc, ln,
                                   *rest):
            samp, adp, ids = self._split_extra(rest, sampled, adapted)
            logits, nk, nv = _forward_shard_slots(
                _strip_shard(p), tok[0], kc[0], vc[0], ln[0],
                off[0], mask[0], cfg, "sort",
                adapters=adp, adapter_ids=ids,
            )
            last_idx = jnp.clip(lens[0] - 1 - off[0], 0, s - 1)
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1
            )[:, 0]
            if samp is None:
                t = jnp.argmax(last, axis=-1).astype(jnp.int32)
            else:
                seeds, pos0, temp, top_p, top_k = samp
                t = sample_tokens(seeds, pos0, last, temp, top_p, top_k)
            nlen = jnp.where(
                mask[0], jnp.minimum(off[0] + s, lens[0]), ln[0]
            )
            return t[None], nk[None], nv[None], nlen[None]

        key = ("prefill_slots", tokens.shape, cache.k.shape,
               sampled, adapted)
        fn = self._fn(key, lambda: self._shard_mapped(
            uccl_moe_prefill_slots, 7 + len(extra), 4))
        tok, nk, nv, nlen = fn(params, tokens, prompt_lens, new_mask,
                               start, cache.k, cache.v, cache.lengths,
                               *extra)
        return tok, MoESlotCache(nk, nv, nlen)

    def verify_slots(self, params, tokens, active, cache: MoESlotCache,
                     impl: str = "sort", sampling=None, adapters=None,
                     adapter_ids=None):
        """Batched draft verification over the slot pool — the speculative-
        decoding primitive, generalizing :meth:`decode_step_slots` from one
        token to a window (mirrors :func:`inference.verify_slots`).

        tokens: [W, B_loc, S] where column 0 is each slot's last committed
        token and columns 1..S-1 its drafted continuation; active:
        [W, B_loc] bool. Greedy acceptance = longest draft prefix matching
        the window's own greedy argmaxes; active slots advance their length
        by ``n_accepted + 1``; rejected-position KV is dead by the
        chunked-prefill stale-KV argument (the next window re-writes it
        before attending). Routes through the sorted EP path by default —
        the multi-token regime, like prefill; the drop-free capacity check
        keeps every routing exact regardless of window width. Returns
        (target tokens [W, B_loc, S], n_accepted [W, B_loc], cache').

        With ``sampling`` (gridded [W, B_loc] per-slot arrays), window
        column j is sampled under the lockstep key for output position
        ``pos0 + j`` — the same acceptance rule against sampled targets
        is exact rejection sampling for deterministic drafters
        (:func:`inference.verify_slots`, docs/SERVING.md)."""
        self._check_drop_free()
        cfg = self.cfg
        sampled, adapted = sampling is not None, adapters is not None
        extra = self._extra_args(sampling, adapters, adapter_ids)

        def uccl_moe_verify_slots(p, tok, mask, kc, vc, ln, *rest):
            samp, adp, ids = self._split_extra(rest, sampled, adapted)
            logits, nk, nv = _forward_shard_slots(
                _strip_shard(p), tok[0], kc[0], vc[0], ln[0],
                ln[0], mask[0], cfg, impl,
                adapters=adp, adapter_ids=ids,
            )
            if samp is None:
                t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                seeds, pos0, temp, top_p, top_k = samp
                t = sample_window(seeds, pos0, logits, temp, top_p, top_k)
            n_acc = greedy_acceptance(tok[0], t)
            nlen = spec_advance(ln[0], mask[0], n_acc)
            return t[None], n_acc[None], nk[None], nv[None], nlen[None]

        key = ("verify_slots", impl, tokens.shape, cache.k.shape,
               sampled, adapted)
        fn = self._fn(key, lambda: self._shard_mapped(
            uccl_moe_verify_slots, 5 + len(extra), 5))
        tok, n_acc, nk, nv, nlen = fn(params, tokens, active,
                                      cache.k, cache.v, cache.lengths,
                                      *extra)
        return tok, n_acc, MoESlotCache(nk, nv, nlen)

    def decode_step_slots(self, params, token, active, cache: MoESlotCache,
                          impl: str = "ll", sampling=None, adapters=None,
                          adapter_ids=None):
        """One masked autoregressive step over the slot pool (packed LL EP
        path by default) — the S=1 case of :meth:`verify_slots`.
        token/active: [W, B_loc]; inactive slots neither write KV nor
        advance their length. Returns (next greedy-or-sampled token
        [W, B_loc], cache')."""
        tok, _, cache = self.verify_slots(params, token[..., None], active,
                                          cache, impl=impl,
                                          sampling=sampling,
                                          adapters=adapters,
                                          adapter_ids=adapter_ids)
        return tok[..., 0], cache

    def generate(self, params, prompt, new_tokens: int, max_seq: int,
                 impl: str = "ll", sampling=None):
        """Greedy (or, with ``sampling``, stochastic) decode.
        prompt: [W, B_loc, S] → tokens [W, B_loc, N].

        ``sampling`` duck-types SamplingParams: every grid row runs under
        the request's seed with lockstep keys per output index, and the
        scalars enter as traced jit arguments — the sampled one-shot
        oracle of the MoE serving stack (mirrors ``inference.generate``).

        The decode loop is ONE jitted ``lax.scan`` over ``new_tokens``
        (cached per (impl, N, shapes) like every other program here), not
        a Python loop of per-token dispatches: the scan carries
        (token, cache) on-device and only the final [W, B_loc, N] token
        block crosses the host boundary."""
        if new_tokens < 1:
            raise ValueError(f"new_tokens must be >= 1, got {new_tokens}")
        if prompt.shape[-1] + new_tokens > max_seq:
            raise ValueError(
                f"prompt {prompt.shape[-1]} + new {new_tokens} tokens "
                f"exceed max_seq {max_seq}: the cache would overflow"
            )
        logits, cache = self.prefill(params, prompt, max_seq)
        if sampling is None:
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key = ("gen", impl, new_tokens, tok0.shape, cache.k.shape)

            def build():
                def uccl_moe_generate(p, tok, kc, vc, ln):
                    def body(carry, _):
                        tok, kc, vc, ln = carry
                        lg, c2 = self._forward(
                            p, tok[..., None], MoEKVCache(kc, vc, ln), impl
                        )
                        ntok = jnp.argmax(lg[:, :, 0],
                                          axis=-1).astype(jnp.int32)
                        return (ntok, c2.k, c2.v, c2.length), tok

                    _, toks = lax.scan(
                        body, (tok, kc, vc, ln), None, length=new_tokens
                    )
                    return jnp.moveaxis(toks, 0, -1)  # [W, B_loc, N]

                return jax.jit(uccl_moe_generate)

            fn = self._fn(key, build)
            return fn(params, tok0, cache.k, cache.v, cache.length)

        key = ("gen_sampled", impl, new_tokens, logits.shape, cache.k.shape)

        def build():
            def uccl_moe_generate_sampled(p, lg0, kc, vc, ln, seed, temp,
                                          top_p, top_k):
                w, b, v = lg0.shape
                seeds, temps, tps, tks = broadcast_params(
                    w * b, seed, temp, top_p, top_k
                )

                def samp(lg, pos):
                    t = sample_tokens(
                        seeds, jnp.full((w * b,), pos, jnp.int32),
                        lg.reshape(w * b, v), temps, tps, tks,
                    )
                    return t.reshape(w, b)

                tok0 = samp(lg0, jnp.int32(0))

                def body(carry, i):
                    tok, kc, vc, ln = carry
                    lg, c2 = self._forward(
                        p, tok[..., None], MoEKVCache(kc, vc, ln), impl
                    )
                    # scan step i emits output index i and samples i+1
                    ntok = samp(lg[:, :, 0], i + 1)
                    return (ntok, c2.k, c2.v, c2.length), tok

                _, toks = lax.scan(
                    body, (tok0, kc, vc, ln),
                    jnp.arange(new_tokens, dtype=jnp.int32),
                )
                return jnp.moveaxis(toks, 0, -1)  # [W, B_loc, N]

            return jax.jit(uccl_moe_generate_sampled)

        fn = self._fn(key, build)
        return fn(params, logits, cache.k, cache.v, cache.length,
                  jnp.int32(int(sampling.seed)),
                  jnp.float32(sampling.temperature),
                  jnp.float32(sampling.top_p), jnp.int32(sampling.top_k))
