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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models import inference
from uccl_tpu.models.inference import (
    KVCache, SlotKVCache, _dense_ffn, _flat_extra, _forward_cached,
    _split_extra, kv_row_shapes,
)
from uccl_tpu.models.sampling import broadcast_params, sample_tokens
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
    # -- block kinds: DATA of the one description MoEServer consumes. The
    # defaults are the uniform block (Mixtral: gqa, every layer moe, softmax
    # gate, no shared expert, float32); GLM-4.7-Flash / DeepSeek-style
    # models are other values of the same fields (:meth:`from_hf`).
    attn: str = "gqa"  # "gqa" | "mla" (latent attention; the widths below)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    first_k_dense: int = 0  # leading layers with a dense FFN, not experts
    dense_ffn: int = 0  # their width
    shared_ffn: int = 0  # width of the always-on shared expert (0 = none)
    gate: str = "softmax"  # "softmax" | "sigmoid_bias" (ep.ops._gate_topk)
    routed_scale: float = 1.0  # multiplies the routed experts' weights
    param_dtype: str = "float32"  # storage dtype of the weight matrices
    # (norms and the gate bias stay float32); activations and cache are
    # float32 either way, so a bfloat16 matrix is upcast where it is used

    def __post_init__(self):
        if self.attn not in ("gqa", "mla"):
            raise ValueError(f"attn {self.attn!r}: want 'gqa' or 'mla'")
        if self.gate not in ep_ops.GATES:
            raise ValueError(f"gate {self.gate!r}: want one of "
                             f"{ep_ops.GATES}")
        if not 0 <= self.first_k_dense < self.n_layers:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} must leave at least "
                f"one of the {self.n_layers} layers to the experts")
        if self.first_k_dense and self.dense_ffn <= 0:
            raise ValueError("first_k_dense needs dense_ffn, its width")
        if self.attn == "mla":
            widths = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                      self.qk_rope_dim, self.v_head_dim)
            if min(widths) <= 0 or self.qk_rope_dim % 2 \
                    or self.kv_lora_rank % 2:
                raise ValueError(
                    f"mla needs its five widths (q_lora_rank, kv_lora_rank, "
                    f"qk_nope_dim, qk_rope_dim, v_head_dim), the rotary and "
                    f"the latent one even; got {widths}")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **overrides) -> "MoEServeConfig":
        """The description from a Hugging Face ``config.json`` dict —
        ``mixtral`` (uniform gqa/softmax blocks) or the
        ``deepseek_v3``/``glm4_moe_lite`` family (latent attention, leading
        dense layers, sigmoid-bias gate, shared experts). ``overrides`` are
        this class's own fields (capacity_factor, param_dtype ...)."""
        heads = hf["num_attention_heads"]
        kw: Dict[str, Any] = dict(
            vocab=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=hf["num_hidden_layers"], n_heads=heads,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            moe_topk=hf["num_experts_per_tok"],
        )
        if "kv_lora_rank" in hf:
            if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
                raise ValueError("group-limited routing (n_group > 1) is "
                                 "not built")
            if hf.get("rope_scaling") is not None:
                raise ValueError("rope_scaling is not built")
            shared = hf.get("n_shared_experts") or 0
            kw.update(
                attn="mla", q_lora_rank=hf["q_lora_rank"],
                kv_lora_rank=hf["kv_lora_rank"],
                qk_nope_dim=hf["qk_nope_head_dim"],
                qk_rope_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
                # gqa's widths: unused by mla, kept consistent for readers
                n_kv_heads=heads,
                head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
                moe_experts=hf["n_routed_experts"],
                moe_ffn=hf["moe_intermediate_size"],
                first_k_dense=hf.get("first_k_dense_replace", 0),
                dense_ffn=hf["intermediate_size"],
                shared_ffn=shared * hf["moe_intermediate_size"],
                gate="sigmoid_bias",
                routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
            )
            if not hf.get("norm_topk_prob", True):
                raise ValueError("norm_topk_prob false is not built")
        else:
            kw.update(
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                moe_experts=hf["num_local_experts"],
                moe_ffn=hf["intermediate_size"],
            )
        kw.update(overrides)
        return cls(**kw)


class MoEKVCache(NamedTuple):
    k: jax.Array  # [W, L, B_loc, S_max, *k_row] (inference.kv_row_shapes:
    v: jax.Array  # gqa [Hkv, D] each; mla [kv_lora_rank] and [qk_rope_dim])
    length: jax.Array  # [W] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32) -> "MoEKVCache":
        lead = (world, cfg.n_layers, batch_local, max_seq)
        k_row, v_row = kv_row_shapes(cfg)
        return MoEKVCache(
            jnp.zeros(lead + k_row, dtype), jnp.zeros(lead + v_row, dtype),
            jnp.zeros((world,), jnp.int32),
        )


class MoESlotCache(NamedTuple):
    """Slot-pool KV cache: one length PER SLOT (not per shard) — the
    continuous-batching engine admits/frees [w, b_loc] rows independently."""

    k: jax.Array  # [W, L, B_loc, S_max, *k_row] (inference.kv_row_shapes)
    v: jax.Array  # [W, L, B_loc, S_max, *v_row]
    lengths: jax.Array  # [W, B_loc] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32,
              sharding=None) -> "MoESlotCache":
        lead = (world, cfg.n_layers, batch_local, max_seq)
        k_row, v_row = kv_row_shapes(cfg)
        return MoESlotCache(
            jnp.zeros(lead + k_row, dtype, device=sharding),
            jnp.zeros(lead + v_row, dtype, device=sharding),
            jnp.zeros((world, batch_local), jnp.int32, device=sharding),
        )

    # -- slot KV export/import views (the disaggregation surface) ----------
    #
    # Mirrors inference.SlotKVCache: a flat slot id s maps to grid row
    # (w, b) = (s // B_loc, s % B_loc). Exports/imports go through host
    # numpy round-trips — np.asarray gathers a sharded pool, and the rebuilt
    # arrays go back under the placement the pool had (``_placed_like``) —
    # which keeps the surface correct on any mesh at the cost of a pool copy
    # per call (admission-rate work, not step-rate).

    def _placed_like(self, k, v, lengths) -> "MoESlotCache":
        """Host arrays as a pool placed the way this one is. jit keys its
        executables on its arguments' placement, so a pool handed back any
        other way (``jnp.asarray``: uncommitted, one device) would make
        every serving program trace, lower and load a second time."""
        return MoESlotCache(*(
            jax.device_put(new, old.sharding)
            for new, old in zip((k, v, lengths), self)))

    def _loc(self, slot: int):
        b_loc = self.k.shape[2]
        return slot // b_loc, slot % b_loc

    def export_rows(self, slot: int, lo: int, hi: int):
        """Host copies of rows [lo, hi): (k, v) each [L, hi-lo, Hkv, D] —
        the same per-slot layout the dense cache exports, so the disagg
        wire format is stack-independent. A latent pool's row (its two
        arrays differ in width) leaves as the two halves of its 576 numbers,
        ``[L, hi-lo, 1, 288]`` each (:func:`kv_wire_dims`): what moves rows
        — the prefix cache, the tiers, the disaggregated wire — wants two
        equal arrays and never looks inside them."""
        import numpy as np

        w, b = self._loc(slot)
        k = np.asarray(self.k[w, :, b, lo:hi])
        v = np.asarray(self.v[w, :, b, lo:hi])
        if k.shape == v.shape:
            return k, v
        flat = np.concatenate([k.reshape(k.shape[:2] + (-1,)),
                               v.reshape(v.shape[:2] + (-1,))], axis=-1)
        half = flat.shape[-1] // 2
        return (np.ascontiguousarray(flat[:, :, None, :half]),
                np.ascontiguousarray(flat[:, :, None, half:]))

    def import_rows(self, slot: int, k_rows, v_rows, *,
                    length: int) -> "MoESlotCache":
        import numpy as np

        w, b = self._loc(slot)
        n = k_rows.shape[1]
        # np.array (not asarray): device gathers come back read-only
        k = np.array(self.k)
        v = np.array(self.v)
        lengths = np.array(self.lengths)
        if k.shape[4:] != v.shape[4:]:  # the latent row's two wire halves
            flat = np.concatenate(
                [np.asarray(k_rows).reshape(k_rows.shape[:2] + (-1,)),
                 np.asarray(v_rows).reshape(v_rows.shape[:2] + (-1,))],
                axis=-1)
            cut = int(np.prod(k.shape[4:]))
            k_rows = flat[..., :cut].reshape(flat.shape[:2] + k.shape[4:])
            v_rows = flat[..., cut:].reshape(flat.shape[:2] + v.shape[4:])
        k[w, :, b, :n] = np.asarray(k_rows, k.dtype)
        v[w, :, b, :n] = np.asarray(v_rows, v.dtype)
        lengths[w, b] = length
        return self._placed_like(k, v, lengths)

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
        return self._placed_like(k, v, lengths)


# Which draw a leaf comes from. The uniform block's leaves keep the twelve-
# way split they always had (so a Mixtral-shaped model's weights are what
# they were); every other leaf folds its own number into the key, and the
# leading dense layers' group folds 64 more.
_SPLIT_KEY = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "router": 5,
              "we_gate": 6, "we_up": 7, "we_down": 8, "head": 9}
_FOLD_KEY = {"wq_a": 21, "wq_b": 22, "wkv_a": 23, "wkv_b": 24,
             "ws_gate": 25, "ws_up": 26, "ws_down": 27, "router_bias": 28,
             "w_gate": 29, "w_up": 30, "w_down": 31}
_DENSE_GROUP_FOLD = 64
ROUTER_BIAS_SCALE = 0.01  # seeded gate bias: choosing by score + bias and
# weighing by the score alone are then told apart


def _attn_shapes(cfg: MoEServeConfig):
    """{leaf: (shape, fan-in)} of one layer's attention matrices, and its
    norm leaves, for the description's attention kind."""
    h = cfg.dim
    if cfg.attn == "mla":
        nh = cfg.n_heads
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        return {
            "wq_a": ((h, cfg.q_lora_rank), h),
            "wq_b": ((cfg.q_lora_rank, nh * qk), cfg.q_lora_rank),
            "wkv_a": ((h, cfg.kv_lora_rank + cfg.qk_rope_dim), h),
            "wkv_b": ((cfg.kv_lora_rank,
                       nh * (cfg.qk_nope_dim + cfg.v_head_dim)),
                      cfg.kv_lora_rank),
            "wo": ((nh * cfg.v_head_dim, h), nh * cfg.v_head_dim),
        }, {"ln1": h, "ln2": h, "q_a_norm": cfg.q_lora_rank,
            "kv_a_norm": cfg.kv_lora_rank}
    qd = cfg.n_heads * cfg.head_dim
    kvd = cfg.n_kv_heads * cfg.head_dim
    return {"wq": ((h, qd), h), "wk": ((h, kvd), h), "wv": ((h, kvd), h),
            "wo": ((qd, h), qd)}, {"ln1": h, "ln2": h}


def init_params(key: jax.Array, cfg: MoEServeConfig) -> Dict[str, Any]:
    """Global parameter tree (experts carry the full [E, ...] axis). Layers
    come in stacked groups: ``blocks`` [n_moe_layers, ...] and, where the
    description has a dense prefix, ``dense_blocks`` [first_k_dense, ...].
    Every matrix is drawn in float32 (embedding 0.02, others 1/sqrt(fan-in))
    and stored in ``cfg.param_dtype``; norms are ones and the gate bias a
    normal of scale 0.01, both float32."""
    k = jax.random.split(key, 12)
    h, f, e = cfg.dim, cfg.moe_ffn, cfg.moe_experts
    dtype = jnp.dtype(cfg.param_dtype)

    def rnd(name, shape, scale, group=0):
        kk = k[_SPLIT_KEY[name]] if name in _SPLIT_KEY and not group else \
            jax.random.fold_in(key, group + (_FOLD_KEY.get(name)
                                             or _SPLIT_KEY[name]))
        return (jax.random.normal(kk, shape, jnp.float32)
                * scale).astype(dtype)

    def group(n, ffn_shapes, fold):
        mats, norms = _attn_shapes(cfg)
        mats = {**mats, **ffn_shapes}
        out = {name: jnp.ones((n, width), jnp.float32)
               for name, width in norms.items()}
        out.update({name: rnd(name, (n,) + shape, 1.0 / math.sqrt(fan), fold)
                    for name, (shape, fan) in mats.items()})
        return out

    moe = {"router": ((h, e), h), "we_gate": ((e, h, f), h),
           "we_up": ((e, h, f), h), "we_down": ((e, f, h), f)}
    if cfg.shared_ffn:
        fs = cfg.shared_ffn
        moe.update({"ws_gate": ((h, fs), h), "ws_up": ((h, fs), h),
                    "ws_down": ((fs, h), fs)})
    params = {
        "embed": rnd("embed", (cfg.vocab, h), 0.02),
        "blocks": group(cfg.n_moe_layers, moe, 0),
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd("head", (h, cfg.vocab), 1.0 / math.sqrt(h)),
    }
    if cfg.gate == "sigmoid_bias":
        params["blocks"]["router_bias"] = jax.random.normal(
            jax.random.fold_in(key, _FOLD_KEY["router_bias"]),
            (cfg.n_moe_layers, e), jnp.float32) * ROUTER_BIAS_SCALE
    if cfg.first_k_dense:
        fd = cfg.dense_ffn
        params["dense_blocks"] = group(
            cfg.first_k_dense,
            {"w_gate": ((h, fd), h), "w_up": ((h, fd), h),
             "w_down": ((fd, h), fd)}, _DENSE_GROUP_FOLD)
    return params


# The router's product. The softmax gate's is at XLA's default, as it always
# was. A sigmoid-bias model computes its router in float32 by definition, and
# has to: top-4 of 64 sigmoid scores sit closer together than a product of
# bfloat16-rounded operands resolves (13 % of served tokens moved on the
# chip when it did not: PERF.md section 6, PR 26); 2048 x 64 a token is free.
_ROUTER_PRECISION = {"softmax": None, "sigmoid_bias": lax.Precision.HIGHEST}


def _moe_block(cfg: MoEServeConfig, impl: str):
    """The FFN half of a layer as an :func:`inference._forward_cached`-style
    ``ffn`` hook, by what the layer's leaves say it is: a dense-prefix layer
    (no router) runs the dense SwiGLU; an expert layer routes over the EP
    axis (sorted path for prefill throughput, packed LL for decode), experts
    being the LOCAL shard, and adds the shared expert — once per token,
    outside ``moe_ffn``, whatever the EP world."""

    def moe_block(h2, lp):
        if "router" not in lp:
            with jax.named_scope("ffn.dense"):
                return _dense_ffn(h2, lp)
        b, sq, hd = h2.shape
        flat = h2.reshape(b * sq, hd)
        with jax.named_scope("moe.router"):
            router_logits = jnp.dot(
                flat.astype(jnp.float32), lp["router"].astype(jnp.float32),
                precision=_ROUTER_PRECISION[cfg.gate])
        out, _, _ = ep_ops.moe_ffn(
            flat, router_logits,
            lp["we_gate"].astype(flat.dtype), lp["we_up"].astype(flat.dtype),
            lp["we_down"].astype(flat.dtype),
            _AXIS,
            num_selected=cfg.moe_topk,
            capacity_factor=cfg.capacity_factor,
            impl=impl,
            wire=cfg.moe_wire,
            n_chunks=cfg.moe_chunks,
            wire_dtype=cfg.wire_dtype,
            gate=cfg.gate,
            gate_bias=lp.get("router_bias"),
            routed_scale=cfg.routed_scale,
        )
        if "ws_gate" in lp:
            with jax.named_scope("moe.shared"):
                out = out + _dense_ffn(
                    flat, {"w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
                           "w_down": lp["ws_down"]})
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


_EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


def _is_expert_leaf(path) -> bool:
    """A tree path of an EP-sharded leaf: ``blocks/we_*``."""
    return path[-1].key in _EXPERT_LEAVES


def _strip_shard(p):
    """Drop the per-shard leading dim shard_map hands each member:
    replicated leaves carry it LEADING ([1, ...] broadcast slice); expert
    leaves carry it at axis 1 ([L, 1, E_local, ...] — the sharded W axis
    of shard_params)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf[:, 0] if _is_expert_leaf(path) else leaf[0],
        p)


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
        # otherwise retain a compiled executable per shape forever. 16
        # holds a chunked engine's steady set (serving/backend.py's
        # docstring sizes it)
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

        def place(path, leaf):
            if _is_expert_leaf(path):
                l = leaf.shape[0]
                return leaf.reshape((l, w, e_local) + leaf.shape[2:])
            return jnp.broadcast_to(leaf, (w,) + leaf.shape)

        return jax.tree_util.tree_map_with_path(place, params)

    def _fn(self, key, build):
        return self._fns.get(key, build)

    @staticmethod
    def _param_specs(params):
        """Partition specs of a placed tree, by its own structure:
        replicated leaves shard their broadcast leading [W] dim; expert
        leaves shard the [W] at axis 1 ([L, W, E_local, ...])."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: P(None, _AXIS) if _is_expert_leaf(path)
            else P(_AXIS), params)

    def _shard_mapped(self, f, n_in, n_out, params, donate=()):
        """jit(shard_map(f)) with params first, then n_in P(dp) arrays.
        The compiled program is named after ``f`` (``jit_<f.__name__>`` on
        the profiler's ``XLA Modules`` line, and part of the persistent
        compile cache's key), so each closure carries a name of its own.
        ``donate``: positions of the arguments the program consumes (the
        slot programs' pool: written in place, handed back as the same
        buffers)."""
        return jax.jit(
            shard_map(
                f, mesh=self.mesh,
                in_specs=(self._param_specs(params),) + (P(_AXIS),) * n_in,
                out_specs=(P(_AXIS),) * n_out,
                check_vma=False,
            ),
            donate_argnums=donate,
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
            key, lambda: self._shard_mapped(uccl_moe_forward, 4, 4, params))
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
        """The engine's fixed [W, B_loc, S_max] KV pool (per-slot lengths),
        born as the slot programs return it: committed, under the sharding
        their ``out_specs`` give (over one shard JAX hands ``P(dp)`` back
        as ``P()``). jit keys its traces on that, so a pool of plain
        ``jnp.zeros`` (uncommitted) made a program's second call — on the
        pool the first gave back — trace, lower and load it all over
        again: every serving program's start-up was paid twice."""
        self._check_drop_free()
        cache = MoESlotCache.empty(
            self.cfg, self.world, batch_local, max_seq,
            sharding=NamedSharding(
                self.mesh, P(_AXIS) if self.world > 1 else P()))
        from uccl_tpu.obs import counters as _obsc

        _obsc.gauge(
            "serving_kv_row_bytes",
            "bytes one cached position of one layer holds in the slot pool",
        ).set(sum(math.prod(a.shape[4:]) * a.dtype.itemsize
                  for a in (cache.k, cache.v)), kind=self.cfg.attn)
        return cache

    def prefill_slots(self, params, tokens, prompt_lens, new_mask,
                      cache: MoESlotCache, start=None, sampling=None,
                      adapters=None, adapter_ids=None, slots=None):
        """Masked batched prefill of newly admitted slots (sorted EP path):
        :func:`inference.prefill_slots` — the one statement of the program,
        read it there — run per shard with the EP block as its FFN.

        Every per-slot argument carries the shard dimension in front:
        tokens [W, B_loc, S]; prompt_lens / new_mask / start and each of
        ``sampling``'s five arrays and ``adapter_ids`` [W, B_loc]; the
        ``adapters`` tables broadcast [W, ...]; ``slots`` (a COMPACT call)
        [W, R] local slot indices, every other per-slot argument and the
        returned token then [W, R] — ``expert_capacity`` sees R * S tokens
        and the queues shrink with R; the wire stays drop-free, which is
        also what keeps chunked prefill bit-exact here (expert rows stay
        independent). Returns (token [W, B_loc | R], cache'). ``cache`` is
        CONSUMED: its arrays are donated to the program, written in place
        and come back as ``cache'`` — keep the pool returned, never the one
        passed."""
        self._check_drop_free()
        cfg = self.cfg
        if start is None:
            start = jnp.zeros_like(prompt_lens)
        sampled, adapted = sampling is not None, adapters is not None
        compact = slots is not None
        extra = _flat_extra(sampling, adapters, adapter_ids)
        if compact:
            extra = [slots] + extra

        def uccl_moe_prefill_slots(p, tok, lens, mask, off, kc, vc, ln,
                                   *rest):
            rest = [r[0] for r in rest]
            idx = None
            if compact:
                idx, rest = rest[0], rest[1:]
            samp, adp, ids = _split_extra(rest, sampled, adapted)
            t, out = inference.prefill_slots(
                _strip_shard(p), tok[0], lens[0], mask[0],
                SlotKVCache(kc[0], vc[0], ln[0]), cfg, start=off[0],
                sampling=samp, adapters=adp, adapter_ids=ids, slots=idx,
                ffn=_moe_block(cfg, "sort"))
            return t[None], out.k[None], out.v[None], out.lengths[None]

        key = ("prefill_slots", tokens.shape, cache.k.shape,
               sampled, adapted, compact)
        fn = self._fn(key, lambda: self._shard_mapped(
            uccl_moe_prefill_slots, 7 + len(extra), 4, params,
            donate=(5, 6, 7)))
        tok, nk, nv, nlen = fn(params, tokens, prompt_lens, new_mask,
                               start, cache.k, cache.v, cache.lengths,
                               *extra)
        return tok, MoESlotCache(nk, nv, nlen)

    def verify_slots(self, params, tokens, active, cache: MoESlotCache,
                     impl: str = "sort", sampling=None, adapters=None,
                     adapter_ids=None):
        """Batched draft verification over the slot pool:
        :func:`inference.verify_slots` (the one statement of the program —
        acceptance rule, cursor advance, the sampled case) run per shard
        with the EP block as its FFN, the sorted path by default — the
        multi-token regime, like prefill; the drop-free capacity check keeps
        every routing exact whatever the window's width. tokens
        [W, B_loc, S]; active, ``sampling``'s arrays and ``adapter_ids``
        [W, B_loc]. Returns (target tokens [W, B_loc, S], n_accepted
        [W, B_loc], cache'); ``cache`` is consumed, as
        :meth:`prefill_slots` consumes it."""
        self._check_drop_free()
        cfg = self.cfg
        sampled, adapted = sampling is not None, adapters is not None
        extra = _flat_extra(sampling, adapters, adapter_ids)

        def uccl_moe_verify_slots(p, tok, mask, kc, vc, ln, *rest):
            samp, adp, ids = _split_extra([r[0] for r in rest], sampled,
                                          adapted)
            t, n_acc, out = inference.verify_slots(
                _strip_shard(p), tok[0], mask[0],
                SlotKVCache(kc[0], vc[0], ln[0]), cfg, sampling=samp,
                adapters=adp, adapter_ids=ids, ffn=_moe_block(cfg, impl))
            return (t[None], n_acc[None], out.k[None], out.v[None],
                    out.lengths[None])

        key = ("verify_slots", impl, tokens.shape, cache.k.shape,
               sampled, adapted)
        fn = self._fn(key, lambda: self._shard_mapped(
            uccl_moe_verify_slots, 5 + len(extra), 5, params,
            donate=(3, 4, 5)))
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
