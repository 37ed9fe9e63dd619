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
from collections import Counter
from dataclasses import dataclass, replace
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
    # -- layer kinds: gqa layers that differ by layer (MiMo-V2-Flash: window
    # and full attention side by side), and layers that do not attend
    # (LFM2: gated short convolutions between full attention layers;
    # Brumby: every layer a power retention, whose state has no position
    # axis). Empty = every layer the one block.
    layer_kinds: Tuple[str, ...] = ()  # per layer: "full" | "window" |
    # "conv" | "retention" (retention layers stand alone)
    window: int = 0  # a window layer's query at p sees [p - window + 1, p]
    window_kv_heads: int = 0  # the window kind's KV heads (n_kv_heads: full)
    window_rope_theta: float = 0.0  # the window kind's theta (rope_theta: full)
    window_ring: int = 0  # rows a slot keeps of a window layer (0: 2 x window)
    rotary_dim: int = 0  # leading numbers of a head that rotate (0: all)
    value_scale: float = 1.0  # multiplies the values before attention
    sink: Tuple[str, ...] = ()  # kinds whose softmax has a learned sink column
    # (with layer kinds, gqa's values are v_head_dim wide: 0 = head_dim)
    unrotated: Tuple[str, ...] = ()  # kinds whose queries and keys carry no
    # rotary embedding (Trinity's full layers); every other kind rotates
    qk_norm: bool = False  # each query and key head RMS-normed (leaves
    # q_norm / k_norm [head_dim]) before the rotation
    attn_gate: bool = False  # attention output times sigmoid(h wg), head by
    # head, before the output projection (leaf wg, as wide as wo is tall)
    post_norms: bool = False  # sandwich norms: each branch's OUTPUT is
    # normed before it joins the residual (leaves ln1_post / ln2_post)
    embed_scale: float = 1.0  # multiplies the embedding's output
    norm_gain_scale: float = 0.0  # init_params draws a layer's norm gains as
    # 1 + this x a seeded normal (0: ones), so that a gain left out shows
    conv_taps: int = 0  # a conv layer's filter: position p reads (p - taps, p]
    conv_ring: int = 0  # rows a slot keeps of a conv layer (0: 2 x conv_taps)
    tie_head: bool = False  # the head is the embedding: no ``head`` leaf
    # -- this member's share of a wider deployment: the router keeps its
    # moe_experts outputs, experts [first_expert, first_expert + held) live
    # here and only their part of the layer's sum is computed (ep.ops.moe_ffn)
    experts_held: int = 0  # 0 = all moe_experts
    first_expert: int = 0

    def __post_init__(self):
        if self.attn not in ("gqa", "mla"):
            raise ValueError(f"attn {self.attn!r}: want 'gqa' or 'mla'")
        kinds = self.layer_kinds
        if kinds:
            if self.attn != "gqa" or len(kinds) != self.n_layers \
                    or set(kinds) - {"full", "window", "conv", "retention"}:
                raise ValueError(
                    f"layer_kinds names 'full', 'window', 'conv' or "
                    f"'retention' for each "
                    f"of the {self.n_layers} gqa layers; got {kinds} "
                    f"({self.attn})")
            if "retention" in kinds and (
                    len(set(kinds)) > 1 or self.sink or self.unrotated
                    or self.attn_gate or self.post_norms):
                raise ValueError(
                    f"retention layers beside another layer kind (a state "
                    f"kept beside rows by position in one slot), or with a "
                    f"sink, unrotated kinds, an attention gate or sandwich "
                    f"norms, are not built; got {kinds}")
            if "window" in kinds and (self.window < 1
                                      or self.window_kv_heads < 1):
                raise ValueError("window layers need window and "
                                 "window_kv_heads")
            if ("conv" in kinds) != (self.conv_taps > 0) \
                    or self.conv_taps == 1:
                raise ValueError(
                    f"conv layers need conv_taps >= 2, and conv_taps belongs "
                    f"to them; got {self.conv_taps} with {kinds}")
            if set(self.sink) - {"full", "window"} or self.rotary_dim % 2:
                raise ValueError("sink names layer kinds; rotary_dim is even")
            if set(self.unrotated) - {"full", "window"}:
                raise ValueError("unrotated names layer kinds")
            for group, holds in (("window", "a window's rows"),
                                 ("conv", "a filter's taps")):
                if group in kinds and self.ring_rows(group) \
                        < self.reach(group):
                    raise ValueError(
                        f"{group}_ring {self.ring_rows(group)} must hold "
                        f"{holds} ({self.reach(group)}): what is read at "
                        f"position p are the ring's last {self.reach(group)} "
                        f"positions, p among them ({holds.split()[-1]} - 1 + "
                        f"the widest write is asked where that width is "
                        f"known: the slot forward and ServingEngine)")
        elif self.window or self.sink or self.rotary_dim \
                or self.value_scale != 1.0 or self.unrotated \
                or self.qk_norm or self.attn_gate or self.post_norms \
                or self.conv_taps or self.conv_ring \
                or (self.attn == "gqa" and self.v_head_dim):
            raise ValueError("window, sink, rotary_dim, value_scale, "
                             "unrotated, qk_norm, attn_gate, post_norms, "
                             "conv_taps, conv_ring and a gqa v_head_dim "
                             "belong to layer_kinds")
        if not 0 <= self.first_expert <= self.moe_experts - self.n_held:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.n_held}) are not among the {self.moe_experts} routed")
        if self.gate not in ep_ops.GATES:
            raise ValueError(f"gate {self.gate!r}: want one of "
                             f"{ep_ops.GATES}")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} is not among the "
                f"{self.n_layers} layers (all of them: a model with no "
                f"expert layer)")
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

    @property
    def n_held(self) -> int:
        """Experts resident here: the queues and the expert leaves."""
        return self.experts_held or self.moe_experts

    @property
    def ring(self) -> int:
        """Rows a slot keeps of each window layer (``MoESlotCache``): at
        least ``window - 1 + the widest write`` (a prefill chunk, a verify
        window), checked where the write's width is known
        (``inference._forward_slots``, ``ServingEngine``)."""
        return self.window_ring or 2 * self.window

    def ring_rows(self, group: str) -> int:
        """Rows a slot keeps of each layer of a ring group
        (``inference.RING_GROUPS``): :attr:`ring` for "window"; for "conv"
        ``conv_ring``, or twice the taps."""
        return self.ring if group == "window" \
            else self.conv_ring or 2 * self.conv_taps

    def reach(self, group: str) -> int:
        """Positions a layer of a ring group reads back from its ring, the
        newest among them: a window layer's query at p sees (p - window, p],
        a conv layer's filter (p - conv_taps, p]. A ring holds ``reach - 1 +
        the widest write`` rows or more."""
        return self.window if group == "window" else self.conv_taps

    @property
    def drop_free_factor(self) -> float:
        """The least ``capacity_factor`` whose EP wire drops no row whatever
        the routing: ROUTED experts / top-k, whatever share is held here
        (``MoEServer._check_drop_free``); 0 with no expert layer."""
        return self.moe_experts / self.moe_topk if self.moe_topk else 0.0

    def sized_for_serving(self, widest: int) -> "MoEServeConfig":
        """This description sized for a serving process whose widest write
        (a prefill chunk, a verify window) is ``widest`` positions: each
        ring at least ``inference.ring_rows_for`` its reach and that write,
        ``capacity_factor`` at least :attr:`drop_free_factor`."""
        rings = {g + "_ring": max(
            self.ring_rows(g), inference.ring_rows_for(self.reach(g), widest))
            for g in inference.RING_GROUPS if g in self.layer_kinds}
        return replace(self, **rings, capacity_factor=max(
            self.capacity_factor, self.drop_free_factor))

    def kv_heads(self, kind: str) -> int:
        return self.window_kv_heads if kind == "window" else self.n_kv_heads

    def theta(self, kind: str) -> float:
        return self.window_rope_theta if kind == "window" \
            else self.rope_theta

    def param_groups(self):
        """``[(group, index in group)]`` by layer: layers are stacked by
        (FFN kind, attention kind). The groups the uniform descriptions
        have keep their names (``dense_blocks``, ``blocks``: a "full" layer
        is the block they always were); a window layer's group is
        ``window_blocks`` / ``dense_window_blocks``, a conv layer's
        ``conv_blocks`` / ``dense_conv_blocks``, a retention layer's
        ``retention_blocks`` / ``dense_retention_blocks``."""
        kinds = self.layer_kinds or ("full",) * self.n_layers
        return inference.indexed_groups(
            ("dense_" if i < self.first_k_dense else "")
            + ("" if kinds[i] == "full" else kinds[i] + "_") + "blocks"
            for i in range(self.n_layers))

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], **overrides) -> "MoEServeConfig":
        """The description from a Hugging Face ``config.json`` dict —
        ``mixtral`` (uniform gqa/softmax blocks), the
        ``deepseek_v3``/``glm4_moe_lite`` family (latent attention, leading
        dense layers, sigmoid-bias gate, shared experts) or ``mimo_v2_flash``
        (keyed on ``hybrid_layer_pattern``: window and full gqa layers with
        their own KV heads and thetas, a partial rotary factor, scaled
        values, a sink in the window softmax, sigmoid-bias experts; the
        first ``num_hidden_layers`` entries of its two per-layer lists are
        read) or ``afmoe`` (Trinity; keyed on ``layer_types``:
        ``sliding_attention`` | ``full_attention`` layers with one KV head
        count and one theta, rotary on the window layers only, QK-norm, a
        gated output, sandwich norms, a scaled embedding,
        ``num_dense_layers`` leading dense layers, then sigmoid-bias experts
        beside ``num_shared_experts`` shared ones; ``layer_types`` is read
        up to ``num_hidden_layers``) or ``lfm2_moe`` (LFM2-24B-A2B; keyed on
        ``conv_L_cache``: ``conv`` | ``full_attention`` layers by
        ``layer_types``, a gated short convolution of ``conv_L_cache`` taps
        whose state is a third cache group, QK-norm on a uniform gqa,
        ``num_dense_layers`` leading dense layers, then sigmoid-bias experts
        with no shared one, a head tied to the embedding) or ``brumby``
        (Brumby-14B-Base; keyed on ``model_type``: every layer a power
        retention — QK-normed, rotary grouped queries and keys, one gate a
        KV head with a bias, a state with no position axis — and a dense
        SwiGLU, no expert layer at all: ``first_k_dense == n_layers``; an
        untied head). A file that
        states a member's share
        gives ``n_routed_experts`` (``afmoe``: ``num_experts``) as the
        experts held and ``router_experts`` as the router's width
        (``first_expert``: the first held). ``overrides`` are this class's
        own fields (capacity_factor, param_dtype, window_ring ...)."""
        heads = hf["num_attention_heads"]
        n_layers = hf["num_hidden_layers"]
        retention = hf.get("model_type") == "brumby"
        kw: Dict[str, Any] = dict(
            vocab=hf["vocab_size"], dim=hf["hidden_size"],
            n_layers=n_layers, n_heads=heads,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            norm_eps=float(hf.get("rms_norm_eps")
                           or hf.get("layernorm_epsilon") or 1e-6),
            moe_topk=0 if retention else hf["num_experts_per_tok"],
        )
        if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing (n_group > 1) is not "
                             "built")
        if retention:
            for key, what in (
                    ("use_sliding_window", "retention layers over a window"),
                    ("rope_scaling", "a scaled rotation"),
                    ("attention_bias", "biases on the q, k, v projections"),
                    ("tie_word_embeddings", "a head tied to the embedding "
                     "beside retention layers")):
                if hf.get(key):
                    raise ValueError(f"{key} {hf[key]!r} ({what}) is not "
                                     f"built")
            kw.update(
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                layer_kinds=("retention",) * n_layers,
                qk_norm=True, norm_gain_scale=NORM_GAIN_SCALE,
                # no expert layer: every layer's FFN is the dense SwiGLU
                first_k_dense=n_layers, dense_ffn=hf["intermediate_size"],
                moe_experts=0, moe_ffn=0,
            )
        elif "conv_L_cache" in hf:
            kinds = _layer_kinds(hf, n_layers, {"conv": "conv",
                                                "full_attention": "full"})
            if hf.get("conv_bias"):
                raise ValueError("conv_bias true (a bias on the conv "
                                 "layers' projections) is not built")
            if not hf.get("use_expert_bias", True):
                raise ValueError("use_expert_bias false (experts chosen by "
                                 "the score alone) is not built")
            if not hf.get("norm_topk_prob", True):
                raise ValueError("norm_topk_prob false (weights not "
                                 "renormalised over the chosen) is not built")
            rope_params = hf.get("rope_parameters") or {}
            if rope_params.get("rope_type", "default") != "default":
                raise ValueError(f"rope_type {rope_params['rope_type']!r}: "
                                 f"the rotation built is 'default'")
            routed = hf.get("router_experts", hf["num_experts"])
            held = hf["num_experts"]
            kw.update(
                rope_theta=float(rope_params.get("rope_theta",
                                                 kw["rope_theta"])),
                norm_eps=float(hf.get("norm_eps") or kw["norm_eps"]),
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                layer_kinds=kinds,
                conv_taps=hf["conv_L_cache"],
                qk_norm=True, norm_gain_scale=NORM_GAIN_SCALE,
                tie_head=bool(hf.get("tie_word_embeddings",
                                     hf.get("tie_embedding", True))),
                moe_experts=routed,
                experts_held=held if held != routed else 0,
                first_expert=hf.get("first_expert", 0),
                moe_ffn=hf["moe_intermediate_size"],
                first_k_dense=hf.get("num_dense_layers", 0),
                dense_ffn=hf["intermediate_size"],
                gate="sigmoid_bias",
                routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
            )
        elif "layer_types" in hf:
            kinds = _layer_kinds(hf, n_layers,
                                 {"sliding_attention": "window",
                                  "full_attention": "full"})
            if hf.get("score_func", "sigmoid") != "sigmoid":
                raise ValueError(f"score_func {hf['score_func']!r}: the "
                                 f"gate built here is 'sigmoid'")
            if not hf.get("route_norm", True):
                raise ValueError("route_norm false (weights not renormalised "
                                 "over the chosen) is not built")
            if hf.get("rope_scaling") is not None:
                raise ValueError("rope_scaling is not built")
            routed = hf.get("router_experts", hf["num_experts"])
            held = hf["num_experts"]
            kw.update(
                n_kv_heads=hf["num_key_value_heads"],
                window_kv_heads=hf["num_key_value_heads"],
                window_rope_theta=kw["rope_theta"],
                head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
                layer_kinds=kinds,
                window=hf["sliding_window"],
                unrotated=("full",), qk_norm=True, attn_gate=True,
                post_norms=True,
                embed_scale=math.sqrt(hf["hidden_size"])
                if hf.get("mup_enabled") else 1.0,
                norm_gain_scale=NORM_GAIN_SCALE,
                moe_experts=routed,
                experts_held=held if held != routed else 0,
                first_expert=hf.get("first_expert", 0),
                moe_ffn=hf["moe_intermediate_size"],
                first_k_dense=hf.get("num_dense_layers", 0),
                dense_ffn=hf["intermediate_size"],
                shared_ffn=(hf.get("num_shared_experts") or 0)
                * hf["moe_intermediate_size"],
                gate="sigmoid_bias",
                routed_scale=float(hf.get("route_scale") or 1.0),
            )
        elif "hybrid_layer_pattern" in hf:
            pattern = list(hf["hybrid_layer_pattern"])[:n_layers]
            freq = list(hf["moe_layer_freq"])[:n_layers]
            if len(pattern) < n_layers or len(freq) < n_layers:
                raise ValueError(
                    f"hybrid_layer_pattern / moe_layer_freq name fewer than "
                    f"num_hidden_layers ({n_layers}) layers")
            dense = next((i for i, m in enumerate(freq) if m), n_layers)
            if not all(freq[dense:]):
                raise ValueError("a dense FFN after the first expert layer "
                                 "(moe_layer_freq) is not built")
            if hf.get("scoring_func", "sigmoid") != "sigmoid" \
                    or not hf.get("norm_topk_prob", True):
                raise ValueError("the gate built is sigmoid scores, "
                                 "renormalised over the chosen")
            if hf.get("n_shared_experts") or hf.get("attention_bias"):
                raise ValueError("shared experts / attention biases beside "
                                 "layer kinds are not built")
            same = (("swa_num_attention_heads", heads),
                    ("swa_head_dim", hf["head_dim"]),
                    ("swa_v_head_dim", hf["v_head_dim"]),
                    ("sliding_window_size", hf["sliding_window"]))
            for key, want in same:
                if hf.get(key, want) != want:
                    raise ValueError(f"{key} {hf[key]} != {want}: window "
                                     f"layers with their own query heads or "
                                     f"head sizes are not built")
            routed = hf.get("router_experts", hf["n_routed_experts"])
            held = hf["n_routed_experts"]
            kw.update(
                n_kv_heads=hf["num_key_value_heads"],
                head_dim=hf["head_dim"], v_head_dim=hf["v_head_dim"],
                layer_kinds=tuple("window" if p else "full"
                                  for p in pattern),
                window=hf["sliding_window"],
                window_kv_heads=hf["swa_num_key_value_heads"],
                window_rope_theta=float(hf["swa_rope_theta"]),
                rotary_dim=int(hf.get("partial_rotary_factor", 1.0)
                               * hf["head_dim"]) // 2 * 2,
                value_scale=float(hf.get("attention_value_scale") or 1.0),
                sink=tuple(kind for kind, key in (
                    ("full", "add_full_attention_sink_bias"),
                    ("window", "add_swa_attention_sink_bias"))
                    if hf.get(key)),
                moe_experts=routed,
                experts_held=held if held != routed else 0,
                first_expert=hf.get("first_expert", 0),
                moe_ffn=hf["moe_intermediate_size"],
                first_k_dense=dense, dense_ffn=hf["intermediate_size"],
                gate="sigmoid_bias",
                routed_scale=float(hf.get("routed_scaling_factor") or 1.0),
            )
        elif "kv_lora_rank" in hf:
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


def _layer_kinds(hf: Dict[str, Any], n_layers: int,
                 kind_of: Dict[str, str]) -> Tuple[str, ...]:
    """The first ``n_layers`` entries of a file's ``layer_types`` as this
    module's layer kinds; an entry ``kind_of`` does not name, or too few
    entries, raise by name."""
    types = list(hf["layer_types"])[:n_layers]
    unknown = sorted(set(types) - set(kind_of))
    if unknown or len(types) < n_layers:
        raise ValueError(
            f"layer_types names {' or '.join(map(repr, kind_of))} for each "
            f"of num_hidden_layers ({n_layers}) layers; got {len(types)} "
            f"entries, unknown {unknown}")
    return tuple(kind_of[t] for t in types)


def _cache_arrays(cfg: MoEServeConfig, world: int, batch_local: int,
                  rows: Dict[Optional[str], int], dtype, sharding=None):
    """The (k, v) of a cache: one ``[W, L, B_loc, rows, *row]`` array each,
    or — where the description has layer kinds — ``{group: array}`` with a
    group's own layer count, ``rows[group]`` and row shape
    (``inference.cache_groups`` / ``kv_row_shapes``; a "conv" group is in
    ``k`` alone; a state group, ``inference.STATE_GROUPS``, is a tuple of
    one array a layer with no rows axis, ``[W, B_loc, *state]``)."""
    layers = Counter(group for group, _ in inference.cache_groups(cfg))

    def array(group, n, which):
        row = kv_row_shapes(cfg, group)[which]
        if group in inference.STATE_GROUPS:  # one array a layer, no rows
            return tuple(jnp.zeros((world, batch_local) + row, dtype,
                                   device=sharding) for _ in range(n))
        return jnp.zeros((world, n, batch_local, rows[group]) + row, dtype,
                         device=sharding)

    out = []
    for which in (0, 1):
        arrays = {
            group: array(group, n, which) for group, n in layers.items()
            # a conv group keeps one array: no value rows
            if kv_row_shapes(cfg, group)[which] is not None}
        out.append(arrays[None] if None in arrays else arrays)
    return out


class MoEKVCache(NamedTuple):
    k: jax.Array  # [W, L, B_loc, S_max, *k_row] (inference.kv_row_shapes:
    v: jax.Array  # gqa [Hkv, D] each; mla [kv_lora_rank] and [qk_rope_dim]);
    # with layer kinds {group: [W, L_group, B_loc, S_max, *row of the kind]},
    # a state group a tuple of L_group arrays [W, B_loc, *state]
    length: jax.Array  # [W] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32) -> "MoEKVCache":
        k, v = _cache_arrays(cfg, world, batch_local,
                             {None: max_seq, "full": max_seq,
                              "window": max_seq, "conv": max_seq}, dtype)
        return MoEKVCache(k, v, jnp.zeros((world,), jnp.int32))


class MoESlotCache(NamedTuple):
    """Slot-pool KV cache: one length PER SLOT (not per shard) — the
    continuous-batching engine admits/frees [w, b_loc] rows independently.

    Where the description has layer kinds the pool is cache GROUPS by
    layer kind, ``k`` and ``v`` each ``{"full": [W, L_full, B_loc, S_max,
    Hkv * .], "window": [W, L_win, B_loc, ring, Hkv_win * .]}`` and ``k``
    alone ``"conv": [W, L_conv, B_loc, ring, dim]``: a full layer keeps
    every position of a slot, a window or conv layer a ring of
    ``cfg.ring_rows(group)`` rows (position p at row p % ring); a retention
    layer keeps no position, only its state AT the slot's length: ``k``
    ``"retention"`` a tuple of ``L_ret`` arrays ``[W, B_loc, Hkv, F, Dv]``
    (S) and ``v`` of ``[W, B_loc, Hkv, F]`` (z), one a layer
    (``inference.STATE_GROUPS``). Rows of a
    pool with ring or state groups cannot be exported, imported or copied
    between slots: the three views below raise
    (``inference.rows_stay``, the sentence of ``pool_traits``)."""

    k: jax.Array  # [W, L, B_loc, S_max, *k_row] (inference.kv_row_shapes)
    v: jax.Array  # [W, L, B_loc, S_max, *v_row]
    lengths: jax.Array  # [W, B_loc] int32

    @staticmethod
    def empty(cfg: MoEServeConfig, world: int, batch_local: int,
              max_seq: int, dtype=jnp.float32,
              sharding=None) -> "MoESlotCache":
        k, v = _cache_arrays(cfg, world, batch_local,
                             {None: max_seq, "full": max_seq,
                              **{g: cfg.ring_rows(g)
                                 for g in inference.RING_GROUPS}},
                             dtype, sharding)
        return MoESlotCache(
            k, v, jnp.zeros((world, batch_local), jnp.int32, device=sharding))

    def _one_group(self, what: str) -> None:
        if isinstance(self.k, dict):
            raise ValueError(inference.rows_stay(self.k) + what)

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
        b_loc = self.lengths.shape[1]
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

        self._one_group("export_rows has no one array a layer to hand out")
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

        self._one_group("import_rows would need a prefix's last reach - 1 "
                        "positions of every ring layer, which no exporter "
                        "keeps")
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

        self._one_group("copy_prefix finds the donor's ring holding its "
                        "newest positions, not the prefix's last reach - 1")
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
             "w_gate": 29, "w_up": 30, "w_down": 31, "sink": 32, "wg": 33,
             "q_norm": 34, "k_norm": 35, "ln1_post": 36, "ln2_post": 37,
             "ln1": 38, "ln2": 39, "w_in": 40, "w_conv": 41, "w_out": 42,
             "bg": 43}
_DENSE_GROUP_FOLD = 64
# a group's fold: the groups that always were keep theirs (so the uniform
# descriptions' weights are what they were); a window group folds 128 more,
# a conv group 256, a retention group 512
_GROUP_FOLD = {"blocks": 0, "dense_blocks": _DENSE_GROUP_FOLD,
               "window_blocks": 128,
               "dense_window_blocks": 128 + _DENSE_GROUP_FOLD,
               "conv_blocks": 256,
               "dense_conv_blocks": 256 + _DENSE_GROUP_FOLD,
               "retention_blocks": 512,
               "dense_retention_blocks": 512 + _DENSE_GROUP_FOLD}
SINK_SCALE = 1.0  # seeded sink logits: as large as the scores they sit
# beside, so that a softmax without its sink column is told apart
ROUTER_BIAS_SCALE = 0.01  # seeded gate bias: choosing by score + bias and
# weighing by the score alone are then told apart
GATE_BIAS_RANGE = (4.0, 7.0)  # a retention layer's gate bias ``bg``, drawn
# uniform: g = sigmoid(. + bg) between 0.982 and 0.999, so a state remembers
# hundreds of positions and a wrong chunk boundary far back still shows
NORM_GAIN_SCALE = 0.1  # seeded norm gains (``from_hf``'s afmoe branch asks
# for them): 1 + 0.1 x a normal, so that a norm whose gain is left out, or a
# branch normed once where the model norms it twice, is told apart


def _attn_shapes(cfg: MoEServeConfig, kind: str = "full"):
    """{leaf: (shape, fan-in)} of one layer's attention matrices, and its
    norm leaves, for the description's attention kind (``kind``: the
    layer's, where the description has layer kinds). A "conv" layer's are
    its short convolution's: ``w_in`` (the three gates' projection),
    ``w_out`` and the filter ``w_conv`` ``[dim, taps]``, drawn at
    1/sqrt(taps) so that a tap left out or the taps reversed shows. A
    "retention" layer's are a grouped attention's with QK-norm and ``wg
    [dim, Hkv]``, one gate a KV head (its bias ``bg`` is ``init_params``'
    own draw)."""
    h = cfg.dim
    if kind == "retention":
        qd, kd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"wq": ((h, qd), h), "wk": ((h, kd), h), "wv": ((h, kd), h),
                "wo": ((qd, h), qd), "wg": ((h, cfg.n_kv_heads), h)}, \
            {"ln1": h, "ln2": h, "q_norm": cfg.head_dim,
             "k_norm": cfg.head_dim}
    if kind == "conv":
        return {"w_in": ((h, 3 * h), h), "w_out": ((h, h), h),
                "w_conv": ((h, cfg.conv_taps), cfg.conv_taps)}, \
            {"ln1": h, "ln2": h}
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
    hkv = cfg.kv_heads(kind)
    vd = cfg.v_head_dim or cfg.head_dim  # gqa's is set with layer kinds only
    od = cfg.n_heads * vd
    mats = {"wq": ((h, qd), h), "wk": ((h, hkv * cfg.head_dim), h),
            "wv": ((h, hkv * vd), h), "wo": ((od, h), od)}
    norms = {"ln1": h, "ln2": h}
    if cfg.attn_gate:
        mats["wg"] = ((h, od), h)
    if cfg.qk_norm:
        norms.update(q_norm=cfg.head_dim, k_norm=cfg.head_dim)
    if cfg.post_norms:
        norms.update(ln1_post=h, ln2_post=h)
    return mats, norms


def init_params(key: jax.Array, cfg: MoEServeConfig) -> Dict[str, Any]:
    """Global parameter tree (the expert leaves carry the ``[n_held, ...]``
    axis: all the routed experts, or this member's share). Layers come in
    stacked groups by (FFN kind, attention kind) — ``cfg.param_groups()``:
    ``blocks`` [n_moe_layers, ...] and, where the description has a dense
    prefix, ``dense_blocks`` [first_k_dense, ...]; with layer kinds the
    window layers' ``window_blocks`` / ``dense_window_blocks``, the conv
    layers' ``conv_blocks`` / ``dense_conv_blocks`` and the retention
    layers' ``retention_blocks`` / ``dense_retention_blocks`` beside them.
    Every matrix is drawn in float32 (embedding 0.02, others 1/sqrt(fan-in))
    and stored in ``cfg.param_dtype``; a layer's norm gains are ones (or,
    where ``cfg.norm_gain_scale`` asks, 1 + that x a normal), the gate bias
    a normal of scale 0.01, a sink kind's per-head logit a normal of
    scale 1.0 and a retention layer's gate bias ``bg`` uniform in
    ``GATE_BIAS_RANGE``, all float32."""
    k = jax.random.split(key, 12)
    h, f, e = cfg.dim, cfg.moe_ffn, cfg.n_held
    dtype = jnp.dtype(cfg.param_dtype)

    def rnd(name, shape, scale, group=0):
        kk = k[_SPLIT_KEY[name]] if name in _SPLIT_KEY and not group else \
            jax.random.fold_in(key, group + (_FOLD_KEY.get(name)
                                             or _SPLIT_KEY[name]))
        return (jax.random.normal(kk, shape, jnp.float32)
                * scale).astype(dtype)

    moe = {"router": ((h, cfg.moe_experts), h), "we_gate": ((e, h, f), h),
           "we_up": ((e, h, f), h), "we_down": ((e, f, h), f)}
    if cfg.shared_ffn:
        fs = cfg.shared_ffn
        moe.update({"ws_gate": ((h, fs), h), "ws_up": ((h, fs), h),
                    "ws_down": ((fs, h), fs)})
    fd = cfg.dense_ffn
    dense = {"w_gate": ((h, fd), h), "w_up": ((h, fd), h),
             "w_down": ((fd, h), fd)}

    def group(name, n):
        kind = next((k for k in ("window", "conv", "retention")
                     if k + "_" in name), "full")
        fold = _GROUP_FOLD[name]
        mats, norms = _attn_shapes(cfg, kind)
        is_dense = name.startswith("dense_")
        mats = {**mats, **(dense if is_dense else moe)}
        def gain(leaf, width):
            if not cfg.norm_gain_scale:
                return jnp.ones((n, width), jnp.float32)
            return 1.0 + cfg.norm_gain_scale * jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD_KEY[leaf]), (n, width),
                jnp.float32)

        out = {leaf: gain(leaf, width) for leaf, width in norms.items()}
        out.update({leaf: rnd(leaf, (n,) + shape, 1.0 / math.sqrt(fan), fold)
                    for leaf, (shape, fan) in mats.items()})
        if cfg.gate == "sigmoid_bias" and not is_dense:
            out["router_bias"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD_KEY["router_bias"]),
                (n, cfg.moe_experts), jnp.float32) * ROUTER_BIAS_SCALE
        if kind == "retention":
            out["bg"] = jax.random.uniform(
                jax.random.fold_in(key, fold + _FOLD_KEY["bg"]),
                (n, cfg.n_kv_heads), jnp.float32, *GATE_BIAS_RANGE)
        if kind in cfg.sink:
            out["sink"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD_KEY["sink"]),
                (n, cfg.n_heads), jnp.float32) * SINK_SCALE
        return out

    sizes = Counter(name for name, _ in cfg.param_groups())
    params = {
        "embed": rnd("embed", (cfg.vocab, h), 0.02),
        "final_norm": jnp.ones((h,), jnp.float32),
    }
    if not cfg.tie_head:  # a tied head is the embedding: no leaf of its own
        params["head"] = rnd("head", (h, cfg.vocab), 1.0 / math.sqrt(h))
    params.update({name: group(name, n) for name, n in sizes.items()})
    return params


# The router's product. The softmax gate's is at XLA's default, as it always
# was. A sigmoid-bias model computes its router in float32 by definition, and
# has to: top-4 of 64 sigmoid scores sit closer together than a product of
# bfloat16-rounded operands resolves (13 % of served tokens moved on the
# chip when it did not: PERF.md section 6, PR 26); 2048 x 64 a token is free.
_ROUTER_PRECISION = {"softmax": None, "sigmoid_bias": lax.Precision.HIGHEST}


def _moe_block(cfg: MoEServeConfig, impl: str, reads: Optional[list] = None):
    """The FFN half of a layer as an :func:`inference._forward_cached`-style
    ``ffn`` hook, by what the layer's leaves say it is: a dense-prefix layer
    (no router) runs the dense SwiGLU; an expert layer routes over the EP
    axis (sorted path for prefill throughput, packed LL for decode), experts
    being the LOCAL shard, and adds the shared expert — once per token,
    outside ``moe_ffn``, whatever the EP world.

    ``reads`` (a list, the decode / verify program's): the block then takes
    what the slot loop hands it (``rows`` [B], ``place``:
    :func:`inference._forward_slots`) down to ``moe_ffn(..., rows=,
    layer=)`` — the expert leaves as stored, not yet sliced or cast — and
    appends each expert layer's ``n_reached`` to it, for the program to sum
    and return. Without it (every prefill program, the one-shot paths) the
    block ignores both and is what it always was."""

    def moe_block(h2, lp, rows=None, place=None):
        if "router" not in lp:
            with jax.named_scope("ffn.dense"):
                return _dense_ffn(h2, lp)
        b, sq, hd = h2.shape
        flat = h2.reshape(b * sq, hd)
        with jax.named_scope("moe.router"):
            router_logits = jnp.dot(
                flat.astype(jnp.float32), lp["router"].astype(jnp.float32),
                precision=_ROUTER_PRECISION[cfg.gate])
        if reads is None:
            experts = [lp[n].astype(flat.dtype) for n in _EXPERT_LEAVES]
            loop_kw = {}
        else:
            group, layer = place
            experts = [group[n] for n in _EXPERT_LEAVES]
            loop_kw = dict(rows=jnp.repeat(rows, sq), layer=layer)
        out, *rest = ep_ops.moe_ffn(
            flat, router_logits, *experts,
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
            experts_held=cfg.experts_held or None,
            first_expert=cfg.first_expert,
            **loop_kw,
        )
        if reads is not None:
            reads.append(rest[-1])  # (aux_loss, z_loss, n_reached)
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


def _member(tree):
    """A member's slice of arrays shard_map hands in with the [1, ...] shard
    dimension in front (an array, or a pool's ``{group: array}``)."""
    return jax.tree.map(lambda a: a[0], tree)


def _lead(tree):
    """The inverse: the shard dimension back in front."""
    return jax.tree.map(lambda a: a[None], tree)


def _shapes(*trees) -> tuple:
    """The leaf shapes of caches' arrays: a compiled program's key."""
    return tuple(a.shape for a in jax.tree.leaves(trees))


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
        if cfg.experts_held and self.world > 1:
            raise ValueError(
                f"experts_held {cfg.experts_held} of {cfg.moe_experts} is "
                f"ONE member's share of a wider deployment; over a dp world "
                f"of {self.world} give each member its own description, or "
                f"all the experts to the world")
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
        e_local = self.cfg.n_held // w

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
                _strip_shard(p), tok[0], _member(kc), _member(vc), ln[0],
                cfg, impl
            )
            return logits[None], _lead(nk), _lead(nv), nlen[None]

        key = ("fwd", impl, tokens.shape, _shapes(cache.k, cache.v))
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
        if cfg.capacity_factor < cfg.drop_free_factor:
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

        row_bytes = _obsc.gauge(
            "serving_kv_row_bytes",
            "bytes one cached position of one layer holds in the slot pool "
            "(label kind: the attention kind, or the layer kind of a pool "
            "with cache groups)")
        pool_bytes = _obsc.gauge(
            "serving_kv_pool_bytes",
            "bytes of the slot pool's arrays by cache group (label group: "
            "full | window | conv | retention, or the attention kind of a "
            "pool with one)")
        for group in {g for g, _ in inference.cache_groups(self.cfg)}:
            arrays = [a for a in (inference.group_array(pool, group)
                                  for pool in (cache.k, cache.v))
                      if a is not None]  # a conv group keeps one
            label = group or self.cfg.attn
            pool_bytes.set(sum(a.nbytes for a in jax.tree.leaves(arrays)),
                           group=label)
            if group in inference.STATE_GROUPS:  # no row by position
                _obsc.gauge(
                    "serving_state_bytes_per_slot",
                    "bytes ONE layer's state holds of one slot in a state "
                    "group of the slot pool, whatever the slot's length "
                    "(label kind: retention — S and z)"
                ).set(sum(math.prod(a[0].shape[2:]) * a[0].dtype.itemsize
                          for a in arrays), kind=label)
                continue
            row_bytes.set(sum(math.prod(a.shape[4:]) * a.dtype.itemsize
                              for a in arrays), kind=label)
            if group in inference.RING_GROUPS:
                _obsc.gauge(
                    "serving_kv_ring_rows",
                    "rows a slot keeps of each window layer in the slot "
                    "pool: the ring (window - 1 + the widest write, or "
                    "more); under the label group=conv, of each conv layer "
                    "(taps - 1 + the widest write, or more)"
                ).set(arrays[0].shape[3],
                      **({} if group == "window" else {"group": group}))
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
                SlotKVCache(_member(kc), _member(vc), ln[0]), cfg,
                start=off[0], sampling=samp, adapters=adp, adapter_ids=ids,
                slots=idx, ffn=_moe_block(cfg, "sort"))
            return t[None], _lead(out.k), _lead(out.v), out.lengths[None]

        key = ("prefill_slots", tokens.shape, _shapes(cache.k, cache.v),
               sampled, adapted, compact)
        fn = self._fn(key, lambda: self._shard_mapped(
            uccl_moe_prefill_slots, 7 + len(extra), 4, params,
            donate=(5, 6, 7)))
        tok, nk, nv, nlen = fn(params, tokens, prompt_lens, new_mask,
                               start, cache.k, cache.v, cache.lengths,
                               *extra)
        return tok, MoESlotCache(nk, nv, nlen)

    def _step_slots(self, kind, params, tokens, active, cache, impl,
                    sampling, adapters, adapter_ids):
        """The one builder of the decode and the verify program (``kind``):
        :func:`inference.verify_slots`, or its one-token case
        :func:`inference.decode_step_slots`, run per shard with the EP block
        as its FFN — one compiled program a call, named after ``kind``
        (``jit_uccl_moe_<kind>_slots``) and keyed apart in ``_fns``. Returns
        the statement's own outputs, then the experts read [W] (a model
        with no expert layer returns no such count), then the new pool."""
        self._check_drop_free()
        cfg = self.cfg
        sampled, adapted = sampling is not None, adapters is not None
        extra = _flat_extra(sampling, adapters, adapter_ids)
        experts = cfg.n_moe_layers > 0  # else no count is returned at all
        counted = experts and self.world == 1 and impl == "sort"
        step, n_out = {"verify": (inference.verify_slots, 2),
                       "decode": (inference.decode_step_slots, 1)}[kind]

        def per_shard(p, tok, mask, kc, vc, ln, *rest):
            samp, adp, ids = _split_extra([r[0] for r in rest], sampled,
                                          adapted)
            reads = [] if counted else None
            *out, pool = step(
                _strip_shard(p), tok[0], mask[0],
                SlotKVCache(_member(kc), _member(vc), ln[0]), cfg,
                sampling=samp, adapters=adp, adapter_ids=ids,
                ffn=_moe_block(cfg, impl, reads))
            if counted:
                out.append(sum(reads))
            return (*(o[None] for o in out), _lead(pool.k), _lead(pool.v),
                    pool.lengths[None])

        per_shard.__name__ = per_shard.__qualname__ = f"uccl_moe_{kind}_slots"
        key = (f"{kind}_slots", impl, tokens.shape,
               _shapes(cache.k, cache.v), sampled, adapted)
        fn = self._fn(key, lambda: self._shard_mapped(
            per_shard, 5 + len(extra), n_out + counted + 3, params,
            donate=(3, 4, 5)))
        *out, nk, nv, nlen = fn(params, tokens, active, cache.k, cache.v,
                                cache.lengths, *extra)
        if experts and not counted:  # the batched GEMMs: every expert,
            # whatever the rows
            out.append(np.full(
                self.world, cfg.n_held // self.world * cfg.n_moe_layers,
                np.int32))
        return (*out, MoESlotCache(nk, nv, nlen))

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
        [W, B_loc], experts read [W], cache'); ``cache`` is consumed, as
        :meth:`prefill_slots` consumes it. Experts read: how many experts'
        weights each member's expert GEMMs read in the step, summed over
        its expert layers, of the ``n_held / W`` x ``n_moe_layers`` it
        holds. On one shard with ``impl`` "sort" the ``active`` rows are
        handed down as the rows that count (``ep.ops.moe_ffn(..., rows=)``)
        and the program reads, and counts, the experts they reached;
        otherwise the program is what it always was and reads them all."""
        return self._step_slots("verify", params, tokens, active, cache,
                                impl, sampling, adapters, adapter_ids)

    def decode_step_slots(self, params, token, active, cache: MoESlotCache,
                          impl: str = "ll", sampling=None, adapters=None,
                          adapter_ids=None):
        """One masked autoregressive step over the slot pool (packed LL EP
        path by default) — the S=1 case of :meth:`verify_slots`, as ONE
        compiled program: the ``[B_loc] -> [B_loc, 1]`` window and the
        ``[:, 0]`` of its result are :func:`inference.decode_step_slots`'s,
        inside the step, so host arrays go straight in and nothing is
        dispatched around it. token/active: [W, B_loc]; inactive slots
        neither write KV nor advance their length. Returns (next
        greedy-or-sampled token [W, B_loc], experts read [W], cache')."""
        return self._step_slots("decode", params, token, active, cache,
                                impl, sampling, adapters, adapter_ids)

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
            key = ("gen", impl, new_tokens, tok0.shape,
                   _shapes(cache.k, cache.v))

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

        key = ("gen_sampled", impl, new_tokens, logits.shape,
               _shapes(cache.k, cache.v))

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
