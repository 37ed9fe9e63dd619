"""Flagship model: MoE transformer exercising every parallel axis (dp/pp/cp/tp + ep).

This is the framework's analog of the applications the reference serves
(Megatron TP/PP workloads, DeepSeek-style EP MoE, long-context CP — SURVEY.md
§2.6): a Mixtral/DeepSeek-class decoder written *manually sharded* in one
``shard_map`` over the 4-axis mesh, TPU-first:

* tensor parallel (``tp``): Megatron-style column/row splits on attention and
  expert FFNs; vocab-parallel embedding + cross-entropy.
* context parallel (``cp``): ring attention (default) or Ulysses over the
  sequence dimension — the long-context layer.
* expert parallel (``dp``×``cp``): capacity-bucketed all-to-all dispatch/combine
  from :mod:`uccl_tpu.ep.ops`.
* pipeline parallel (``pp``): GPipe microbatch schedule from
  :mod:`uccl_tpu.parallel.pipeline`, layers sharded over stages.
* data parallel (``dp``): batch sharding; gradient reduction falls out of
  shard_map's transpose (replicated params → psum'd cotangents).

Everything is static-shape, scan-based, and bfloat16-on-MXU friendly.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uccl_tpu.ep import ops as ep_ops
from uccl_tpu.models.layers import rms_norm, rope, tp_cross_entropy
from uccl_tpu.ops.attention import attention_reference, ring_attention, ulysses_attention
from uccl_tpu.parallel.mesh import AXIS
from uccl_tpu.parallel.pipeline import gpipe_spmd, pipeline_train


@dataclasses.dataclass(frozen=True)
class FlagshipConfig:
    vocab: int = 1024
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 32
    moe_experts: int = 8
    moe_topk: int = 2
    moe_ffn: int = 512
    capacity_factor: float = 1.5
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 1e-3
    n_microbatches: int = 1
    pp_schedule: str = "gpipe"  # "gpipe" (autodiff+remat) | "1f1b" (manual)
    seq_mode: str = "ring"  # "ring" | "ulysses"
    attn_impl: str = "auto"  # "auto" | "flash" | "xla": kernel when cp == 1
    moe_impl: str = "sort"  # "sort" (ragged) | "dense" (oracle) | "ll" (packed
    # grouped-GEMM path, no padded FLOPs — ep/ll.py)
    moe_wire: str = "lax"  # "lax" | "pallas" (device-initiated remote-DMA
    # a2a; forward-only — the Pallas kernel has no vjp, so keep "lax" for
    # training paths)
    moe_chunks: int = 0  # pallas-wire chunk-pipeline depth (0 = auto: the
    # EP layer picks 2 double-buffered chunks when the budget allows,
    # overlapping expert GEMMs with the dispatch/combine wire; ignored on
    # the lax wire)
    wire_fp8: bool = False
    wire_dtype: Any = None  # None | "fp8" | "int8": block-quantized EP wire
    # payloads (shared ops.quant codec; wire_fp8=True is the legacy
    # spelling of "fp8" — an explicit wire_dtype wins)
    remat: str = "full"  # "full" | "dots" | "mlp" | "none" — see _remat_wrap
    dtype: Any = jnp.float32  # activation dtype (bfloat16 on TPU)


# ---------------------------------------------------------------------------
# Parameters


def param_specs(cfg: FlagshipConfig) -> Dict[str, Any]:
    """PartitionSpec tree matching :func:`init_params`' pytree."""
    ep_axes = AXIS.EP
    return {
        "embed": P(AXIS.TP, None),
        "blocks": {
            "ln1": P(AXIS.PP, None),
            "ln2": P(AXIS.PP, None),
            "wq": P(AXIS.PP, None, AXIS.TP),
            "wk": P(AXIS.PP, None, AXIS.TP),
            "wv": P(AXIS.PP, None, AXIS.TP),
            "wo": P(AXIS.PP, AXIS.TP, None),
            "router": P(AXIS.PP, None, None),
            "we_gate": P(AXIS.PP, ep_axes, None, AXIS.TP),
            "we_up": P(AXIS.PP, ep_axes, None, AXIS.TP),
            "we_down": P(AXIS.PP, ep_axes, AXIS.TP, None),
        },
        "final_norm": P(None),
        "head": P(None, AXIS.TP),
    }


def init_params(key: jax.Array, cfg: FlagshipConfig) -> Dict[str, Any]:
    """Initialize the full (global) parameter pytree on host."""
    k = jax.random.split(key, 10)
    h, l = cfg.dim, cfg.n_layers
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    e, f = cfg.moe_experts, cfg.moe_ffn
    s_in = 1.0 / math.sqrt(h)
    s_ffn = 1.0 / math.sqrt(f)

    def rnd(kk, shape, scale):
        return (jax.random.normal(kk, shape, jnp.float32) * scale).astype(jnp.float32)

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
            "we_down": rnd(k[8], (l, e, f, h), s_ffn),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd(k[9], (h, cfg.vocab), s_in),
    }


def shard_params(params, mesh: Mesh, cfg: FlagshipConfig):
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


# ---------------------------------------------------------------------------
# Per-shard forward (inside shard_map)


def resolve_attn_impl(cfg: FlagshipConfig) -> str:
    """The attention path ``cfg`` takes on this backend: ``"flash"`` (the
    Pallas kernel) or ``"xla"`` (the einsum reference). ``"auto"`` is flash
    on TPU, where it is compiled, and xla on CPU, where the kernel could
    only be interpreted."""
    if cfg.attn_impl in ("flash", "xla"):
        return cfg.attn_impl
    if cfg.attn_impl != "auto":
        raise ValueError(
            f"unknown attn_impl {cfg.attn_impl!r} (want auto|flash|xla)"
        )
    return "flash" if jax.default_backend() == "tpu" else "xla"


def _attention(x, lp, cfg: FlagshipConfig):
    """x: [B, S_loc, H_model] -> [B, S_loc, H_model] (pre-psum over tp)."""
    b, s_loc, _ = x.shape
    d = cfg.head_dim
    nh_loc = lp["wq"].shape[-1] // d
    nkv_loc = lp["wk"].shape[-1] // d
    with jax.named_scope("attn.qkv"):
        q = (x @ lp["wq"].astype(x.dtype)).reshape(b, s_loc, nh_loc, d)
        kk = (x @ lp["wk"].astype(x.dtype)).reshape(b, s_loc, nkv_loc, d)
        v = (x @ lp["wv"].astype(x.dtype)).reshape(b, s_loc, nkv_loc, d)
        cp_idx = lax.axis_index(AXIS.CP)
        positions = cp_idx * s_loc + jnp.arange(s_loc)
        q = rope(q, positions, cfg.rope_theta)
        kk = rope(kk, positions, cfg.rope_theta)
    with jax.named_scope("attn.core"):
        attn = _attention_core(q, kk, v, cfg)
    with jax.named_scope("attn.out"):
        return attn.reshape(b, s_loc, nh_loc * d) @ lp["wo"].astype(x.dtype)


def _attention_core(q, kk, v, cfg: FlagshipConfig):
    """Causal attention over the (possibly context-parallel) sequence."""
    impl = resolve_attn_impl(cfg)
    if lax.axis_size(AXIS.CP) == 1:
        if impl == "flash":
            # No context parallelism: the single-shard Pallas flash kernel
            # (MXU blockwise online softmax in VMEM). A sequence it cannot
            # tile is an error there, never a quiet switch to the reference.
            from uccl_tpu.ops.pallas_attention import flash_attention

            return flash_attention(q, kk, v, True)
        else:
            # Direct single-shard attention, NOT ring_attention at n=1: the
            # math is identical, but the ring's self-ppermute would poison
            # manual-schedule vjps (ppermute's transpose silently drops
            # cotangents under check_vma=False when the vjp runs inside a
            # non-uniformly-predicated cond — the sharp edge check_vma=True
            # exists to catch).
            return attention_reference(q, kk, v, causal=True)
    if cfg.seq_mode == "ulysses":
        return ulysses_attention(q, kk, v, AXIS.CP, causal=True, impl=impl)
    return ring_attention(q, kk, v, AXIS.CP, causal=True, impl=impl)


def _layer(x, lp, cfg: FlagshipConfig):
    """One transformer block (per-shard). x: [B, S_loc, H]. Returns (x, aux)."""
    b, s_loc, h = x.shape
    attn_out = _attention(rms_norm(x, lp["ln1"], cfg.norm_eps), lp, cfg)
    with jax.named_scope("attn.out"):
        x = x + lax.psum(attn_out, AXIS.TP)

    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    flat = h2.reshape(b * s_loc, h)
    with jax.named_scope("moe.router"):
        router_logits = flat.astype(jnp.float32) @ lp["router"]
    moe_out, aux, z = ep_ops.moe_ffn(
        flat,
        router_logits,
        lp["we_gate"].astype(flat.dtype),
        lp["we_up"].astype(flat.dtype),
        lp["we_down"].astype(flat.dtype),
        AXIS.EP,
        num_selected=cfg.moe_topk,
        capacity_factor=cfg.capacity_factor,
        wire_fp8=cfg.wire_fp8,
        wire_dtype=cfg.wire_dtype,
        impl=cfg.moe_impl,
        wire=cfg.moe_wire,
        n_chunks=cfg.moe_chunks,
    )
    x = x + lax.psum(moe_out.reshape(b, s_loc, h), AXIS.TP)
    aux_scalar = cfg.aux_loss_weight * aux + cfg.z_loss_weight * z
    return x, aux_scalar


def _remat_wrap(f, mode: str):
    """Rematerialization wrapper for one transformer block under the
    per-stage ``lax.scan``. ``"full"`` recomputes the whole block in
    backward (minimum activation liveness — the conservative default);
    ``"dots"`` saves no-batch-dim matmul outputs (projections, router,
    vocab — NOT the expert einsums, which carry the ``e`` batch dim) and
    recomputes the rest; ``"mlp"`` additionally saves the expert-GEMM
    operands/results tagged in :mod:`uccl_tpu.ep.ops` / :mod:`~.ep.ll`
    (``MOE_CHECKPOINT_NAMES``) while still rematerializing the attention
    interior — the measured v5e sweet spot (backward re-runs NO forward
    GEMM; attention is HBM-bound on its [S,S] scores, so saving them
    costs more bandwidth than recomputing them); ``"none"`` disables
    remat (the scan saves every residual — fastest when activations
    fit). Gradients are bit-identical across modes; only the
    memory/recompute schedule changes."""
    if mode == "full":
        return jax.checkpoint(f)
    if mode == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if mode == "mlp":
        pol = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                *ep_ops.MOE_CHECKPOINT_NAMES
            ),
        )
        return jax.checkpoint(f, policy=pol)
    if mode == "none":
        return f
    raise ValueError(
        f"unknown remat mode {mode!r} (want full|dots|mlp|none)"
    )


@jax.named_scope("embed")
def _embed(tokens, embed_local, cfg: FlagshipConfig):
    """Vocab-parallel embedding lookup. tokens: [B, S_loc] -> [B, S_loc, H]."""
    v_loc = embed_local.shape[0]
    off = lax.axis_index(AXIS.TP) * v_loc
    local = tokens - off
    in_range = (local >= 0) & (local < v_loc)
    emb = jnp.take(embed_local, jnp.clip(local, 0, v_loc - 1), axis=0)
    emb = jnp.where(in_range[..., None], emb, jnp.zeros_like(emb))
    return lax.psum(emb, AXIS.TP)


def _per_shard_logits_aux(params, tokens, cfg: FlagshipConfig):
    """tokens: [B_loc, S_loc] -> (logits [B_loc, S_loc, V_loc], aux scalar)."""
    b_loc, s_loc = tokens.shape
    m = cfg.n_microbatches
    if b_loc % m:
        raise ValueError(f"local batch {b_loc} not divisible by {m} microbatches")

    x = _embed(tokens, params["embed"], cfg).astype(cfg.dtype)
    xmb = x.reshape(m, b_loc // m, s_loc, cfg.dim)

    layer_ckpt = _remat_wrap(partial(_layer, cfg=cfg), cfg.remat)

    def stage_fn(xm):
        def body(carry, lp):
            y, aux = layer_ckpt(carry, lp)
            return y, aux

        y, auxs = lax.scan(body, xm, params["blocks"])
        return y, jnp.sum(auxs)

    out, aux = gpipe_spmd(stage_fn, xmb, AXIS.PP)
    x = out.reshape(b_loc, s_loc, cfg.dim)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = x.astype(jnp.float32) @ params["head"]
    return logits, aux


def _per_shard_loss(params, tokens, targets, cfg: FlagshipConfig):
    logits, aux = _per_shard_logits_aux(params, tokens, cfg)
    v_loc = logits.shape[-1]
    off = lax.axis_index(AXIS.TP) * v_loc
    per_token = tp_cross_entropy(
        logits.reshape(-1, v_loc), targets.reshape(-1), off, AXIS.TP
    )
    loss = jnp.mean(per_token)
    loss = lax.pmean(loss, AXIS.EP)  # average over dp×cp data shards
    # aux is summed over layers and microbatches; normalize and average
    aux_norm = lax.pmean(aux, AXIS.EP) / (cfg.n_layers * cfg.n_microbatches)
    return loss + aux_norm, loss


# ---------------------------------------------------------------------------
# Manual-schedule training path (pp_schedule="1f1b")
#
# The gpipe path above differentiates THROUGH the pipeline scan (autodiff +
# remat: simple, but residual liveness grows with M). This path runs the
# hand-written 1F1B schedule (parallel/pipeline.py pipeline_train): bounded
# activation liveness, explicit boundary gradients — the embedding backward
# runs through the returned input cotangents, the loss head through its own
# gradient outputs, and the MoE aux/z losses ride the aux channel.


def _grad_sync_specs(cfg: FlagshipConfig):
    """Per-leaf mesh axes a manual gradient must be psum'd over: every axis
    the parameter is REPLICATED on — except pp, whose reduction
    pipeline_train already performed (loss params / input cotangents) or
    which shards the leaf (stage params)."""
    def axes_of(spec):
        used = set()
        for part in spec:
            if part is None:
                continue
            if isinstance(part, (tuple, list)):
                used.update(part)
            else:
                used.add(part)
        return tuple(
            a for a in AXIS.ALL if a not in used and a != AXIS.PP
        )

    return jax.tree.map(axes_of, param_specs(cfg))


def _per_shard_manual_grads(params, tokens, targets, cfg: FlagshipConfig):
    """Per-shard (total, ce, grads) on the manual 1F1B schedule. Gradient
    semantics match autodiff-of-pmean(loss over dp×cp): per-member partials,
    psum over each leaf's replicated axes, divided by the EP world."""
    # Ring/Ulysses CP rotate KV via lax.ppermute inside the stage. XLA's
    # collective-permute has no replica groups (its source-target pairs are
    # global), so a ppermute inside the schedule's per-slot lax.cond would
    # deadlock: members on stages whose predicate is false never post their
    # sends (root-caused round 3 — the round-2 "zeroed cotangents" were this
    # same unmatched-collective unsoundness). psum/all_to_all are safe under
    # cond because their replica groups never cross pp. Fix: run the
    # schedule in uniform (select-not-branch) mode whenever cp > 1 — the
    # same discipline gpipe_spmd always uses — at ~(P-1)/M extra masked
    # compute on the ramp slots.
    uniform = lax.axis_size(AXIS.CP) != 1
    b_loc, s_loc = tokens.shape
    m = cfg.n_microbatches
    if b_loc % m:
        raise ValueError(f"local batch {b_loc} not divisible by {m} microbatches")

    def embed_fn(emb):
        return _embed(tokens, emb, cfg).astype(cfg.dtype)

    x, embed_vjp = jax.vjp(embed_fn, params["embed"])
    xmb = x.reshape(m, b_loc // m, s_loc, cfg.dim)
    tmb = targets.reshape(m, b_loc // m, s_loc)

    layer_ckpt = _remat_wrap(partial(_layer, cfg=cfg), cfg.remat)

    def stage_fn(blocks, xm):
        def body(carry, lp):
            y, aux = layer_ckpt(carry, lp)
            return y, aux

        y, auxs = lax.scan(body, xm, blocks)
        return y, jnp.sum(auxs)

    n_tok = b_loc * s_loc  # per-shard tokens: summed mb losses == local mean

    def loss_head(lp, y, tgt):
        with jax.named_scope("head"):
            xln = rms_norm(y, lp["final_norm"], cfg.norm_eps)
            logits = xln.astype(jnp.float32) @ lp["head"]
        v_loc = logits.shape[-1]
        off = lax.axis_index(AXIS.TP) * v_loc
        per_token = tp_cross_entropy(
            logits.reshape(-1, v_loc), tgt.reshape(-1), off, AXIS.TP
        )
        return jnp.sum(per_token) / n_tok

    loss_params = {
        "final_norm": params["final_norm"], "head": params["head"]
    }
    total, ce, dblocks, dlp, dxmb = pipeline_train(
        stage_fn, loss_head, params["blocks"], loss_params, xmb, tmb,
        AXIS.PP, aux_weight=1.0 / (cfg.n_layers * m), uniform=uniform,
    )
    (d_embed,) = embed_vjp(dxmb.reshape(b_loc, s_loc, cfg.dim).astype(x.dtype))

    grads = {
        "embed": d_embed,
        "blocks": dblocks,
        "final_norm": dlp["final_norm"],
        "head": dlp["head"],
    }
    n_ep = lax.axis_size(AXIS.EP)
    # Seed redundancy: the loss value is replicated across tp, and seeding
    # every member's vjp with 1 differentiates n_tp copies of it (the psum
    # transposes under check_vma=False mix the redundant seeds) — every
    # partial comes out exactly n_tp too large, uniformly. One global
    # divide restores d(L)/dθ; the autodiff path never sees this because
    # shard_map's own transpose accounts for replicated outputs.
    n_tp = lax.axis_size(AXIS.TP)

    def sync(g, axes):
        if axes:
            g = lax.psum(g, tuple(axes))
        return g / (n_ep * n_tp)

    grads = jax.tree.map(sync, grads, _grad_sync_specs(cfg))
    return lax.pmean(total, AXIS.EP), lax.pmean(ce, AXIS.EP), grads


def manual_loss_and_grads(params, tokens, targets, cfg: FlagshipConfig, mesh: Mesh):
    """Global (total, ce, grads) on the manual 1F1B schedule — the
    grads-producing counterpart of value_and_grad over :func:`loss_fn`."""

    def f(p, t, y):
        return _per_shard_manual_grads(p, t, y, cfg)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(param_specs(cfg), _data_spec(), _data_spec()),
        out_specs=(P(), P(), param_specs(cfg)),
        check_vma=False,
    )(params, tokens, targets)


# ---------------------------------------------------------------------------
# Host API


def _data_spec() -> P:
    return P(AXIS.DP, AXIS.CP)


def forward(params, tokens, cfg: FlagshipConfig, mesh: Mesh):
    """Global forward: tokens [B, S] -> logits [B, S, V]. Jit-compatible."""

    def f(p, t):
        logits, _ = _per_shard_logits_aux(p, t, cfg)
        return logits

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(param_specs(cfg), _data_spec()),
        out_specs=P(AXIS.DP, AXIS.CP, AXIS.TP),
        check_vma=False,
    )(params, tokens)


def loss_fn(params, tokens, targets, cfg: FlagshipConfig, mesh: Mesh):
    """Global mean loss (includes aux); returns (total_loss, ce_loss)."""

    def f(p, t, y):
        return _per_shard_loss(p, t, y, cfg)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(param_specs(cfg), _data_spec(), _data_spec()),
        out_specs=(P(), P()),
        check_vma=False,
    )(params, tokens, targets)


def make_train_step(cfg: FlagshipConfig, mesh: Mesh, learning_rate: float = 3e-4):
    """Returns (train_step, init_optimizer). train_step is jittable:
    (params, opt_state, tokens, targets) -> (params, opt_state, metrics)."""
    import optax

    tx = optax.adamw(learning_rate, weight_decay=0.01)

    def total_loss(p, t, y):
        total, ce = loss_fn(p, t, y, cfg, mesh)
        return total, ce

    def train_step(params, opt_state, tokens, targets):
        if cfg.pp_schedule == "1f1b":
            total, ce, grads = manual_loss_and_grads(
                params, tokens, targets, cfg, mesh
            )
        elif cfg.pp_schedule == "gpipe":
            (total, ce), grads = jax.value_and_grad(total_loss, has_aux=True)(
                params, tokens, targets
            )
        else:
            raise ValueError(
                f"unknown pp_schedule {cfg.pp_schedule!r}: expected 'gpipe' "
                "or '1f1b'"
            )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": total, "ce": ce}

    def init_optimizer(params):
        return tx.init(params)

    return train_step, init_optimizer


# ---------------------------------------------------------------------------
# Dense single-device reference (oracle for tests)


def reference_forward(params, tokens, cfg: FlagshipConfig):
    """Unsharded oracle implementing the same math (no mesh, no collectives).
    Capacity is computed from the *global* token count, so results match the
    sharded model only when capacity is large enough that nothing drops."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def one_layer(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        d = cfg.head_dim
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, d)
        kk = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, d)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, d)
        pos = jnp.arange(s)
        q, kk = rope(q, pos, cfg.rope_theta), rope(kk, pos, cfg.rope_theta)
        attn = attention_reference(q, kk, v, causal=True)
        x = x + attn.reshape(b, s, -1) @ lp["wo"]

        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        flat = h2.reshape(b * s, cfg.dim)
        logits = flat.astype(jnp.float32) @ lp["router"]
        cap = ep_ops.expert_capacity(
            flat.shape[0], cfg.moe_topk, cfg.moe_experts, cfg.capacity_factor
        )
        r = ep_ops.route_topk(logits, cfg.moe_topk, cap)
        xe = jnp.einsum("tec,th->ech", r.dispatch_mask.astype(flat.dtype), flat)
        act = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, lp["we_gate"])) * jnp.einsum(
            "ech,ehf->ecf", xe, lp["we_up"]
        )
        ye = jnp.einsum("ecf,efh->ech", act, lp["we_down"])
        moe = jnp.einsum("tec,ech->th", r.combine_weights.astype(ye.dtype), ye)
        x = x + moe.reshape(b, s, cfg.dim)
        return x, None

    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["blocks"])
        x, _ = one_layer(x, lp)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["head"]
