"""Shared model building blocks (per-shard functions for shard_map code):
RMSNorm, rotary embeddings, tensor-parallel cross-entropy.
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

Axis = Union[str, Tuple[str, ...]]


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rope(
    x: jax.Array, positions: jax.Array, theta: float = 10000.0,
    rotary_dim: int = 0,
) -> jax.Array:
    """Rotary position embedding, split-half (Llama) convention.

    x: [B, S, H, D]; positions: [S] absolute positions (callers under sequence
    sharding pass ``cp_index * S_local + arange(S_local)``), or [B, S]
    per-sequence positions (the slot-pool serving path, where every slot sits
    at its own decode offset). ``rotary_dim`` (0 = all of D) rotates the
    leading ``rotary_dim`` numbers of each head — split-half within them —
    and leaves the rest as they are (a partial rotary factor).
    """
    d = x.shape[-1]
    if 0 < rotary_dim < d:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
            axis=-1)
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [(B,) S, half]
    cos = jnp.cos(ang)[..., None, :]  # [(B,) S, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def tp_cross_entropy(
    logits_local: jax.Array,
    targets: jax.Array,
    vocab_offset: jax.Array,
    axis: Axis,
) -> jax.Array:
    """Cross-entropy with the vocab dimension sharded over ``axis``.

    logits_local: [T, V_local] this member's vocab slice (f32 recommended);
    targets: [T] global token ids; vocab_offset: scalar start of the local
    slice. Returns per-token loss [T] (replicated across the axis).

    The log-sum-exp runs distributed: global max via pmax, then psum of the
    local exp-sums — the standard Megatron vocab-parallel loss, expressed with
    XLA collectives.
    """
    logits_local = logits_local.astype(jnp.float32)
    v_local = logits_local.shape[-1]
    # the global max is a numerical-stability shift only — no gradient flows
    # through it (and pmax has no differentiation rule)
    m = lax.pmax(lax.stop_gradient(jnp.max(logits_local, axis=-1)), axis)  # [T]
    sumexp = jnp.sum(jnp.exp(logits_local - m[:, None]), axis=-1)
    lse = m + jnp.log(lax.psum(sumexp, axis))  # [T]
    # target logit: only the owning member contributes
    local_idx = targets - vocab_offset
    in_range = (local_idx >= 0) & (local_idx < v_local)
    safe_idx = jnp.clip(local_idx, 0, v_local - 1)
    tgt_local = jnp.take_along_axis(logits_local, safe_idx[:, None], axis=-1)[:, 0]
    tgt = lax.psum(jnp.where(in_range, tgt_local, 0.0), axis)
    return lse - tgt
