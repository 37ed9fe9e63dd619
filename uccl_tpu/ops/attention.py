"""Attention: reference, ring (context-parallel over a mesh axis), Ulysses.

Sequence/context parallelism is a first-class capability this framework adds over
the reference (SURVEY.md §5 "long-context": the reference has only lite-ep's
experimental 0-SM CP primitive, lite-ep/README.md:25). Two schemes:

* :func:`ring_attention` — KV blocks rotate around the ``cp`` ring via
  ``lax.ppermute`` while each member accumulates blockwise online-softmax
  attention for its local queries. Communication rides ICI neighbor links and
  overlaps with compute under XLA's async collective scheduling.
* :func:`ulysses_attention` — all-to-all reshard (sequence ↔ heads) so each
  member runs full-sequence attention on a head slice; reuses the same
  ``all_to_all`` machinery as expert parallelism.

All functions are *per-shard* (designed for use inside ``shard_map``), take
``[B, S, H, D]`` tensors, support GQA (fewer KV heads than Q heads), causal
masking, and accumulate in float32 regardless of input dtype.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from uccl_tpu.utils.topology import ppermute_pairs

_NEG_INF = -1e30  # finite "masked" score: keeps online-softmax math NaN-free


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: repeat KV heads to match Q heads. [B,S,Hkv,D] -> [B,S,Hkv*n_rep,D]."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def _scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """[B,Sq,H,D] x [B,Sk,H,D] -> [B,H,Sq,Sk] in f32."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jax.Array:
    """Full (single-shard) attention. Offsets give the absolute positions of the
    local q/kv blocks so causal masking stays correct under sequence sharding."""
    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = _scores(q, k, scale)
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1]) + kv_offset
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out.astype(q.dtype)


def _block_attend(q, k, v, m, l, o, scale, mask):
    """One online-softmax accumulation step.

    q: [B,Sq,H,D]; k,v: [B,Sk,H,D] (heads already repeated); mask: [Sq,Sk] bool
    or None; m,l: [B,H,Sq] f32 running max / normalizer; o: [B,Sq,H,D] f32.
    """
    s = _scores(q, k, scale)  # [B,H,Sq,Sk]
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_blk = jnp.max(s, axis=-1)  # [B,H,Sq]
    m_new = jnp.maximum(m, m_blk)
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])  # [B,H,Sq,Sk]
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _auto_block(s: int, cap: int = 1024) -> int:
    """Largest power-of-two block <= cap dividing s (1 if s is odd).

    cap=1024 was the fastest tile at head_dim 64 in the July v5e block sweep
    (PERF.md, carried-forward table)."""
    b = cap
    while b > 1 and s % b:
        b //= 2
    return b


def _merge_blocks(o_acc, lse_acc, o_blk, lse_blk):
    """Merge two normalized blockwise attention results via their LSEs.

    o: [B,Sq,H,D] f32 (each already softmax-normalized over its own keys);
    lse: [B,H,Sq]. Fully-masked blocks carry lse=-1e30 and merge as no-ops.
    """
    m = jnp.maximum(lse_acc, lse_blk)
    w_acc = jnp.exp(lse_acc - m)
    w_blk = jnp.exp(lse_blk - m)
    denom = w_acc + w_blk

    def bcast(w):  # [B,H,Sq] -> [B,Sq,H,1]
        return w.transpose(0, 2, 1)[..., None]

    o = (o_acc * bcast(w_acc) + o_blk * bcast(w_blk)) / bcast(denom)
    return o, m + jnp.log(denom)


def _flash_ring(q, k, v, axis, causal, block_q, block_k, interpret):
    """Ring attention with the Pallas flash kernel as the per-block compute.

    Step 0 is every member's own (causal-diagonal) block — a static causal
    flash call. Later steps are either fully visible (source chunk strictly
    earlier) or fully masked; a lax.cond picks between a non-causal flash
    call and a skip, so no per-element ring mask is ever built and the whole
    schedule stays SPMD. Blocks merge through the differentiable LSE merge,
    so training works end to end with no [S, S] materialization anywhere.
    """
    from uccl_tpu.ops.pallas_attention import flash_attention_lse

    n = lax.axis_size(axis)
    r = lax.axis_index(axis)
    b, sq, h, d = q.shape
    perm = ppermute_pairs(n, 1)

    o0, lse0 = flash_attention_lse(q, k, v, causal, block_q, block_k, interpret)
    o0 = o0.astype(jnp.float32)
    if n == 1:
        return o0.astype(q.dtype)

    def step(carry, t):
        k_blk, v_blk, o_acc, lse_acc = carry
        src = (r - t) % n

        def full(_):
            ob, lb = flash_attention_lse(
                q, k_blk, v_blk, False, block_q, block_k, interpret
            )
            return ob.astype(jnp.float32), lb

        def skip(_):
            return (
                jnp.zeros((b, sq, h, d), jnp.float32),
                jnp.full((b, h, sq), _NEG_INF, jnp.float32),
            )

        if causal:
            o_blk, lse_blk = lax.cond(src < r, full, skip, None)
        else:
            o_blk, lse_blk = full(None)
        o_acc, lse_acc = _merge_blocks(o_acc, lse_acc, o_blk, lse_blk)
        k_nxt = lax.ppermute(k_blk, axis, perm)
        v_nxt = lax.ppermute(v_blk, axis, perm)
        return (k_nxt, v_nxt, o_acc, lse_acc), None

    k1 = lax.ppermute(k, axis, perm)
    v1 = lax.ppermute(v, axis, perm)
    (_, _, o, _), _ = lax.scan(
        step, (k1, v1, o0, lse0), jnp.arange(1, n)
    )
    return o.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    *,
    causal: bool = True,
    impl: str = "xla",
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Context-parallel attention over mesh axis ``axis`` (per-shard fn).

    Each member holds the sequence chunk at position ``axis_index``; KV blocks
    rotate backwards around the ring so member r sees blocks originating from
    r, r-1, r-2, ... — with causal masking, later-origin blocks contribute
    nothing and are masked entirely (the compute is uniform across members to
    stay SPMD; XLA overlaps the ppermute with the block compute).

    impl="flash" runs each block through the Pallas flash kernel and merges
    via LSEs (:func:`_flash_ring`); impl="xla" uses einsum block attends.
    """
    if impl == "flash":
        # block sizing (and the error for a sequence Mosaic cannot tile)
        # is flash_attention_lse's; there is no quiet switch to the XLA path
        return _flash_ring(q, k, v, axis, causal, block_q, block_k, interpret)
    n = lax.axis_size(axis)
    r = lax.axis_index(axis)
    n_rep = q.shape[2] // k.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, d = q.shape
    sk = k.shape[1]
    perm = ppermute_pairs(n, 1)  # send local block to the next member

    qpos = jnp.arange(sq)[:, None]  # positions within a chunk
    kpos = jnp.arange(sk)[None, :]

    def step(carry, _):
        k_blk, v_blk, src, m, l, o = carry
        if causal:
            # absolute positions: q at r*sq + qpos, kv at src*sk + kpos
            mask = (r * sq + qpos) >= (src * sk + kpos)
        else:
            mask = None
        # GQA-repeat only at compute time: the ring carries the narrow KV
        # blocks, so ppermute traffic stays 1/n_rep of the repeated size.
        m, l, o = _block_attend(
            q, _repeat_kv(k_blk, n_rep), _repeat_kv(v_blk, n_rep), m, l, o, scale, mask
        )
        k_nxt = lax.ppermute(k_blk, axis, perm)
        v_nxt = lax.ppermute(v_blk, axis, perm)
        return (k_nxt, v_nxt, (src - 1) % n, m, l, o), None

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    (k, v, _, m, l, o), _ = lax.scan(step, (k, v, r, m0, l0, o0), None, length=n)
    l = jnp.maximum(l, 1e-20)  # fully-masked rows (can't happen with causal self-block)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    *,
    causal: bool = True,
    impl: str = "xla",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ulysses sequence parallelism (per-shard fn): all-to-all turns the
    sequence sharding into a head sharding, full-sequence attention runs on
    the local head slice, and the inverse all-to-all restores sequence
    sharding. Reuses the EP all-to-all path (SURVEY.md §2.6: "Ulysses =
    head-sharded all-to-all reusing the EP path"). Q heads must divide the
    axis size; KV heads are GQA-repeated up to the Q head count first when they
    don't divide it (costs wire bandwidth, keeps the schedule uniform)."""
    n = lax.axis_size(axis)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs q heads divisible by axis size {n}: q{q.shape}"
        )
    if k.shape[2] % n:
        rep = q.shape[2] // k.shape[2]
        k, v = _repeat_kv(k, rep), _repeat_kv(v, rep)

    def seq_to_heads(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if impl == "flash":
        from uccl_tpu.ops.pallas_attention import flash_attention

        out = flash_attention(qg, kg, vg, causal, interpret=interpret)
        return heads_to_seq(out)
    out = attention_reference(qg, kg, vg, causal=causal)
    return heads_to_seq(out)
