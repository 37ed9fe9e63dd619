"""Pallas TPU flash attention: forward + backward kernels, LSE-exposing API.

The hot attention op on the MXU: blockwise online-softmax attention computed in
VMEM, one (batch×head, q-block) program at a time, streaming KV blocks. The
causal variant skips fully-masked KV blocks, so wasted FLOPs shrink from 2× to
~0 at long sequence.

This is the framework's analog of the reference's hand-written device kernels
(the reference's compute-heavy paths are CUDA kernels, e.g.
ep/src/internode_ll.cu; attention itself lives in the frameworks UCCL serves).

Three public entry points:

* :func:`flash_attention` — drop-in attention, custom VJP backed by Pallas
  dq and dk/dv kernels (FlashAttention-2-style recomputation from the saved
  LSE — no [S, S] matrix is ever materialized, forward or backward).
* :func:`flash_attention_lse` — same, returning ``(out, lse)``. The LSE
  output is differentiable: its cotangent folds into the backward row term
  (``dS = P∘(dP − (Δ − g_lse))``), which is exactly what blockwise/ring
  merging needs to train through merged blocks.

``interpret=None`` resolves once from the backend
(:func:`uccl_tpu.utils.device.pallas_interpret`): compiled by Mosaic on TPU,
interpreted on CPU (the tests), an error anywhere else.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from uccl_tpu.utils.device import pallas_interpret

_NEG_INF = -1e30


def _compiler_params():
    # scratch carries state only across the innermost grid axis; the two
    # outer axes' programs are independent, so megacore may split them.
    # No vmem_limit_bytes: at the auto-sized 1024x1024 tiles all three
    # kernels compile under Mosaic's default scoped-VMEM limit on v5e
    # (libtpu 0.0.34; PERF.md "Bring-up").
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


# ---------------------------------------------------------------------------
# Forward kernel


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, scale, block_q, block_k, causal,
):
    """Grid (bh, iq, jk): one KV block per program, streamed through VMEM.

    Ref shapes: q [1, BQ, D]; k/v [1, BK, D]; o [1, BQ, D]; lse [1, BQ, 1].
    The LSE rides as a [BQ, 1] column (trailing singleton) so its block spec
    is TPU-tileable — a 2-D [1, BQ] block over [B*H, S] violates Mosaic's
    (8, 128) tiling rule, which only surfaces on real hardware. All kernel
    arithmetic stays rank-2 for the same reason.
    Scratch (m/l [BQ, 1], acc [BQ, D]) carries the online softmax across the
    jk dimension — jk is innermost, so for a fixed (bh, iq) the programs run
    back-to-back and the scratch is private to that q block.
    """
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[:, :] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:, :] = jnp.zeros_like(l_ref)
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    # Causal: KV blocks strictly after this q block contribute nothing.
    last_q_pos = (iq + 1) * block_q - 1
    relevant = (not causal) or (jk * block_k <= last_q_pos)

    @pl.when(relevant)
    def _attend():
        q = q_ref[0].astype(jnp.float32)  # [BQ, D]
        k = k_ref[0].astype(jnp.float32)  # [BK, D]
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            qpos = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = jk * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m = m_ref[:, :]  # [BQ, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[:, :] = l_ref[:, :] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:, :] = acc_ref[:, :] * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[:, :] = m_new

    @pl.when(jk == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :], 1e-20)  # [BQ, 1]
        o_ref[0] = (acc_ref[:, :] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :] + jnp.log(l)


@jax.named_scope("attn.flash")
def _flash_fwd(
    q, k, v, causal, block_q, block_k, interpret
) -> Tuple[jax.Array, jax.Array]:
    """q: [B, S, H, D]; k/v: [B, Sk, Hkv, D] -> (out [B,S,H,D], lse [B,H,S])."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {hkv}")
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})"
        )
    scale = 1.0 / math.sqrt(d)

    # [B, S, H, D] -> [B*H, S, D] program-major layout
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal
    )
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
            # GQA: head bh maps to kv head bh//n_rep; one KV block per program
            pl.BlockSpec((1, block_k, d), lambda bh, iq, jk: (bh // n_rep, jk, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, jk: (bh // n_rep, jk, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, jk: (bh, iq, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt)
    return (
        out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
        lse.reshape(b, h, sq),
    )


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 style: recompute P from saved LSE)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref,
    *, scale, block_q, block_k, causal,
):
    """Grid (bh, iq, jk), jk innermost: accumulate dQ for one q block while
    streaming KV blocks. delta = rowsum(dO∘O) − g_lse (the combined row term)."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    last_q_pos = (iq + 1) * block_q - 1
    relevant = (not causal) or (jk * block_k <= last_q_pos)

    @pl.when(relevant)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [BQ, 1]
        delta = delta_ref[0]  # [BQ, 1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qpos = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = jk * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)  # masked scores underflow to 0
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        ds = p * (dp - delta) * scale
        acc_ref[:, :] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(jk == n_kv - 1)
    def _finish():
        dq_ref[0] = acc_ref[:, :].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, block_q, block_k, causal,
):
    """Grid (bh, jk, iq), iq innermost: accumulate dK/dV for one KV block while
    streaming q blocks (at full q-head resolution; GQA-reduced outside)."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    last_q_pos = (iq + 1) * block_q - 1
    relevant = (not causal) or (jk * block_k <= last_q_pos)

    @pl.when(relevant)
    def _accum():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [BQ, 1]
        delta = delta_ref[0]  # [BQ, 1]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qpos = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kpos = jk * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [BQ, BK]
        dv_acc[:, :] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dk_acc[:, :] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:, :].astype(dv_ref.dtype)


@jax.named_scope("attn.flash")
def _flash_bwd(q, k, v, out, lse, g_out, g_lse, causal, block_q, block_k,
               interpret):
    """Pallas backward: returns (dq, dk, dv) without materializing [S, S]."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    scale = 1.0 / math.sqrt(d)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    dot = g_out.transpose(0, 2, 1, 3).reshape(b * h, sq, d).astype(q.dtype)
    # LSE/delta travel as [B*H, S, 1] columns (TPU-tileable blocks, see
    # _fwd_kernel docstring).
    lse_t = lse.reshape(b * h, sq, 1)
    # Combined row term: Δ − g_lse. The g_lse fold-in makes the LSE output
    # differentiable (dS = P∘(dP − (Δ − g_lse))), which ring merging needs.
    delta = jnp.sum(
        g_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * h, sq, 1)
    if g_lse is not None:
        delta = delta - g_lse.reshape(b * h, sq, 1)

    common = dict(scale=scale, block_q=block_q, block_k=block_k, causal=causal)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, jk: (bh // n_rep, jk, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, jk: (bh // n_rep, jk, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, jk: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, jk: (bh, iq, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt, dot, lse_t, delta)

    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
        ),
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, jk, iq: (bh // n_rep, jk, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, jk, iq: (bh // n_rep, jk, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, jk, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, jk, iq: (bh, iq, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda bh, jk, iq: (bh, jk, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, jk, iq: (bh, jk, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt, dot, lse_t, delta)

    # GQA: fold the n_rep q-head contributions back onto each KV head.
    dk = dk_full.reshape(b, hkv, n_rep, sk, d).sum(2).transpose(0, 2, 1, 3)
    dv = dv_full.reshape(b, hkv, n_rep, sk, d).sum(2).transpose(0, 2, 1, 3)
    return (
        dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


# ---------------------------------------------------------------------------
# Public API


def _default_blocks():
    """Tile sizes from config (UCCL_TPU_FLASH_BLOCK_Q/K): the on-chip tuning
    knob — the flash-vs-XLA crossover moves with (BQ, BKV) at long sequence,
    and an env sweep (benchmarks/attention_bench.py --block-sweep) must be
    able to retune without code changes. 0 (the default) means auto-size
    from the sequence: largest power-of-two divisor capped at 1024, the
    measured v5e optimum (see ops.attention._auto_block)."""
    from uccl_tpu.utils.config import param

    bq = param("flash_block_q", 0,
               help="flash attention q-tile rows (0 = auto-size)")
    bk = param("flash_block_k", 0,
               help="flash attention kv-tile rows (0 = auto-size)")
    return int(bq.get()), int(bk.get())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse_core(q, k, v, causal, block_q, block_k, interpret):
    # block_q/block_k are CONCRETE here: custom_vjp routes differentiation
    # through _lse_vjp_fwd (not this body), so any None-resolution must
    # happen in the public wrapper below, before the custom_vjp boundary.
    return _flash_fwd(q, k, v, causal, block_q, block_k, interpret)


def _lse_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _lse_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd(
        q, k, v, out, lse, g_out, g_lse, causal, block_q, block_k, interpret
    )


_flash_lse_core.defvjp(_lse_vjp_fwd, _lse_vjp_bwd)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Flash attention returning (out [B,S,H,D], lse [B,H,S]).

    The lse output is differentiable, so callers may merge blocks (ring/
    blockwise attention) and train straight through the merge. block_q/k
    default from UCCL_TPU_FLASH_BLOCK_Q/K; unset (0) auto-sizes to the
    largest power-of-two divisor of the sequence capped at 1024 — the
    measured v5e optimum at head_dim 64 (PERF.md round-5 block sweep).
    """
    from uccl_tpu.ops.attention import _auto_block

    dq, dk = _default_blocks()
    auto_q = auto_k = False
    if block_q is None:
        block_q = dq or _auto_block(q.shape[1])
        auto_q = not dq
    if block_k is None:
        block_k = dk or _auto_block(k.shape[1])
        auto_k = not dk
    # Fail fast when AUTO-sizing produced a sub-8 tile (ragged sequence,
    # e.g. S=1001 -> 1) that is about to be compiled by Mosaic, which
    # would reject it obscurely. Explicitly passed blocks (args or env)
    # are the caller's own; interpret mode accepts any tile and keeps
    # working (short decode-style sequences included).
    if interpret is None:
        interpret = pallas_interpret()
    if not interpret and (
        (auto_q and block_q < 8) or (auto_k and block_k < 8)
    ):
        raise ValueError(
            f"flash attention: no usable block for seq lengths "
            f"q={q.shape[1]}, kv={k.shape[1]} (auto-sized blocks "
            f"({block_q},{block_k}) < 8). Pad the sequence to a multiple "
            f"of 8 or pass explicit block_q/block_k."
        )
    return _flash_lse_core(q, k, v, causal, block_q, block_k, interpret)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention. q: [B, S, H, D]; k/v: [B, Sk, Hkv, D] (GQA-aware).
    Forward and backward both run as Pallas kernels; no [S, S] tensor is
    materialized in either direction."""
    out, _ = flash_attention_lse(q, k, v, causal, block_q, block_k, interpret)
    return out
