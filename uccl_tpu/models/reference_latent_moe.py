"""Plain reference forward for the serving stack's latent-attention /
dense-then-MoE descriptions (``MoEServeConfig`` with ``attn="mla"``: the
DeepSeek-V3 / GLM-4.7-Flash block). What tier-1 holds the program to.

Straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``: the EXPANDED attention (every
position's compressed row multiplied out into per-head keys and values, one
rotary key shared by the heads), a loop over the experts with masks, no
cache, no batching, no capacity. It shares no routing, attention, norm or
rotary code with the program (``models/inference.py``, ``ep/ops.py``): only
the parameter tree ``init_params`` draws and the description's fields.

    h      = RMSNorm(x, ln1)
    c_q    = RMSNorm(h W_qa, q_a_norm);  q_i = (c_q W_qb)_i = [q_nope_i | q_rope_i]
    [c|r]  = h W_kva;  c_kv = RMSNorm(c, kv_a_norm);  k_r = RoPE(r)
    [k_nope_i | v_i] = (c_kv W_kvb)_i
    score_i(t,s) = (q_nope_i(t).k_nope_i(s) + RoPE(q_rope_i)(t).k_r(s)) / sqrt(d_nope + d_rope)
    x      = x + concat_i(softmax_s(score_i) v_i) W_o
    dense layers:   x = x + W_down(silu(h2 W_gate) * (h2 W_up))
    expert layers:  s = sigmoid(h2 W_r); chosen = top-k of (s + b)
                    w = scale * s[chosen] / (sum s[chosen] + 1e-20)
                    x = x + sum_j w_j E_j(h2) + E_shared(h2)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, pos, theta):
    """Split-half rotary embedding of x [T, ..., D] at positions [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (pos.astype(jnp.float32)[:, None] * inv).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def gate_weights(h2, router, bias, topk: int, scale: float):
    """Dense [T, E] combine weights of the sigmoid-bias gate: the bias
    chooses, it does not weigh."""
    s = jax.nn.sigmoid(h2 @ router)
    order = jnp.argsort(-(s + bias), axis=-1)[:, :topk]  # not lax.top_k
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None],
                                  order].set(1.0)
    w = s * chosen
    return scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def attention(x, lp, cfg):
    t = x.shape[0]
    nh, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = jnp.arange(t)
    h = _norm(x, lp["ln1"], cfg.norm_eps)
    q = (_norm(h @ lp["wq_a"], lp["q_a_norm"], cfg.norm_eps)
         @ lp["wq_b"]).reshape(t, nh, dn + dr)
    ckr = h @ lp["wkv_a"]
    c_kv = _norm(ckr[:, :r], lp["kv_a_norm"], cfg.norm_eps)
    k_r = _rotate(ckr[:, r:], pos, cfg.rope_theta)
    kv = (c_kv @ lp["wkv_b"]).reshape(t, nh, dn + dv)
    q_r = _rotate(q[..., dn:], pos, cfg.rope_theta)
    causal = pos[None, :] <= pos[:, None]
    outs = []
    for i in range(nh):
        s = (q[:, i, :dn] @ kv[:, i, :dn].T + q_r[:, i] @ k_r.T) \
            / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(p @ kv[:, i, dn:])
    return x + jnp.concatenate(outs, axis=-1) @ lp["wo"]


def experts(x, lp, cfg):
    h2 = _norm(x, lp["ln2"], cfg.norm_eps)
    w = gate_weights(h2, lp["router"], lp["router_bias"], cfg.moe_topk,
                     cfg.routed_scale)
    out = _swiglu(h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for e in range(cfg.moe_experts):
        mask = w[:, e:e + 1]
        out = out + mask * _swiglu(h2, lp["we_gate"][e], lp["we_up"][e],
                                   lp["we_down"][e])
    return x + out


def forward_logits(params, tokens, cfg):
    """Logits [T, V] of one token sequence [T] under the global (un-placed)
    parameter tree of ``moe_inference.init_params``."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        x = p["embed"][jnp.asarray(tokens)]
        for i in range(cfg.first_k_dense):
            lp = jax.tree.map(lambda a: a[i], p["dense_blocks"])
            x = attention(x, lp, cfg)
            x = x + _swiglu(_norm(x, lp["ln2"], cfg.norm_eps),
                            lp["w_gate"], lp["w_up"], lp["w_down"])
        for i in range(cfg.n_moe_layers):
            lp = jax.tree.map(lambda a: a[i], p["blocks"])
            x = experts(attention(x, lp, cfg), lp, cfg)
        return _norm(x, p["final_norm"], cfg.norm_eps) @ p["head"]
