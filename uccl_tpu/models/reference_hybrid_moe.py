"""Plain reference forward for the serving stack's descriptions with LAYER
KINDS (``MoEServeConfig.layer_kinds``: the MiMo-V2-Flash block — window and
full grouped-query attention layers with their own KV head counts and
thetas, keys wider than values, a partial rotary factor, scaled values, a
learned sink in the window softmax; a leading dense layer, then sigmoid-bias
experts of which this member may hold a share — and the Trinity (``afmoe``)
block: one KV head count, rotary on the window layers only, each query and
key head RMS-normed, the heads' output gated, each branch's output normed
before the residual, the embedding scaled, a shared expert beside the held
share — and the LFM2 (``lfm2_moe``) block: gated short convolutions in
the "conv" layers between full attention layers with QK-norm, no shared
expert, the head tied to the embedding — and the Brumby (``brumby``) block:
every layer a power retention of degree 2, a dense SwiGLU, no expert
layer). What tier-1 holds the program to.

Straightforward ``jax.numpy`` in float32 at
``default_matmul_precision("highest")``: a loop over the query heads with a
``[T, T]`` banded mask and the sink as one more column, a loop over the
experts, no cache, no ring, no batching, no capacity (norm, SwiGLU and gate
are ``reference_latent_moe``'s). It shares no routing, attention, norm or
rotary code with the program (``models/inference.py``,
``ep/ops.py``): only the parameter tree ``init_params`` draws and the
description's fields. Given the same share: the expert leaves it is handed
are the ``experts_held`` experts from ``first_expert``, the sum runs over
those alone, and ``embed`` / ``head`` are the vocabulary's slice.

    h    = RMSNorm(x, ln1);  Hkv, theta by the layer's kind
    q_j  = (h Wq)_j [192];  k_g = (h Wk)_g [192];  v_g = a (h Wv)_g [128]
    q_j, k_g: the leading rotary_dim numbers rotated (split-half), theta
    s_j(t,u) = q_j(t).k_{j // (H/Hkv)}(u) / sqrt(192)
    full:    P_j(t,.) = softmax over u <= t
    window:  P_j(t,u) = exp(s_j(t,u)) / (exp(sink_j) + sum_u' exp(s_j(t,u')))
             over t - window < u <= t  (the sink's own column is dropped)
    x    = x + concat_j(P_j v_{j // (H/Hkv)}) Wo
    dense layers:   x = x + W_down(silu(h2 W_gate) * (h2 W_up))
    expert layers:  s = sigmoid(h2 W_r) [E];  chosen = top-k of (s + b)
                    w = scale * s[chosen] / (sum s[chosen] + 1e-20)
                    x = x + sum over chosen j that are HELD of w_j E_j(h2)

and, where the description says so (the Trinity block):

    x0   = sqrt(H) E[token]                                   (embed_scale)
    q_j <- RMSNorm(q_j, q_norm [D]);  k_g <- RMSNorm(k_g, k_norm [D]), then
           rotated in window layers and NOT in full ones  (qk_norm, unrotated)
    o_j  = P_j v_g * sigmoid((h Wg)_j)                          (attn_gate)
    x    = x + RMSNorm(concat_j(o_j) Wo, ln1_post)             (post_norms)
    x    = x + RMSNorm(F(h2), ln2_post),  F the dense SwiGLU or
           E_shared(h2) + sum over chosen held j of w_j E_j(h2)

and in a "conv" layer (the LFM2 block), in place of the attention half:

    [b | c | u] = h W_in  (three H-wide parts);   y(t) = b(t) * u(t)
    z(t) = sum_{j < taps} w[:, j] * y(t - (taps - 1) + j),  y(t < 0) = 0
    x    = x + (c * z) W_out                                   (conv_taps)

and in a "retention" layer (the Brumby block: power retention of degree 2,
every layer of that model), in place of the attention half, the QUADRATIC
form — the whole ``[T, T]`` matrix, no state, no chunks, no feature map:

    q_j, k_g as above (RMS-normed, then rotated);  v_g = (h Wv)_g
    log g_g(t) = log sigmoid((h Wg)_g + bg_g)              one a KV head
    G_g(t, u)  = exp(sum of log g_g over u+1 .. t),  u <= t
    a_j(t, u)  = G_g(t, u) (q_j(t).k_g(u) / sqrt(D))^2,    g = j // (H/Hkv)
    y_j(t)     = sum_u a_j(t, u) v_g(u) / (sum_u a_j(t, u) + 1e-6)
    x          = x + concat_j(y_j) Wo

and where the head is tied (``tie_head``):  logits = RMSNorm(x) E^T
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# the other plain reference's norm, SwiGLU and sigmoid-bias gate: the same
# equations, and as free of the program's code
from uccl_tpu.models.reference_latent_moe import (
    _f32, _norm, _swiglu, gate_weights,
)


def _rotate(x, pos, theta, rot):
    """Split-half rotary embedding of the leading ``rot`` numbers of x
    [T, D] at positions [T]; the rest untouched."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    a, b = x[:, :half], x[:, half:rot]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[:, rot:]], axis=-1)


def attention(x, lp, cfg, kind: str):
    """One layer's attention half on one sequence [T, H], head by head."""
    t = x.shape[0]
    nh, d = cfg.n_heads, cfg.head_dim
    dv = cfg.v_head_dim or d
    window = kind == "window"
    hkv = cfg.window_kv_heads if window else cfg.n_kv_heads
    theta = cfg.window_rope_theta if window else cfg.rope_theta
    rot = cfg.rotary_dim or d
    pos = jnp.arange(t)
    h = _norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(t, nh, d)
    k = (h @ lp["wk"]).reshape(t, hkv, d)
    v = (h @ lp["wv"]).reshape(t, hkv, dv) * cfg.value_scale
    seen = pos[None, :] <= pos[:, None]
    if window:
        seen = seen & (pos[None, :] > pos[:, None] - cfg.window)

    def placed(y, gain):
        """One head's queries or keys [T, D]: normed where the description
        norms them, then rotated where this layer's kind rotates."""
        if cfg.qk_norm:
            y = _norm(y, lp[gain], cfg.norm_eps)
        return y if kind in cfg.unrotated else _rotate(y, pos, theta, rot)

    heads = []
    for j in range(nh):
        g = j // (nh // hkv)
        s = placed(q[:, j], "q_norm") @ placed(k[:, g], "k_norm").T \
            / math.sqrt(d)
        s = jnp.where(seen, s, -jnp.inf)
        if kind in cfg.sink:
            s = jnp.concatenate(
                [s, jnp.full((t, 1), lp["sink"][j])], axis=-1)
        p = jax.nn.softmax(s, axis=-1)[:, :t]
        o = p @ v[:, g]
        if cfg.attn_gate:
            o = o * jax.nn.sigmoid(h @ lp["wg"][:, j * dv:(j + 1) * dv])
        heads.append(o)
    out = jnp.concatenate(heads, axis=-1) @ lp["wo"]
    if cfg.post_norms:
        out = _norm(out, lp["ln1_post"], cfg.norm_eps)
    return x + out


def short_conv(x, lp, cfg):
    """One conv layer's operator half on one sequence [T, H]: the filter as
    an explicit loop over the taps on ``y`` padded with ``taps - 1`` rows of
    zeros on the left."""
    t = x.shape[0]
    taps = cfg.conv_taps
    h = _norm(x, lp["ln1"], cfg.norm_eps)
    b, c, u = jnp.split(h @ lp["w_in"], 3, axis=-1)
    y = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), b * u])
    z = jnp.zeros_like(x)
    for j in range(taps):
        z = z + lp["w_conv"][:, j] * y[j:j + t]
    return x + (c * z) @ lp["w_out"]


def power_retention(x, lp, cfg):
    """One retention layer's operator half on one sequence [T, H], head by
    head, in the quadratic form: the decay as a difference of cumulative
    sums of ``log g``, the scores squared, each row divided by its sum."""
    t = x.shape[0]
    nh, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(t)
    h = _norm(x, lp["ln1"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(t, nh, d)
    k = (h @ lp["wk"]).reshape(t, hkv, d)
    v = (h @ lp["wv"]).reshape(t, hkv, d)
    since = jnp.cumsum(jax.nn.log_sigmoid(h @ lp["wg"] + lp["bg"]), axis=0)
    seen = pos[None, :] <= pos[:, None]
    heads = []
    for j in range(nh):
        g = j // (nh // hkv)
        qj = _rotate(_norm(q[:, j], lp["q_norm"], cfg.norm_eps), pos,
                     cfg.rope_theta, d)
        kg = _rotate(_norm(k[:, g], lp["k_norm"], cfg.norm_eps), pos,
                     cfg.rope_theta, d)
        decay = jnp.where(seen, jnp.exp(jnp.where(
            seen, since[:, None, g] - since[None, :, g], 0.0)), 0.0)
        a = decay * (qj @ kg.T / math.sqrt(d)) ** 2
        heads.append(a @ v[:, g] / (jnp.sum(a, axis=-1, keepdims=True)
                                    + 1e-6))
    return x + jnp.concatenate(heads, axis=-1) @ lp["wo"]


def expert_layer_sum(h2, lp, cfg, first: int = None, held: int = None):
    """The routed experts' weighted sum for rows ``h2`` [T, H], over the
    experts ``[first, first + held)`` whose leaves ``lp`` carries (the
    description's own share by default)."""
    first = cfg.first_expert if first is None else first
    held = cfg.n_held if held is None else held
    w = gate_weights(h2, lp["router"], lp["router_bias"], cfg.moe_topk,
                     cfg.routed_scale)
    out = jnp.zeros_like(h2)
    for j in range(held):
        out = out + w[:, first + j, None] * _swiglu(
            h2, lp["we_gate"][j], lp["we_up"][j], lp["we_down"][j])
    return out


def forward_logits(params, tokens, cfg):
    """Logits [T, V] (float32) of one token sequence [T]: the description's
    forward, every layer of its kind."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        x = p["embed"][jnp.asarray(tokens)] * cfg.embed_scale
        for i, (group, j) in enumerate(cfg.param_groups()):
            lp = jax.tree.map(lambda a: a[j], p[group])
            kind = cfg.layer_kinds[i]
            x = short_conv(x, lp, cfg) if kind == "conv" \
                else power_retention(x, lp, cfg) if kind == "retention" \
                else attention(x, lp, cfg, kind)
            h2 = _norm(x, lp["ln2"], cfg.norm_eps)
            if "router" in lp:
                out = expert_layer_sum(h2, lp, cfg)
                if cfg.shared_ffn:  # every member computes it: once a token
                    out = out + _swiglu(h2, lp["ws_gate"], lp["ws_up"],
                                        lp["ws_down"])
            else:
                out = _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
            if cfg.post_norms:
                out = _norm(out, lp["ln2_post"], cfg.norm_eps)
            x = x + out
        head = p["embed"].T if cfg.tie_head else p["head"]
        return _norm(x, p["final_norm"], cfg.norm_eps) @ head
