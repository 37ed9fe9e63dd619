"""Plain reference for the ``mimo_v2_flash`` block (MiMo-V2-Flash's
``config.json``): RMSNorm; grouped-query attention whose layers are of two
kinds by ``hybrid_layer_pattern`` — FULL (causal softmax, ``num_key_value_
heads`` KV heads, ``rope_theta``) and WINDOW (position p sees [p - 127, p],
``swa_num_key_value_heads`` KV heads, ``swa_rope_theta``, and one more
softmax column holding the head's learned ``attention_sink_bias``, its own
probability dropped) — with keys 192 wide and values 128, the first
``int(partial_rotary_factor x 192)`` numbers of each head rotated, the
values scaled by ``attention_value_scale``; a leading dense SwiGLU layer,
then layers of sigmoid-scored experts chosen with a bias and weighted
without it, renormalised, no shared expert; final norm, untied head.
Straight ``jax.numpy``: no cache, no ring, no grouped heads, no dispatch, no
capacity — a loop over (head, block of queries) with a ``[block, T]`` banded
mask, and a loop over the experts HELD HERE, every token through each with
its gate weight (zero off its top-k). float32 at
``default_matmul_precision("highest")`` unless asked for less.

The share: the configuration's file gives ``n_routed_experts`` as the
experts this chip holds (``first_expert`` on) of the ``router_experts`` the
router scores, and ``vocab_size`` as its slice. The reference is given the
same share: the gate runs over all ``router_experts``, the sum over the
held ones alone, the logits over the slice.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed by the same draws the program's
``init_params`` makes (checked against the program at a tiny size in
``chipbench/tests``), kept in bfloat16 as published, and upcast where they
are used. Queries go a block at a time so that a 12,800-token request's
scores are ``[block, 12800]`` a head and not ``[12800, 12800]``.

``cfg`` is the configuration file's own dict (the published keys, with
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` as reduced;
the two per-layer lists are read up to ``num_hidden_layers``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# the program's draws (uccl_tpu/models/moe_inference.py::init_params): the
# old leaves of the group ``blocks`` from a twelve-way split of the key,
# every other leaf from the key with its group's and its own number folded in
_SPLIT = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "router": 5,
          "we_gate": 6, "we_up": 7, "we_down": 8, "head": 9}
_FOLD = {"router_bias": 28, "w_gate": 29, "w_up": 30, "w_down": 31,
         "sink": 32}
_GROUP = {"blocks": 0, "dense_blocks": 64, "window_blocks": 128,
          "dense_window_blocks": 192}
BIAS_SCALE = 0.01
SINK_SCALE = 1.0
QUERY_BLOCK = 1600  # a request is padded to a multiple of it


def layers(cfg):
    """[(group, index in group, kind, dense)] by layer: stacked groups by
    (FFN kind, attention kind), as the program stacks them."""
    n = cfg["num_hidden_layers"]
    seen, out = {}, []
    for window, moe in zip(cfg["hybrid_layer_pattern"][:n],
                           cfg["moe_layer_freq"][:n]):
        group = ("" if moe else "dense_") + ("window_" if window else "") \
            + "blocks"
        out.append((group, seen.get(group, 0),
                    "window" if window else "full", not moe))
        seen[group] = seen.get(group, 0) + 1
    return out


def _kind(cfg, kind):
    """(KV heads, theta, has a sink) of a layer kind."""
    if kind == "window":
        return (cfg["swa_num_key_value_heads"], float(cfg["swa_rope_theta"]),
                bool(cfg["add_swa_attention_sink_bias"]))
    return (cfg["num_key_value_heads"], float(cfg["rope_theta"]),
            bool(cfg["add_full_attention_sink_bias"]))


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The seeded weights as the program draws them: normal draws in float32
    scaled 0.02 (embedding) and 1/sqrt(fan-in) elsewhere, stored in
    ``dtype``; norms ones, the gate bias a normal of scale 0.01 and a window
    layer's per-head sink a normal of scale 1.0, all float32."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    d, dv = cfg["head_dim"], cfg["v_head_dim"]
    e, held = cfg.get("router_experts", cfg["n_routed_experts"]), \
        cfg["n_routed_experts"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    k12 = jax.random.split(key, 12)

    def rnd(name, shape, fan, fold):
        kk = k12[_SPLIT[name]] if name in _SPLIT and not fold else \
            jax.random.fold_in(key, fold + (_FOLD.get(name) or _SPLIT[name]))
        return (jax.random.normal(kk, shape, jnp.float32)
                * (1.0 / math.sqrt(fan))).astype(dtype)

    sizes = {}
    for group, _, kind, dense in layers(cfg):
        sizes[group] = (sizes.get(group, (0,))[0] + 1, kind, dense)
    out = {}
    for group, (n, kind, dense) in sizes.items():
        hkv, _, sink = _kind(cfg, kind)
        fold = _GROUP[group]
        mats = {"wq": ((h, nh * d), h), "wk": ((h, hkv * d), h),
                "wv": ((h, hkv * dv), h), "wo": ((nh * dv, h), nh * dv)}
        if dense:
            mats.update({"w_gate": ((h, fd), h), "w_up": ((h, fd), h),
                         "w_down": ((fd, h), fd)})
        else:
            mats.update({"router": ((h, e), h), "we_gate": ((held, h, f), h),
                         "we_up": ((held, h, f), h),
                         "we_down": ((held, f, h), f)})
        g = {name: rnd(name, (n,) + shape, fan, fold)
             for name, (shape, fan) in mats.items()}
        g["ln1"] = jnp.ones((n, h), jnp.float32)
        g["ln2"] = jnp.ones((n, h), jnp.float32)
        if not dense:
            g["router_bias"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD["router_bias"]),
                (n, e), jnp.float32) * BIAS_SCALE
        if sink:
            g["sink"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD["sink"]), (n, nh),
                jnp.float32) * SINK_SCALE
        out[group] = g
    v = cfg["vocab_size"]
    out["embed"] = (jax.random.normal(k12[_SPLIT["embed"]], (v, h),
                                      jnp.float32) * 0.02).astype(dtype)
    out["final_norm"] = jnp.ones((h,), jnp.float32)
    out["head"] = rnd("head", (h, v), h, 0)
    return out


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta, rot):
    """Split-half rotary embedding of the leading ``rot`` numbers of the
    last axis of x [T, H, D]; the rest untouched."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = (positions.astype(jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rot].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


def gate(h2, router, bias, topk, scale):
    """sigmoid scores in float32 over ALL the routed experts; the ``topk``
    with the largest ``score + bias`` are chosen; their weights are the
    scores alone, renormalised over the chosen and scaled. Returns the dense
    [T, E] combine weights: zero off the chosen experts."""
    # the router is float32 by the model's definition, whatever precision
    # the other products are asked to run at
    s = jax.nn.sigmoid(jnp.dot(h2.astype(jnp.float32),
                               router.astype(jnp.float32),
                               precision="highest"))
    _, idx = lax.top_k(s + bias, topk)
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32),
                     axis=1)
    w = s * chosen
    return scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)


def _swiglu(x, w_gate, w_up, w_down):
    hid = jax.nn.silu(x @ w_gate.astype(x.dtype)) * (x @ w_up.astype(x.dtype))
    return hid @ w_down.astype(x.dtype)


def _attention(x, lp, cfg, kind):
    """The attention half of a layer on one sequence [T, H] -> [T, H]: a
    loop over (query head, block of queries), each against every key of the
    head's KV head under the kind's mask."""
    nh, d, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    hkv, theta, has_sink = _kind(cfg, kind)
    window = cfg["sliding_window"]
    rot = int(cfg["partial_rotary_factor"] * d) // 2 * 2
    t = x.shape[0]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)
    hn = _rms_norm(x, lp["ln1"], cfg["layernorm_epsilon"])
    q = _rope((hn @ lp["wq"].astype(hn.dtype)).reshape(t, nh, d), pos, theta,
              rot)
    k = _rope((hn @ lp["wk"].astype(hn.dtype)).reshape(t, hkv, d), pos, theta,
              rot)
    v = (hn @ lp["wv"].astype(hn.dtype)).reshape(t, hkv, dv)
    v = v * jnp.asarray(cfg["attention_value_scale"], v.dtype)
    scale = 1.0 / math.sqrt(d)

    def one(jb):
        j, b = jb
        g = j // (nh // hkv)
        qb = lax.dynamic_slice_in_dim(
            lax.dynamic_index_in_dim(q, j, 1, keepdims=False), b * blk, blk)
        kg = lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        s = (qb @ kg.T).astype(jnp.float32) * scale
        qpos = b * blk + jnp.arange(blk)
        seen = pos[None, :] <= qpos[:, None]
        if kind == "window":
            seen = seen & (pos[None, :] > qpos[:, None] - window)
        s = jnp.where(seen, s, -1e30)
        if has_sink:
            s = jnp.concatenate(
                [s, jnp.full((blk, 1), lp["sink"][j], jnp.float32)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)[:, :t]
        return p.astype(vg.dtype) @ vg  # [blk, dv]

    jj, bb = jnp.meshgrid(jnp.arange(nh), jnp.arange(t // blk),
                          indexing="ij")
    out = lax.map(one, (jj.reshape(-1), bb.reshape(-1)))  # [nh*nb, blk, dv]
    attn = out.reshape(nh, t, dv).transpose(1, 0, 2).reshape(t, nh * dv)
    return x + attn @ lp["wo"].astype(attn.dtype)


def _moe(x, lp, cfg):
    """The expert half of an expert layer on rows [T, H]: every row through
    every expert HELD HERE, one at a time, weighted by its gate over all the
    routed experts (zero off the top-k)."""
    h2 = _rms_norm(x, lp["ln2"], cfg["layernorm_epsilon"])
    w = gate(h2, lp["router"], lp["router_bias"],
             cfg["num_experts_per_tok"],
             cfg.get("routed_scaling_factor") or 1.0)
    first, held = cfg.get("first_expert", 0), cfg["n_routed_experts"]

    def one(acc, ew):
        wg, wu, wd, w_e = ew
        # elementwise weighting: the gate is never rounded to a product's
        # operand precision
        return acc + _swiglu(h2, wg, wu, wd) \
            * w_e[:, None].astype(acc.dtype), None

    routed, _ = lax.scan(
        one, jnp.zeros_like(h2),
        (lp["we_gate"], lp["we_up"], lp["we_down"],
         w[:, first:first + held].T))
    return x + routed


def _dense(x, lp, cfg):
    h2 = _rms_norm(x, lp["ln2"], cfg["layernorm_epsilon"])
    return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, rows, cfg_key, dtype, precision):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        for group, i, kind, dense in layers(cfg):
            lp = jax.tree.map(lambda a: a[i], weights[group])
            x = _attention(x, lp, cfg, kind)
            x = _dense(x, lp, cfg) if dense else _moe(x, lp, cfg)
        x = _rms_norm(jnp.take(x, rows, axis=0), weights["final_norm"],
                      cfg["layernorm_epsilon"])
        return x.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "swa_num_key_value_heads", "head_dim", "v_head_dim",
         "partial_rotary_factor", "rope_theta", "swa_rope_theta",
         "sliding_window", "attention_value_scale",
         "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
         "layernorm_epsilon", "num_hidden_layers", "hybrid_layer_pattern",
         "moe_layer_freq", "n_routed_experts", "router_experts",
         "first_expert", "num_experts_per_tok", "routed_scaling_factor",
         "moe_intermediate_size", "intermediate_size", "vocab_size")


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _KEYS if k in cfg)


def forward_logits(weights, tokens, cfg, rows=None, dtype=jnp.float32,
                   precision="highest"):
    """Logits [R, V] (float32) at positions ``rows`` [R] (all positions if
    None) of one token sequence [T]: the published forward over this chip's
    share. A caller names the rows it compares. ``dtype`` below float32 is
    for the lower-precision control."""
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = jnp.arange(tokens.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)
    return _forward(weights, tokens, rows, cfg_key(cfg), dtype, precision)


@jax.jit
def served_token_gaps(logits, following):
    """For each row, how far the reference logit of the token that FOLLOWED
    it (``following`` [R]) lies below the reference's best at that row: 0
    where the served token is the reference's own choice."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, following[:, None], axis=-1)[:, 0]
    return best - got
