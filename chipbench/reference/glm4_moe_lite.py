"""Plain reference for the ``glm4_moe_lite`` block (GLM-4.7-Flash's
``config.json``; the layer equations of ``transformers``'
``Glm4MoeLiteForCausalLM``, which are DeepSeek-V3's): RMSNorm, multi-head
latent attention in its EXPANDED form (every position's compressed row is
multiplied out into per-head keys and values; one rotary key shared by all
heads), a leading dense SwiGLU layer, then layers of sigmoid-scored experts
chosen with a bias and weighted without it, renormalised and scaled, beside
one shared expert; final norm, untied head. Straight ``jax.numpy``: no
cache, no absorbed products, no dispatch, no capacity — a loop over heads
and a loop over experts, every token through every expert with its weight
(zero off its top-k). float32 at ``default_matmul_precision("highest")``
unless asked for less.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed by the same draws the program's
``init_params`` makes (checked against the program at a tiny size in
``chipbench/tests``), kept in bfloat16 as published, and upcast where they
are used — a layer, an expert at a time — so 7.8 GB of weights and a
6,528-token forward fit one chip.

``cfg`` is the configuration file's own dict (the published keys, with
``num_hidden_layers`` as reduced).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# the program's draws (uccl_tpu/models/moe_inference.py::init_params): the
# old leaves from a twelve-way split of the key, every other leaf from the
# key with its own number folded in, the dense group's with 64 more
_SPLIT = {"embed": 0, "wo": 4, "router": 5, "we_gate": 6, "we_up": 7,
          "we_down": 8, "head": 9}
_FOLD = {"wq_a": 21, "wq_b": 22, "wkv_a": 23, "wkv_b": 24, "ws_gate": 25,
         "ws_up": 26, "ws_down": 27, "router_bias": 28, "w_gate": 29,
         "w_up": 30, "w_down": 31}
_DENSE_GROUP = 64
BIAS_SCALE = 0.01


def _dims(cfg):
    return dict(
        h=cfg["hidden_size"], nh=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], r=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], e=cfg["n_routed_experts"],
        f=cfg["moe_intermediate_size"], fd=cfg["intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        k_dense=cfg["first_k_dense_replace"], l=cfg["num_hidden_layers"],
        v=cfg["vocab_size"])


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The seeded weights as the program draws them: normal draws in
    float32 scaled 0.02 (embedding) and 1/sqrt(fan-in) elsewhere, stored in
    ``dtype``; norms ones and the gate bias a normal of scale 0.01, both
    float32. Layers in two stacked groups, ``dense_blocks`` then ``blocks``."""
    d = _dims(cfg)
    h, nh = d["h"], d["nh"]
    k12 = jax.random.split(key, 12)

    def rnd(name, shape, fan, group=0):
        kk = k12[_SPLIT[name]] if name in _SPLIT and not group else \
            jax.random.fold_in(key, group + (_FOLD.get(name) or _SPLIT[name]))
        return (jax.random.normal(kk, shape, jnp.float32)
                * (1.0 / math.sqrt(fan))).astype(dtype)

    def group(n, ffn, fold):
        mats = {
            "wq_a": ((h, d["rq"]), h),
            "wq_b": ((d["rq"], nh * (d["dn"] + d["dr"])), d["rq"]),
            "wkv_a": ((h, d["r"] + d["dr"]), h),
            "wkv_b": ((d["r"], nh * (d["dn"] + d["dv"])), d["r"]),
            "wo": ((nh * d["dv"], h), nh * d["dv"]),
            **ffn,
        }
        out = {name: rnd(name, (n,) + shape, fan, fold)
               for name, (shape, fan) in mats.items()}
        for name, width in (("ln1", h), ("ln2", h), ("q_a_norm", d["rq"]),
                            ("kv_a_norm", d["r"])):
            out[name] = jnp.ones((n, width), jnp.float32)
        return out

    e, f, fs, fd = d["e"], d["f"], d["fs"], d["fd"]
    n_moe = d["l"] - d["k_dense"]
    blocks = group(n_moe, {
        "router": ((h, e), h), "we_gate": ((e, h, f), h),
        "we_up": ((e, h, f), h), "we_down": ((e, f, h), f),
        "ws_gate": ((h, fs), h), "ws_up": ((h, fs), h),
        "ws_down": ((fs, h), fs)}, 0)
    blocks["router_bias"] = jax.random.normal(
        jax.random.fold_in(key, _FOLD["router_bias"]), (n_moe, e),
        jnp.float32) * BIAS_SCALE
    return {
        "embed": (jax.random.normal(k12[_SPLIT["embed"]], (d["v"], h),
                                    jnp.float32) * 0.02).astype(dtype),
        "dense_blocks": group(d["k_dense"], {
            "w_gate": ((h, fd), h), "w_up": ((h, fd), h),
            "w_down": ((fd, h), fd)}, _DENSE_GROUP),
        "blocks": blocks,
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd("head", (h, d["v"]), h),
    }


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Split-half rotary embedding over the last axis; x [T, ..., D]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gate(h2, router, bias, topk, scale):
    """sigmoid scores in float32; the ``topk`` experts with the largest
    ``score + bias`` are chosen; their weights are the scores alone,
    renormalised over the chosen and scaled. Returns the dense [T, E]
    combine weights: zero off the chosen experts."""
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


def _attention(x, lp, cfg):
    """The attention half of a layer on one sequence [T, H] -> [T, H],
    expanded: per-head keys and values from every position's compressed
    row, a loop over the heads."""
    d = _dims(cfg)
    nh, r, dn, dr, dv = d["nh"], d["r"], d["dn"], d["dr"], d["dv"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    t = x.shape[0]
    pos = jnp.arange(t)
    hn = _rms_norm(x, lp["ln1"], eps)
    cq = _rms_norm(hn @ lp["wq_a"].astype(hn.dtype), lp["q_a_norm"], eps)
    q = (cq @ lp["wq_b"].astype(cq.dtype)).reshape(t, nh, dn + dr)
    ckr = hn @ lp["wkv_a"].astype(hn.dtype)
    ckv = _rms_norm(ckr[:, :r], lp["kv_a_norm"], eps)
    k_rope = _rope(ckr[:, r:], pos, theta)  # [T, dr]: one head for all
    kv = (ckv @ lp["wkv_b"].astype(ckv.dtype)).reshape(t, nh, dn + dv)
    q_rope = _rope(q[..., dn:], pos, theta)
    causal = pos[None, :] <= pos[:, None]
    scale = 1.0 / math.sqrt(dn + dr)

    def head(i):
        s = (q[:, i, :dn] @ kv[:, i, :dn].T + q_rope[:, i] @ k_rope.T)
        s = jnp.where(causal, s.astype(jnp.float32) * scale, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return p.astype(kv.dtype) @ kv[:, i, dn:]  # [T, dv]

    heads = lax.map(head, jnp.arange(nh))  # [nh, T, dv]
    attn = jnp.transpose(heads, (1, 0, 2)).reshape(t, nh * dv)
    return x + attn @ lp["wo"].astype(attn.dtype)


def _moe(x, lp, cfg):
    """The expert half of an expert layer on rows [T, H]: every row through
    every routed expert, one expert at a time, weighted by its gate (zero
    off the top-k); the shared expert once for every row."""
    h2 = _rms_norm(x, lp["ln2"], cfg["rms_norm_eps"])
    w = gate(h2, lp["router"], lp["router_bias"],
             cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])

    def one(acc, ew):
        wg, wu, wd, w_e = ew
        # elementwise weighting: the gate is never rounded to a product's
        # operand precision
        return acc + _swiglu(h2, wg, wu, wd) * w_e[:, None].astype(acc.dtype), None

    routed, _ = lax.scan(
        one, jnp.zeros_like(h2),
        (lp["we_gate"], lp["we_up"], lp["we_down"], w.T))
    return x + routed + _swiglu(h2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _dense(x, lp, cfg):
    h2 = _rms_norm(x, lp["ln2"], cfg["rms_norm_eps"])
    return x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, rows, cfg_key, dtype, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        for i in range(cfg["first_k_dense_replace"]):
            lp = jax.tree.map(lambda a: a[i], weights["dense_blocks"])
            x = _dense(_attention(x, lp, cfg), lp, cfg)
        for i in range(cfg["num_hidden_layers"]
                       - cfg["first_k_dense_replace"]):
            lp = jax.tree.map(lambda a: a[i], weights["blocks"])
            x = _moe(_attention(x, lp, cfg), lp, cfg)
        x = _rms_norm(jnp.take(x, rows, axis=0), weights["final_norm"],
                      cfg["rms_norm_eps"])
        return x.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    keep = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "n_routed_experts", "n_shared_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "intermediate_size", "first_k_dense_replace",
            "num_hidden_layers", "vocab_size", "rms_norm_eps", "rope_theta",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keep)


def forward_logits(weights, tokens, cfg, rows=None, dtype=jnp.float32,
                   precision="highest"):
    """Logits [R, V] (float32) at positions ``rows`` [R] (all positions if
    None) of one token sequence [T]: the published forward. With a
    154,880-wide vocabulary the logits of every position of a long request
    would be 4 GB, so a caller names the rows it compares. ``dtype`` below
    float32 is for the lower-precision control."""
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
