"""Plain reference for the ``brumby`` block (Brumby-14B-Base's
``config.json``): every layer a POWER RETENTION of degree 2 and a dense
SwiGLU; final norm, an untied head. The retention in its QUADRATIC form —
no state, no chunks, no feature map: queries, keys and values as a
grouped-query attention's (each query and key head RMS-normed with a
learned gain of ``head_dim`` numbers, then rotated, all of a head, theta
``rope_theta``; query head j reads KV head ``j // (H / Hkv)``), one gate a
KV head and position ``log g = log sigmoid(h W_g + b_g)``, and

    a(i, j) = exp(sum of log g over j+1 .. i) (q_i . k_j / sqrt(D))^2, j <= i
    y_i     = sum_j a(i, j) v_j / (sum_j a(i, j) + 1e-6)

a loop over (query head, block of queries), each against every key of the
head's KV head: the decay as a difference of the cumulative sums of ``log
g``, the ``[block, T]`` matrix row-normalised. float32 at
``default_matmul_precision("highest")`` unless asked for less.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed by the same draws the program's
``init_params`` makes (checked against the program at a tiny size in
``chipbench/tests``), kept in bfloat16 as published, and upcast where they
are used; every norm's gain is 1 + 0.1 x a seeded normal and the gate's
bias uniform in [4, 7], float32. Norm, rotation, SwiGLU and the gap of a
served token are the sibling reference's (``mimo_v2_flash.py``): the same
equations. Queries go a block at a time so that a 17,024-token request's
matrix is ``[608, 17024]`` a head.

``cfg`` is the configuration file's own dict (the published keys, with
``num_hidden_layers`` as reduced).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.mimo_v2_flash import (  # noqa: F401 — re-exported
    _rms_norm, _rope, _swiglu, served_token_gaps,
)

# the program's draws (uccl_tpu/models/moe_inference.py::init_params):
# ``embed`` and ``head`` from a twelve-way split of the key, every leaf of
# the group ``dense_retention_blocks`` from the key with the group's number
# (512 + 64) and its own folded in
_SPLIT = {"embed": 0, "head": 9}
_FOLD = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "w_gate": 29, "w_up": 30,
         "w_down": 31, "wg": 33, "q_norm": 34, "k_norm": 35, "ln1": 38,
         "ln2": 39, "bg": 43}
GROUP = "dense_retention_blocks"
_GROUP_FOLD = 512 + 64
GAIN_SCALE = 0.1
GATE_BIAS_RANGE = (4.0, 7.0)
NORMALISER_EPS = 1e-6
QUERY_BLOCK = 608  # a request is padded to a multiple of it


def _head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] \
        // cfg["num_attention_heads"]


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The seeded weights as the program draws them: normal draws in float32
    scaled 0.02 (embedding) and 1/sqrt(fan-in) elsewhere, stored in
    ``dtype``; every norm gain of a layer 1 + 0.1 x a normal and the gate's
    bias uniform in [4, 7], float32; the final norm ones."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, d = cfg["num_key_value_heads"], _head_dim(cfg)
    n, f = cfg["num_hidden_layers"], cfg["intermediate_size"]
    k12 = jax.random.split(key, 12)

    def fold(name):
        return jax.random.fold_in(key, _GROUP_FOLD + _FOLD[name])

    mats = {"wq": ((h, nh * d), h), "wk": ((h, hkv * d), h),
            "wv": ((h, hkv * d), h), "wo": ((nh * d, h), nh * d),
            "wg": ((h, hkv), h), "w_gate": ((h, f), h), "w_up": ((h, f), h),
            "w_down": ((f, h), f)}
    g = {name: (jax.random.normal(fold(name), (n,) + shape, jnp.float32)
                * (1.0 / math.sqrt(fan))).astype(dtype)
         for name, (shape, fan) in mats.items()}
    for name, width in (("ln1", h), ("ln2", h), ("q_norm", d),
                        ("k_norm", d)):
        g[name] = 1.0 + GAIN_SCALE * jax.random.normal(
            fold(name), (n, width), jnp.float32)
    g["bg"] = jax.random.uniform(fold("bg"), (n, hkv), jnp.float32,
                                 *GATE_BIAS_RANGE)
    return {
        GROUP: g,
        "embed": (jax.random.normal(k12[_SPLIT["embed"]],
                                    (cfg["vocab_size"], h), jnp.float32)
                  * 0.02).astype(dtype),
        "head": (jax.random.normal(k12[_SPLIT["head"]],
                                   (h, cfg["vocab_size"]), jnp.float32)
                 * (1.0 / math.sqrt(h))).astype(dtype),
        "final_norm": jnp.ones((h,), jnp.float32),
    }


def _retention(x, lp, cfg):
    """The operator half of a layer on one sequence [T, H] -> [T, H]: a
    loop over (query head, block of queries), each block's ``[block, T]``
    matrix of decayed squared scores against every key of the head's KV
    head, divided by its row sums."""
    nh, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = _head_dim(cfg), cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    t = x.shape[0]
    blk = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)
    hn = _rms_norm(x, lp["ln1"], eps)
    q = _rms_norm((hn @ lp["wq"].astype(hn.dtype)).reshape(t, nh, d),
                  lp["q_norm"], eps)
    k = _rms_norm((hn @ lp["wk"].astype(hn.dtype)).reshape(t, hkv, d),
                  lp["k_norm"], eps)
    q, k = _rope(q, pos, theta, d), _rope(k, pos, theta, d)
    v = (hn @ lp["wv"].astype(hn.dtype)).reshape(t, hkv, d)
    log_g = jax.nn.log_sigmoid(hn @ lp["wg"].astype(hn.dtype)
                               + lp["bg"].astype(hn.dtype))
    since = jnp.cumsum(log_g, axis=0)  # [T, Hkv]
    scale = 1.0 / math.sqrt(d)

    def one(jb):
        j, b = jb
        g = j // (nh // hkv)
        qb = lax.dynamic_slice_in_dim(
            lax.dynamic_index_in_dim(q, j, 1, keepdims=False), b * blk, blk)
        kg = lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        cg = lax.dynamic_index_in_dim(since, g, 1, keepdims=False)
        s = (qb @ kg.T).astype(jnp.float32) * scale
        qpos = b * blk + jnp.arange(blk)
        seen = pos[None, :] <= qpos[:, None]
        cq = lax.dynamic_slice_in_dim(cg, b * blk, blk).astype(jnp.float32)
        decay = jnp.exp(jnp.where(
            seen, cq[:, None] - cg.astype(jnp.float32)[None, :], -jnp.inf))
        a = decay * s * s
        num = a.astype(vg.dtype) @ vg  # [blk, d]
        den = jnp.sum(a, axis=-1, keepdims=True) + NORMALISER_EPS
        return (num.astype(jnp.float32) / den).astype(x.dtype)

    jj, bb = jnp.meshgrid(jnp.arange(nh), jnp.arange(t // blk),
                          indexing="ij")
    out = lax.map(one, (jj.reshape(-1), bb.reshape(-1)))  # [nh*nb, blk, d]
    y = out.reshape(nh, t, d).transpose(1, 0, 2).reshape(t, nh * d)
    return x + y @ lp["wo"].astype(y.dtype)


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, rows, cfg_key, dtype, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        for i in range(cfg["num_hidden_layers"]):
            lp = {leaf: a[i] for leaf, a in weights[GROUP].items()}
            x = _retention(x, lp, cfg)
            h2 = _rms_norm(x, lp["ln2"], cfg["rms_norm_eps"])
            x = x + _swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = _rms_norm(jnp.take(x, rows, axis=0), weights["final_norm"],
                      cfg["rms_norm_eps"])
        return x.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rms_norm_eps", "rope_theta", "num_hidden_layers",
         "intermediate_size", "vocab_size")


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    return tuple((k, cfg[k]) for k in _KEYS if k in cfg)


def forward_logits(weights, tokens, cfg, rows=None, dtype=jnp.float32,
                   precision="highest"):
    """Logits [R, V] (float32) at positions ``rows`` [R] (all positions if
    None) of one token sequence [T]: the published forward. A caller names
    the rows it compares. ``dtype`` below float32 is for the lower-precision
    control."""
    tokens = jnp.asarray(tokens, jnp.int32)
    rows = jnp.arange(tokens.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)
    return _forward(weights, tokens, rows, cfg_key(cfg), dtype, precision)
