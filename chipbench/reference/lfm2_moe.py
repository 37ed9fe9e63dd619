"""Plain reference for the ``lfm2_moe`` block (LFM2-24B-A2B's
``config.json``): layers of two kinds by ``layer_types`` — ``conv`` (a gated
short convolution: ``[b | c | u] = h W_in``, ``y = b * u``, a causal
depthwise filter of ``conv_L_cache`` taps over ``y`` along the positions,
``(c * z) W_out``; no scores, no softmax) and ``full_attention`` (causal
grouped-query attention, each query and key head RMS-normed with a learned
gain of ``head_dim`` numbers before the rotation, all of a head rotated,
theta ``rope_parameters.rope_theta``) — one norm a branch; ``num_dense_
layers`` leading dense SwiGLU layers, then layers of ``num_experts``
sigmoid-scored experts chosen with a bias and weighted without it,
renormalised, times ``routed_scaling_factor``, no shared expert; final norm,
the head TIED to the embedding. Straight ``jax.numpy``: no cache, no ring,
no grouped heads, no dispatch, no capacity — the filter as a loop over the
taps on the sequence padded with zeros on the left, attention a loop over
(head, block of queries) against every key with a ``[block, T]`` causal
mask, and a loop over the experts, every token through each with its gate
weight (zero off its top-k). float32 at
``default_matmul_precision("highest")`` unless asked for less.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed by the same draws the program's
``init_params`` makes (checked against the program at a tiny size in
``chipbench/tests``), kept in bfloat16 as published, and upcast where they
are used; every norm's gain is 1 + 0.1 x a seeded normal, float32. Norm,
rotation, SwiGLU, gate (the renormalising sum + 1e-20, as the program's) and
the gap of a served token are the sibling reference's
(``mimo_v2_flash.py``): the same equations. Queries go a block at a time so
that a 4,864-token request's scores are ``[block, 4864]`` a head.

``cfg`` is the configuration file's own dict (the published keys, with
``num_hidden_layers`` and ``num_dense_layers`` as reduced; ``layer_types``
is read up to ``num_hidden_layers``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.mimo_v2_flash import (  # noqa: F401 — re-exported
    _rms_norm, _rope, _swiglu, gate, served_token_gaps,
)

# the program's draws (uccl_tpu/models/moe_inference.py::init_params): the
# old leaves of the group ``blocks`` from a twelve-way split of the key,
# every other leaf from the key with its group's and its own number folded in
_SPLIT = {"embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "router": 5,
          "we_gate": 6, "we_up": 7, "we_down": 8}
_FOLD = {"router_bias": 28, "w_gate": 29, "w_up": 30, "w_down": 31,
         "q_norm": 34, "k_norm": 35, "ln1": 38, "ln2": 39, "w_in": 40,
         "w_conv": 41, "w_out": 42}
_GROUP = {"blocks": 0, "dense_blocks": 64, "conv_blocks": 256,
          "dense_conv_blocks": 320}
_KIND = {"conv": "conv", "full_attention": "full"}
BIAS_SCALE = 0.01
GAIN_SCALE = 0.1
QUERY_BLOCK = 608  # a request is padded to a multiple of it


def layers(cfg):
    """[(group, index in group, kind, dense)] by layer: stacked groups by
    (FFN kind, operator kind), as the program stacks them."""
    seen, out = {}, []
    for i, name in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        kind, dense = _KIND[name], i < cfg["num_dense_layers"]
        group = ("dense_" if dense else "") \
            + ("conv_" if kind == "conv" else "") + "blocks"
        out.append((group, seen.get(group, 0), kind, dense))
        seen[group] = seen.get(group, 0) + 1
    return out


def _head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] \
        // cfg["num_attention_heads"]


def init_weights(key, cfg, dtype=jnp.bfloat16):
    """The seeded weights as the program draws them: normal draws in float32
    scaled 0.02 (embedding), 1/sqrt(taps) (the filter) and 1/sqrt(fan-in)
    elsewhere, stored in ``dtype``; every norm gain of a layer 1 + 0.1 x a
    normal and the gate bias a normal of scale 0.01, float32; the final norm
    ones; no head (it is the embedding)."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    hkv, d = cfg["num_key_value_heads"], _head_dim(cfg)
    e, taps = cfg["num_experts"], cfg["conv_L_cache"]
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
        fold = _GROUP[group]
        gains = {"ln1": h, "ln2": h}
        if kind == "conv":
            mats = {"w_in": ((h, 3 * h), h), "w_out": ((h, h), h),
                    "w_conv": ((h, taps), taps)}
        else:
            mats = {"wq": ((h, nh * d), h), "wk": ((h, hkv * d), h),
                    "wv": ((h, hkv * d), h), "wo": ((nh * d, h), nh * d)}
            gains.update(q_norm=d, k_norm=d)
        if dense:
            mats.update({"w_gate": ((h, fd), h), "w_up": ((h, fd), h),
                         "w_down": ((fd, h), fd)})
        else:
            mats.update({"router": ((h, e), h), "we_gate": ((e, h, f), h),
                         "we_up": ((e, h, f), h), "we_down": ((e, f, h), f)})
        g = {name: rnd(name, (n,) + shape, fan, fold)
             for name, (shape, fan) in mats.items()}
        for name, width in gains.items():
            g[name] = 1.0 + GAIN_SCALE * jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD[name]), (n, width),
                jnp.float32)
        if not dense:
            g["router_bias"] = jax.random.normal(
                jax.random.fold_in(key, fold + _FOLD["router_bias"]),
                (n, e), jnp.float32) * BIAS_SCALE
        out[group] = g
    out["embed"] = (jax.random.normal(
        k12[_SPLIT["embed"]], (cfg["vocab_size"], h), jnp.float32)
        * 0.02).astype(dtype)
    out["final_norm"] = jnp.ones((h,), jnp.float32)
    return out


def _conv(x, lp, cfg):
    """The operator half of a conv layer on one sequence [T, H] -> [T, H]:
    the filter as a loop over the taps, on ``y`` with ``taps - 1`` rows of
    zeros before position 0."""
    t, h = x.shape
    taps = cfg["conv_L_cache"]
    hn = _rms_norm(x, lp["ln1"], cfg["norm_eps"])
    bcu = hn @ lp["w_in"].astype(hn.dtype)
    b, c, u = bcu[:, :h], bcu[:, h:2 * h], bcu[:, 2 * h:]
    y = jnp.concatenate([jnp.zeros((taps - 1, h), x.dtype), b * u])
    w = lp["w_conv"].astype(x.dtype)
    z = jnp.zeros_like(x)
    for j in range(taps):
        z = z + w[:, j] * y[j:j + t]
    out = c * z
    return x + out @ lp["w_out"].astype(out.dtype)


def _attention(x, lp, cfg):
    """The operator half of an attention layer on one sequence [T, H] ->
    [T, H]: a loop over (query head, block of queries), each against every
    key of the head's KV head under the causal mask."""
    nh, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = _head_dim(cfg), cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
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
        p = jax.nn.softmax(
            jnp.where(pos[None, :] <= qpos[:, None], s, -1e30), axis=-1)
        return p.astype(vg.dtype) @ vg  # [blk, d]

    jj, bb = jnp.meshgrid(jnp.arange(nh), jnp.arange(t // blk),
                          indexing="ij")
    out = lax.map(one, (jj.reshape(-1), bb.reshape(-1)))  # [nh*nb, blk, d]
    attn = out.reshape(nh, t, d).transpose(1, 0, 2).reshape(t, nh * d)
    return x + attn @ lp["wo"].astype(attn.dtype)


_EXPERTS = ("we_gate", "we_up", "we_down")


def _moe(h2, lp, cfg, layer):
    """The expert layer's sum on rows [T, H]: every row through every
    expert, one at a time, weighted by its gate (zero off the top-k). The
    expert leaves come STACKED over the group's layers, ``[n, E, ...]``, and
    an expert's matrices are read where they lie (``[layer, e]``)."""
    w = gate(h2, lp["router"], lp["router_bias"],
             cfg["num_experts_per_tok"],
             cfg.get("routed_scaling_factor") or 1.0)

    def one(acc, ew):
        e, w_e = ew
        wg, wu, wd = (lp[leaf][layer, e] for leaf in _EXPERTS)
        # elementwise weighting: the gate is never rounded to a product's
        # operand precision
        return acc + _swiglu(h2, wg, wu, wd) \
            * w_e[:, None].astype(acc.dtype), None

    routed, _ = lax.scan(one, jnp.zeros_like(h2),
                         (jnp.arange(cfg["num_experts"]), w.T))
    return routed


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, rows, cfg_key, dtype, precision):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_key}
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        for group, i, kind, dense in layers(cfg):
            lp = {leaf: a if leaf in _EXPERTS else a[i]
                  for leaf, a in weights[group].items()}
            x = _conv(x, lp, cfg) if kind == "conv" \
                else _attention(x, lp, cfg)
            h2 = _rms_norm(x, lp["ln2"], cfg["norm_eps"])
            x = x + (_swiglu(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
                     if dense else _moe(h2, lp, cfg, i))
        x = _rms_norm(jnp.take(x, rows, axis=0), weights["final_norm"],
                      cfg["norm_eps"])
        # the head is the embedding
        return jnp.einsum("rh,vh->rv", x.astype(jnp.float32),
                          weights["embed"].astype(jnp.float32))


_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "norm_eps", "conv_L_cache", "num_hidden_layers",
         "num_dense_layers", "layer_types", "num_experts",
         "num_experts_per_tok", "routed_scaling_factor",
         "moe_intermediate_size", "intermediate_size", "vocab_size")


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    key = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                for k in _KEYS if k in cfg)
    return key + (("rope_parameters",
                   tuple(sorted(cfg["rope_parameters"].items()))),)


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
