"""Plain reference for the Mixtral block (arXiv:2401.04088; the layer
equations of ``transformers``' ``MixtralForCausalLM``): RMSNorm, rotary GQA
attention, softmax -> top-k -> renormalise routing over SwiGLU experts,
final norm, untied head. Straight ``jax.numpy``: no cache, no batching, no
capacity, no kernels; float32 at ``default_matmul_precision("highest")``
unless a control asks for less.

It imports nothing of the program and takes nothing the program made: the
weights are drawn here from the seed, by the same draws the program's
``init_params`` makes (same key splits, shapes and scales — checked against
the program at a tiny size in ``chipbench/tests``), so a run compares two
computations of one model.

``cfg`` is the configuration file's own dict (``hidden_size``,
``num_attention_heads`` ... as published, with ``num_hidden_layers`` as
reduced).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

def _dims(cfg):
    h = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return h, nh, nkv, d, cfg["intermediate_size"], cfg["num_local_experts"]


def init_weights(key, cfg, n_splits):
    """The seeded weights (float32), as the program's ``init_params`` draws
    them: ``n_splits`` keys from the one given (the serving stack splits
    twelve ways; the first ten are used), normal draws scaled 0.02
    (embedding) and 1/sqrt(fan-in) elsewhere."""
    h, nh, nkv, d, f, e = _dims(cfg)
    l, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    qd, kvd = nh * d, nkv * d
    k = jax.random.split(key, n_splits)
    s_in, s_f = 1.0 / math.sqrt(h), 1.0 / math.sqrt(f)

    def rnd(kk, shape, scale):
        return jax.random.normal(kk, shape, jnp.float32) * scale

    return {
        "embed": rnd(k[0], (v, h), 0.02),
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
            "we_down": rnd(k[8], (l, e, f, h), s_f),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd(k[9], (h, v), s_in),
    }


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Split-half rotary embedding; x [T, heads, D], positions [T]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def route(h2, router, topk):
    """softmax over all experts -> top-k -> renormalise (dropless, as
    Mixtral is). Returns the dense [T, E] combine weights: zero off the
    chosen experts."""
    gates = jax.nn.softmax(h2.astype(jnp.float32) @ router, axis=-1)
    vals, idx = lax.top_k(gates, topk)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, router.shape[-1], dtype=jnp.float32)
    return jnp.sum(onehot * vals[..., None], axis=1)


def _attention_rows(x, lp, cfg):
    """The attention half of a layer on one sequence [T, H] -> [T, H]."""
    h, nh, nkv, d, _, _ = _dims(cfg)
    t = x.shape[0]
    positions = jnp.arange(t)
    hn = _rms_norm(x, lp["ln1"], cfg["rms_norm_eps"])
    theta = cfg["rope_theta"]
    q = _rope((hn @ lp["wq"].astype(hn.dtype)).reshape(t, nh, d),
              positions, theta)
    k = _rope((hn @ lp["wk"].astype(hn.dtype)).reshape(t, nkv, d),
              positions, theta)
    v = (hn @ lp["wv"].astype(hn.dtype)).reshape(t, nkv, d)
    rep = nh // nkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    causal = positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v).reshape(t, nh * d)
    return x + attn @ lp["wo"].astype(attn.dtype)


def _expert_rows(x, w, lp, cfg):
    """The expert half of a layer on a block of rows [N, H] with their
    combine weights ``w`` [N, E] (any rows: no token sees another here)."""
    h2 = _rms_norm(x, lp["ln2"], cfg["rms_norm_eps"])
    hid = jax.nn.silu(jnp.einsum("nh,ehf->enf", h2, lp["we_gate"].astype(h2.dtype))) \
        * jnp.einsum("nh,ehf->enf", h2, lp["we_up"].astype(h2.dtype))
    y = jnp.einsum("enf,efh->enh", hid, lp["we_down"].astype(hid.dtype))
    # the weighted sum is elementwise, not a matrix product: the gates are
    # never rounded to a product's operand precision
    return x + jnp.sum(y * w.T.astype(y.dtype)[:, :, None], axis=0)


def _block(x, lp, cfg):
    """One decoder layer on one sequence: x [T, H] -> [T, H]. Every token
    goes through every expert, weighted (zero off its top-k): no dispatch,
    four times the routed arithmetic, and plain."""
    x = _attention_rows(x, lp, cfg)
    w = route(_rms_norm(x, lp["ln2"], cfg["rms_norm_eps"]),
              lp["router"], cfg["num_experts_per_tok"])
    return _expert_rows(x, w, lp, cfg)


@partial(jax.jit, static_argnames=("cfg_key", "dtype", "precision"))
def _forward(weights, tokens, cfg_key, dtype, precision):
    cfg = dict(cfg_key)
    with jax.default_matmul_precision(precision):
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dtype)
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree.map(lambda a: a[i], weights["blocks"])
            x = _block(x, lp, cfg)
        x = _rms_norm(x, weights["final_norm"], cfg["rms_norm_eps"])
        return x.astype(jnp.float32) @ weights["head"].astype(jnp.float32)


def cfg_key(cfg):
    """The configuration's numbers as a hashable static argument."""
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "num_local_experts",
            "num_experts_per_tok", "num_hidden_layers", "vocab_size",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


def forward_logits(weights, tokens, cfg, dtype=jnp.float32,
                   precision="highest"):
    """Logits [T, V] (float32) of one token sequence [T]: the published
    forward. ``dtype`` below float32 is for the lower-precision controls."""
    return _forward(weights, jnp.asarray(tokens, jnp.int32), cfg_key(cfg),
                    dtype, precision)


# -- lower-precision controls (never run by a benchmark run) ---------------

def quantize_weights(weights, kind):
    """Round-trip every matrix through ``kind`` ("bf16" | "fp8" | "int8"),
    scaled per output column by its largest magnitude for fp8/int8, and give
    it back in bfloat16: the weights a lower-precision server would hold."""
    def q(a):
        if a.ndim < 2:
            return a
        if kind == "bf16":
            return a.astype(jnp.bfloat16)
        amax = jnp.max(jnp.abs(a), axis=-2, keepdims=True) + 1e-30
        if kind == "fp8":
            scale = amax / 448.0  # float8_e4m3fn's largest finite
            r = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        elif kind == "int8":
            scale = amax / 127.0
            r = jnp.clip(jnp.rint(a / scale), -127, 127)
        else:
            raise ValueError(f"unknown control precision {kind!r}")
        return (r * scale).astype(jnp.bfloat16)

    return jax.tree.map(q, weights)


@jax.jit
def served_token_gaps(logits, following):
    """For each position of a sequence, how far the reference logit of the
    token that FOLLOWED it (``following`` [T], the served continuation
    shifted by one) lies below the reference's best at that position: 0
    where the served token is the reference's own choice. Whole padded
    sequences in, so one program serves every request; the caller keeps the
    positions that produced served tokens."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, following[:, None], axis=-1)[:, 0]
    return best - got
