"""Dense (Llama-family) transformer: second model family.

Same parallel machinery as the flagship MoE (pp/dp/cp/tp via one shard_map;
GPipe microbatching; vocab-parallel embedding + CE) with a dense SwiGLU MLP in
place of the expert layer — the model class the reference's Megatron/DDP
workloads train over the NCCL plugin (SURVEY.md §2.6 DP/TP/PP rows).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from uccl_tpu.models import flagship as _fs
from uccl_tpu.models.layers import rms_norm, rope, tp_cross_entropy
from uccl_tpu.ops.attention import attention_reference
from uccl_tpu.parallel.mesh import AXIS
from uccl_tpu.parallel.pipeline import gpipe_spmd


@dataclasses.dataclass(frozen=True)
class DenseConfig:
    vocab: int = 1024
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 32
    ffn: int = 768
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    n_microbatches: int = 1
    remat: str = "full"  # "full" | "dots" | "mlp" | "none" (flagship.
    # _remat_wrap; "mlp" is accepted but ≡ "dots" here — the dense FFN has
    # no MOE_CHECKPOINT_NAMES tags for the save-names half to match)
    seq_mode: str = "ring"
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    # flagship-compat fields consumed by the shared attention block
    @property
    def aux_loss_weight(self):
        return 0.0

    @property
    def z_loss_weight(self):
        return 0.0


def param_specs(cfg: DenseConfig) -> Dict[str, Any]:
    return {
        "embed": P(AXIS.TP, None),
        "blocks": {
            "ln1": P(AXIS.PP, None),
            "ln2": P(AXIS.PP, None),
            "wq": P(AXIS.PP, None, AXIS.TP),
            "wk": P(AXIS.PP, None, AXIS.TP),
            "wv": P(AXIS.PP, None, AXIS.TP),
            "wo": P(AXIS.PP, AXIS.TP, None),
            "w_gate": P(AXIS.PP, None, AXIS.TP),
            "w_up": P(AXIS.PP, None, AXIS.TP),
            "w_down": P(AXIS.PP, AXIS.TP, None),
        },
        "final_norm": P(None),
        "head": P(None, AXIS.TP),
    }


def init_params(key: jax.Array, cfg: DenseConfig) -> Dict[str, Any]:
    k = jax.random.split(key, 10)
    h, l, f = cfg.dim, cfg.n_layers, cfg.ffn
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    s_in, s_f = 1.0 / math.sqrt(h), 1.0 / math.sqrt(f)

    def rnd(kk, shape, scale):
        return jax.random.normal(kk, shape, jnp.float32) * scale

    return {
        "embed": rnd(k[0], (cfg.vocab, h), 0.02),
        "blocks": {
            "ln1": jnp.ones((l, h), jnp.float32),
            "ln2": jnp.ones((l, h), jnp.float32),
            "wq": rnd(k[1], (l, h, qd), s_in),
            "wk": rnd(k[2], (l, h, kvd), s_in),
            "wv": rnd(k[3], (l, h, kvd), s_in),
            "wo": rnd(k[4], (l, qd, h), 1.0 / math.sqrt(qd)),
            "w_gate": rnd(k[5], (l, h, f), s_in),
            "w_up": rnd(k[6], (l, h, f), s_in),
            "w_down": rnd(k[7], (l, f, h), s_f),
        },
        "final_norm": jnp.ones((h,), jnp.float32),
        "head": rnd(k[8], (h, cfg.vocab), s_in),
    }


def shard_params(params, mesh: Mesh, cfg: DenseConfig):
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        param_specs(cfg),
    )


def _layer(x, lp, cfg: DenseConfig):
    b, s_loc, h = x.shape
    attn_out = _fs._attention(rms_norm(x, lp["ln1"], cfg.norm_eps), lp, cfg)
    x = x + lax.psum(attn_out, AXIS.TP)
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    act = jax.nn.silu(h2 @ lp["w_gate"].astype(h2.dtype)) * (
        h2 @ lp["w_up"].astype(h2.dtype)
    )
    mlp = act @ lp["w_down"].astype(act.dtype)
    x = x + lax.psum(mlp, AXIS.TP)
    return x, jnp.zeros((), jnp.float32)


def _per_shard_logits(params, tokens, cfg: DenseConfig):
    b_loc, s_loc = tokens.shape
    m = cfg.n_microbatches
    if b_loc % m:
        raise ValueError(f"local batch {b_loc} not divisible by {m} microbatches")
    x = _fs._embed(tokens, params["embed"], cfg).astype(cfg.dtype)
    xmb = x.reshape(m, b_loc // m, s_loc, cfg.dim)
    layer_ckpt = _fs._remat_wrap(partial(_layer, cfg=cfg), cfg.remat)

    def stage_fn(xm):
        def body(carry, lp):
            y, aux = layer_ckpt(carry, lp)
            return y, aux

        y, auxs = lax.scan(body, xm, params["blocks"])
        return y, jnp.sum(auxs)

    out, _ = gpipe_spmd(stage_fn, xmb, AXIS.PP)
    x = out.reshape(b_loc, s_loc, cfg.dim)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["head"]


def forward(params, tokens, cfg: DenseConfig, mesh: Mesh):
    def f(p, t):
        return _per_shard_logits(p, t, cfg)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(param_specs(cfg), P(AXIS.DP, AXIS.CP)),
        out_specs=P(AXIS.DP, AXIS.CP, AXIS.TP),
        check_vma=False,
    )(params, tokens)


def loss_fn(params, tokens, targets, cfg: DenseConfig, mesh: Mesh):
    def f(p, t, y):
        logits = _per_shard_logits(p, t, cfg)
        v_loc = logits.shape[-1]
        off = lax.axis_index(AXIS.TP) * v_loc
        per_token = tp_cross_entropy(
            logits.reshape(-1, v_loc), y.reshape(-1), off, AXIS.TP
        )
        return lax.pmean(jnp.mean(per_token), AXIS.EP)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(param_specs(cfg), P(AXIS.DP, AXIS.CP), P(AXIS.DP, AXIS.CP)),
        out_specs=P(),
        check_vma=False,
    )(params, tokens, targets)


def make_train_step(cfg: DenseConfig, mesh: Mesh, learning_rate: float = 3e-4):
    import optax

    tx = optax.adamw(learning_rate, weight_decay=0.01)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, targets, cfg, mesh)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return train_step, tx.init


def reference_forward(params, tokens, cfg: DenseConfig):
    """Unsharded oracle."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["blocks"])
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        d = cfg.head_dim
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, d)
        kk = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, d)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, d)
        pos = jnp.arange(s)
        q, kk = rope(q, pos, cfg.rope_theta), rope(kk, pos, cfg.rope_theta)
        attn = attention_reference(q, kk, v, causal=True)
        x = x + attn.reshape(b, s, -1) @ lp["wo"]
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        act = jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])
        x = x + act @ lp["w_down"]
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x.astype(jnp.float32) @ params["head"]
