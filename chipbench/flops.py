"""Operations and bytes the algorithms need, from shapes: the numerators of
``mfu`` and of the roofline shares. Kept with the benchmark so that no PR
that claims a gain can change them.

``cfg`` is a configuration file's dict (the published keys)."""

from __future__ import annotations


def _dims(cfg):
    h = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nh
    return h, nh, nkv, d


def train_flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs per token of one training step (forward + backward = 3 x
    forward), matrix multiplications only, causal attention at half the full
    score cost; recomputed operations do not count. (Copied from
    ``bench.py::_model_flops_per_token``.)"""
    h, nh, nkv, d = _dims(cfg)
    qd, kvd = nh * d, nkv * d
    per_layer = (
        h * qd + 2 * h * kvd + qd * h  # wq, wk, wv, wo
        + h * cfg["num_local_experts"]  # router
        + cfg["num_experts_per_tok"] * 3 * h * cfg["intermediate_size"]
    )
    n_active = cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]
    attn_core = cfg["num_hidden_layers"] * 2 * nh * d * seq
    return 3.0 * (2.0 * n_active + attn_core)


def decode_step_bytes(cfg, experts_touched: int, kv_rows: int,
                      itemsize: int = 4) -> float:
    """Bytes one decode step must read from HBM: the attention and router
    weights and the head, the experts at least one token was routed to, and
    the cached key/value rows in use (``kv_rows`` summed over the active
    slots). Activations and the embedding rows are negligible beside them."""
    h, nh, nkv, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    attn = layers * (h * nh * d * 2 + h * nkv * d * 2)
    router = layers * h * cfg["num_local_experts"]
    experts = layers * experts_touched * 3 * h * cfg["intermediate_size"]
    head = h * cfg["vocab_size"]
    kv = layers * kv_rows * 2 * nkv * d
    return float(itemsize * (attn + router + experts + head + kv))
