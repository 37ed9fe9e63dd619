"""Operations and bytes the ``mimo_v2_flash`` block needs, from shapes: the
numerators of the roofline shares of the cells that run it. Kept with the
benchmark, beside ``flops.py``, so that no PR that claims a gain can change
them.

``cfg`` is a configuration file's dict (the published keys; its
``n_routed_experts`` and ``vocab_size`` are this chip's share, its two
per-layer lists are read up to ``num_hidden_layers``)."""

from __future__ import annotations

WEIGHT_BYTES = 2  # the matrices are stored in bfloat16, as published
CACHE_BYTES = 4  # the cache is float32


def layer_counts(cfg) -> dict:
    """How many of the run layers are full / window attention and dense /
    expert FFN."""
    n = cfg["num_hidden_layers"]
    window = sum(1 for p in cfg["hybrid_layer_pattern"][:n] if p)
    moe = sum(1 for m in cfg["moe_layer_freq"][:n] if m)
    return {"full": n - window, "window": window, "dense": n - moe,
            "moe": moe}


def kv_row(cfg, kind: str) -> int:
    """Numbers one cached position of one layer of ``kind`` holds: the
    kind's KV heads x (192-wide key + 128-wide value)."""
    hkv = cfg["swa_num_key_value_heads"] if kind == "window" \
        else cfg["num_key_value_heads"]
    return hkv * (cfg["head_dim"] + cfg["v_head_dim"])


def attention_params(cfg, kind: str) -> int:
    """One layer's attention matrices: q, k, v, o."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    return (h * nh * cfg["head_dim"] + h * kv_row(cfg, kind)
            + nh * cfg["v_head_dim"] * h)


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def full_cache_bytes(cfg, kv_rows: int) -> float:
    """Bytes of the full layers' cached rows in use (``kv_rows`` summed over
    the active slots): what their attention must read at least once."""
    return float(CACHE_BYTES * layer_counts(cfg)["full"] * kv_rows
                 * kv_row(cfg, "full"))


def window_cache_bytes(cfg, slots: int, kv_rows: int) -> float:
    """Bytes of the window layers' rows a step's queries can see: ``min(
    length, window)`` rows of each active slot's ring — ``slots x window``,
    or all ``kv_rows`` where that is fewer."""
    rows = min(slots * cfg["sliding_window"], kv_rows)
    return float(CACHE_BYTES * layer_counts(cfg)["window"] * rows
                 * kv_row(cfg, "window"))


def held_experts_reached(cfg, rows: int) -> float:
    """Expected number of the held experts at least one of ``rows`` decoding
    rows reaches, a layer: each row draws ``num_experts_per_tok`` of the
    ``router_experts`` the router scores."""
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    k = cfg["num_experts_per_tok"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - k / routed) ** rows)


def decode_step_bytes(cfg, slots: int, kv_rows: int) -> float:
    """Bytes one decode step must read from HBM: the bfloat16 attention,
    router, dense-layer and head-slice weights, the held experts at least
    one decoding row reaches, the full layers' cached rows in use and
    ``min(length, window)`` rows of each window layer's ring."""
    h = cfg["hidden_size"]
    n = layer_counts(cfg)
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    weights = (
        n["full"] * attention_params(cfg, "full")
        + n["window"] * attention_params(cfg, "window")
        + n["dense"] * 3 * h * cfg["intermediate_size"]
        + n["moe"] * (h * routed
                      + held_experts_reached(cfg, slots) * expert_params(cfg))
        + h * cfg["vocab_size"])
    return (float(WEIGHT_BYTES * weights) + full_cache_bytes(cfg, kv_rows)
            + window_cache_bytes(cfg, slots, kv_rows))
