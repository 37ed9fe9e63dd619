"""Operations and bytes the ``afmoe`` block (Trinity) needs, from shapes: the
numerators of the roofline and peak shares of the cells that run it. Kept
with the benchmark, beside ``flops.py``, so that no PR that claims a gain
can change them.

``cfg`` is a configuration file's dict (the published keys; its
``num_experts`` and ``vocab_size`` are this chip's share, ``layer_types`` is
read up to ``num_hidden_layers``)."""

from __future__ import annotations

WEIGHT_BYTES = 2  # the matrices are stored in bfloat16, as published
CACHE_BYTES = 4  # the cache is float32


def layer_counts(cfg) -> dict:
    """How many of the run layers are full / window attention and dense /
    expert FFN."""
    n = cfg["num_hidden_layers"]
    window = sum(1 for t in cfg["layer_types"][:n]
                 if t == "sliding_attention")
    dense = min(cfg["num_dense_layers"], n)
    return {"full": n - window, "window": window, "dense": dense,
            "moe": n - dense}


def kv_row(cfg) -> int:
    """Numbers one cached position of one layer holds, in either kind: the
    KV heads x (a key + a value of ``head_dim``)."""
    return cfg["num_key_value_heads"] * 2 * cfg["head_dim"]


def attention_params(cfg) -> int:
    """One layer's attention matrices: q, gate, o (hidden x heads x
    head_dim each) and k, v."""
    h = cfg["hidden_size"]
    return 3 * h * cfg["num_attention_heads"] * cfg["head_dim"] \
        + h * kv_row(cfg)


def expert_params(cfg) -> int:
    """One expert's three matrices (a routed one; the shared expert is
    ``num_shared_experts`` of them wide)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def full_cache_bytes(cfg, kv_rows: int) -> float:
    """Bytes of the full layers' cached rows in use (``kv_rows`` summed over
    the active slots): what their attention must read at least once."""
    return float(CACHE_BYTES * layer_counts(cfg)["full"] * kv_rows
                 * kv_row(cfg))


def window_cache_bytes(cfg, window_rows: int) -> float:
    """Bytes of the window layers' rows a step's queries can see:
    ``window_rows`` = ``min(length, window)`` summed over the active slots,
    of every window layer's ring."""
    return float(CACHE_BYTES * layer_counts(cfg)["window"] * window_rows
                 * kv_row(cfg))


def held_experts_reached(cfg, rows: int) -> float:
    """Expected number of the held experts at least one of ``rows`` decoding
    rows reaches, a layer: each row draws ``num_experts_per_tok`` of the
    ``router_experts`` the router scores."""
    routed = cfg.get("router_experts", cfg["num_experts"])
    k = cfg["num_experts_per_tok"]
    return cfg["num_experts"] * (1.0 - (1.0 - k / routed) ** rows)


def decode_step_bytes(cfg, slots: int, kv_rows: int,
                      window_rows: int) -> float:
    """Bytes one decode step must read from HBM: the bfloat16 attention
    (gate included), router, shared-expert, dense-layer and head-slice
    weights, the held experts at least one of the ``slots`` decoding rows
    reaches, the full layers' cached rows in use and ``min(length,
    window)`` rows of each window layer's ring."""
    h = cfg["hidden_size"]
    n = layer_counts(cfg)
    routed = cfg.get("router_experts", cfg["num_experts"])
    weights = (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + n["dense"] * 3 * h * cfg["intermediate_size"]
        + n["moe"] * (h * routed
                      + (cfg["num_shared_experts"]
                         + held_experts_reached(cfg, slots))
                      * expert_params(cfg))
        + h * cfg["vocab_size"])
    return (float(WEIGHT_BYTES * weights) + full_cache_bytes(cfg, kv_rows)
            + window_cache_bytes(cfg, window_rows))


def routed_expert_flops(cfg, tokens: int) -> float:
    """FLOPs of the (row, choice) pairs of ``tokens`` rows that land on the
    experts HELD here, all expert layers: ``num_experts_per_tok`` draws a
    row of which ``num_experts / router_experts`` are expected here, three
    matrices each, 2 FLOPs a multiply-add. The rows a capacity-padded queue
    adds do not count, nor does the shared expert (scope ``moe.shared``)."""
    routed = cfg.get("router_experts", cfg["num_experts"])
    return float(layer_counts(cfg)["moe"] * tokens
                 * cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
                 * 2 * expert_params(cfg))
