"""Operations and bytes the ``lfm2_moe`` block (LFM2-24B-A2B) needs, from
shapes: the numerators of the roofline and peak shares of the cells that run
it. Kept with the benchmark, beside ``flops.py``, so that no PR that claims
a gain can change them.

``cfg`` is a configuration file's dict (the published keys; ``layer_types``
is read up to ``num_hidden_layers``; every expert and the whole vocabulary
are held)."""

from __future__ import annotations

WEIGHT_BYTES = 2  # the matrices are stored in bfloat16, as published
CACHE_BYTES = 4  # the cache, rings included, is float32


def layer_counts(cfg) -> dict:
    """How many of the run layers are conv / full attention and dense /
    expert FFN."""
    n = cfg["num_hidden_layers"]
    conv = sum(1 for t in cfg["layer_types"][:n] if t == "conv")
    dense = min(cfg["num_dense_layers"], n)
    return {"conv": conv, "full": n - conv, "dense": dense, "moe": n - dense}


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] \
        // cfg["num_attention_heads"]


def kv_row(cfg) -> int:
    """Numbers one cached position of one attention layer holds: the KV
    heads x (a key + a value of ``head_dim``)."""
    return cfg["num_key_value_heads"] * 2 * head_dim(cfg)


def attention_params(cfg) -> int:
    """One attention layer's matrices: q and o (hidden x heads x head_dim
    each), k and v."""
    h = cfg["hidden_size"]
    return 2 * h * cfg["num_attention_heads"] * head_dim(cfg) \
        + h * kv_row(cfg)


def conv_params(cfg) -> int:
    """One conv layer's operator: the three gates' projection (hidden x 3
    hidden), the output projection and the filter's taps."""
    h = cfg["hidden_size"]
    return 3 * h * h + h * h + h * cfg["conv_L_cache"]


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def full_cache_bytes(cfg, kv_rows: int) -> float:
    """Bytes of the attention layers' cached rows in use (``kv_rows`` summed
    over the active slots): what their attention must read at least once."""
    return float(CACHE_BYTES * layer_counts(cfg)["full"] * kv_rows
                 * kv_row(cfg))


def conv_state_bytes(cfg, slots: int) -> float:
    """Bytes of ring rows a decode step moves for its ``slots`` decoding
    rows: ``conv_L_cache`` rows of ``hidden_size`` numbers a row and conv
    layer — the ``conv_L_cache - 1`` positions before it read, its own
    written."""
    return float(CACHE_BYTES * layer_counts(cfg)["conv"] * slots
                 * cfg["conv_L_cache"] * cfg["hidden_size"])


def conv_decode_bytes(cfg, slots: int) -> float:
    """Bytes the conv operators of one decode step must move: every conv
    layer's bfloat16 matrices once, and the ring rows of its decoding
    rows."""
    return float(WEIGHT_BYTES * layer_counts(cfg)["conv"] * conv_params(cfg)) \
        + conv_state_bytes(cfg, slots)


def decode_step_bytes(cfg, slots: int, kv_rows: int,
                      experts_read: float) -> float:
    """Bytes one decode step must read from HBM: the bfloat16 conv,
    attention, router, dense-layer and embedding-as-head weights, the
    experts its decoding rows REACHED (``experts_read``: the program's own
    count, summed over the expert layers), the attention layers' cached
    rows in use and the conv layers' ring rows of the ``slots`` decoding
    rows."""
    h = cfg["hidden_size"]
    n = layer_counts(cfg)
    weights = (
        n["conv"] * conv_params(cfg) + n["full"] * attention_params(cfg)
        + n["dense"] * 3 * h * cfg["intermediate_size"]
        + n["moe"] * h * cfg["num_experts"]
        + experts_read * expert_params(cfg)
        + h * cfg["vocab_size"])
    return (float(WEIGHT_BYTES * weights) + full_cache_bytes(cfg, kv_rows)
            + conv_state_bytes(cfg, slots))


def routed_expert_flops(cfg, tokens: int) -> float:
    """FLOPs of the routed experts for ``tokens`` rows, all expert layers:
    ``num_experts_per_tok`` experts a row, three matrices each, 2 FLOPs a
    multiply-add. The rows a capacity-padded queue adds do not count."""
    return float(layer_counts(cfg)["moe"] * tokens
                 * cfg["num_experts_per_tok"] * 2 * expert_params(cfg))
