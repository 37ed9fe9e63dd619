"""Operations and bytes the ``glm4_moe_lite`` block needs, from shapes: the
numerators of the roofline and peak shares of the cells that run it. Kept
with the benchmark, beside ``flops.py``, so that no PR that claims a gain
can change them.

``cfg`` is a configuration file's dict (the published keys)."""

from __future__ import annotations

WEIGHT_BYTES = 2  # the matrices are stored in bfloat16, as published
CACHE_BYTES = 4  # the latent cache is float32


def _layers(cfg):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def latent_row(cfg) -> int:
    """Numbers one cached position of one layer holds: ``[c_kv | k_rope]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg) -> int:
    """One layer's attention matrices: q_a, q_b, kv_a, kv_b, o."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * rq + rq * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def expert_params(cfg) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def latent_cache_bytes(cfg, kv_rows: int) -> float:
    """Bytes of the cached latent rows in use, all layers: what attention
    over them must read at least once."""
    return float(CACHE_BYTES * cfg["num_hidden_layers"] * kv_rows
                 * latent_row(cfg))


def decode_step_bytes(cfg, experts_reached: float, kv_rows: int) -> float:
    """Bytes one decode step must read from HBM: the bfloat16 attention,
    router, shared-expert, dense-layer and head weights, the routed experts
    at least one decoding row reaches (``experts_reached`` a layer), and
    576 float32 numbers a cached row in use (``kv_rows`` summed over the
    active slots) in every layer."""
    h = cfg["hidden_size"]
    dense, moe = _layers(cfg)
    weights = (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + dense * 3 * h * cfg["intermediate_size"]
        + moe * (h * cfg["n_routed_experts"]
                 + cfg["n_shared_experts"] * expert_params(cfg)
                 + experts_reached * expert_params(cfg))
        + h * cfg["vocab_size"])
    return float(WEIGHT_BYTES * weights) + latent_cache_bytes(cfg, kv_rows)


def routed_expert_flops(cfg, tokens: int) -> float:
    """FLOPs of the routed experts for ``tokens`` rows, all expert layers:
    ``num_experts_per_tok`` experts a row, three matrices each, 2 FLOPs a
    multiply-add. The rows a capacity-padded queue adds do not count."""
    _, moe = _layers(cfg)
    return float(moe * tokens * cfg["num_experts_per_tok"]
                 * 2 * expert_params(cfg))
