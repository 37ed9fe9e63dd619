"""Operations and bytes the ``brumby`` block (Brumby-14B-Base: every layer a
power retention of degree 2 and a dense SwiGLU) needs, from shapes: the
numerators of the roofline and peak shares of the cells that run it. Kept
with the benchmark, beside ``flops.py``, so that no PR that claims a gain
can change them.

A slot's state is counted at the MODEL's size: the ``D (D + 1) / 2`` = 8,256
distinct degree-2 monomials of a head of 128, ``S [Hkv, 8256, D]`` and ``z
[Hkv, 8256]`` in float32 a layer — what the recurrence must read and write
at least once a position. The program holds 9,216 features a head (every
pair of 16-wide blocks as a full outer product, so each diagonal block's
off-diagonal products twice): those 11.6 % more bytes and operations are
the program's and do not count here.

``cfg`` is a configuration file's dict (the published keys; every head and
the whole vocabulary are held)."""

from __future__ import annotations

WEIGHT_BYTES = 2  # the matrices are stored in bfloat16, as published
STATE_BYTES = 4  # the state is float32


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] \
        // cfg["num_attention_heads"]


def state_features(cfg) -> int:
    """Distinct degree-2 monomials of one head: D (D + 1) / 2."""
    d = head_dim(cfg)
    return d * (d + 1) // 2


def retention_params(cfg) -> int:
    """One layer's retention matrices: q and o (hidden x heads x head_dim
    each), k and v (hidden x KV heads x head_dim each) and the gate (hidden
    x KV heads)."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    return 2 * h * cfg["num_attention_heads"] * d \
        + 2 * h * cfg["num_key_value_heads"] * d \
        + h * cfg["num_key_value_heads"]


def layer_params(cfg) -> int:
    """One layer's matrices: the retention's and the SwiGLU's three."""
    return retention_params(cfg) \
        + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def state_bytes_per_slot(cfg) -> int:
    """Bytes of one slot's state in ONE layer: S [Hkv, F, D] and z [Hkv, F],
    float32."""
    return STATE_BYTES * cfg["num_key_value_heads"] * state_features(cfg) \
        * (head_dim(cfg) + 1)


def state_step_bytes(cfg, slots: int) -> float:
    """Bytes of state a step over ``slots`` rows must move: every layer's
    state of each row read once and written once."""
    return float(2 * slots * cfg["num_hidden_layers"]
                 * state_bytes_per_slot(cfg))


def retention_decode_bytes(cfg, slots: int) -> float:
    """Bytes the retention operators of one decode step must move: every
    layer's bfloat16 q, k, v, g, o matrices once, and the state of its
    ``slots`` decoding rows read and written (the rows in use, not the rows
    the program touches)."""
    return float(WEIGHT_BYTES * cfg["num_hidden_layers"]
                 * retention_params(cfg)) + state_step_bytes(cfg, slots)


def decode_step_bytes(cfg, slots: int) -> float:
    """Bytes one decode step must move: the bfloat16 layers and head, and
    the state of its ``slots`` decoding rows read and written. No row by
    position: the same at a slot's 16,000th position as at its 100th."""
    return float(WEIGHT_BYTES * (cfg["num_hidden_layers"] * layer_params(cfg)
                                 + head_params(cfg))) \
        + state_step_bytes(cfg, slots)


def retention_token_flops(cfg, chunk: int) -> float:
    """FLOPs of the retention's own terms for ONE token of a call of
    ``chunk`` positions a row, all layers (2 a multiply-add): phi(q) against
    S and z for every query head, phi(k) v^T and phi(k) into the state for
    every KV head, and the call's own ``[chunk, chunk]`` scores and values.
    The projections are matrix parameters and count with them."""
    d, f = head_dim(cfg), state_features(cfg)
    nh, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return float(cfg["num_hidden_layers"] * (
        2 * nh * f * (d + 1) + 2 * hkv * f * (d + 1)
        + 2 * 2 * nh * d * chunk))


def retention_prefill_flops(cfg, tokens: int, chunk: int) -> float:
    """FLOPs of the retention operators for ``tokens`` real tokens of a
    prefill program: their projections and their own terms."""
    return float(tokens) * (
        2.0 * cfg["num_hidden_layers"] * retention_params(cfg)
        + retention_token_flops(cfg, chunk))


def retention_prefill_bytes(cfg, rows: int) -> float:
    """Bytes the retention operators of one prefill program must move:
    their matrices once and one round trip of the state of its ``rows``
    prefilling rows."""
    return retention_decode_bytes(cfg, rows)


def prefill_flops(cfg, tokens: int, rows: int, chunk: int) -> float:
    """FLOPs one prefill program needs for ``tokens`` real tokens in
    ``rows`` rows: 2 x the layers' matrix parameters a token, the retention
    terms, and the head for the ONE position a row whose logits are read."""
    return float(tokens) * (
        2.0 * cfg["num_hidden_layers"] * layer_params(cfg)
        + retention_token_flops(cfg, chunk)) \
        + 2.0 * rows * head_params(cfg)
