"""Benchmark: flagship MoE training-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": ...}
On anything but a TPU of a known ``device_kind`` it exits non-zero and
prints no metric.

The headline metric is end-to-end training tokens/sec of the flagship MoE
transformer (sorted/ragged expert dispatch + flash attention code paths).
``vs_baseline`` compares against the *vendor stack*: the same model lowered
through XLA's stock paths — dense GShard-style one-hot einsum dispatch and
plain XLA attention — mirroring the reference's "UCCL vs NCCL, same app"
framing (README.md:29). ``mfu`` is model-FLOPs utilization against the
device's peak bf16 matmul throughput (the metric culture of
ep/bench/test_low_latency.py:438-464: report the number, not vibes).
"""

from __future__ import annotations

import json
import os
import sys
import time
from statistics import median as _median

import jax
import jax.numpy as jnp

# Peak dense-matmul FLOP/s (bf16) of ONE chip, keyed by the exact
# ``device_kind`` JAX reports, each with the source of the figure. A kind
# that is not listed is an error, never a default: an MFU against a guessed
# peak is not a measurement.
_PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197e12,
}


def _require_tpu():
    """(platform, device_kind, peak FLOP/s) of the chip, or exit non-zero.
    This benchmark measures a TPU; on anything else it refuses — a CPU
    number must never appear under a device metric's name."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"[bench] needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}); refusing to measure"
        )
    if dev.device_kind not in _PEAK_FLOPS:
        sys.exit(
            f"[bench] no peak FLOP/s on record for device_kind "
            f"{dev.device_kind!r}; add it to _PEAK_FLOPS with its source"
        )
    return dev.platform, dev.device_kind, _PEAK_FLOPS[dev.device_kind]



_BASE_VOCAB = 16384  # full-size vocab; token sampling must match _build's cfg


def _build(cfg_kw=None):
    from uccl_tpu.models.flagship import (
        FlagshipConfig,
        init_params,
        make_train_step,
        shard_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    base = dict(
        vocab=_BASE_VOCAB,
        dim=1024,
        n_layers=4,
        n_heads=16,
        n_kv_heads=4,
        head_dim=64,
        moe_experts=8,
        moe_topk=2,
        moe_ffn=2816,
        capacity_factor=1.25,
        n_microbatches=1,
        dtype=jnp.bfloat16,
        aux_loss_weight=0.01,
        z_loss_weight=1e-3,
    )
    base.update(cfg_kw or {})  # caller overrides (impls, remat)
    cfg = FlagshipConfig(**base)
    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    params = shard_params(init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
    train_step, init_opt = make_train_step(cfg, mesh)
    opt_state = init_opt(params)
    return cfg, mesh, params, train_step, opt_state


def _model_flops_per_token(cfg, seq: int) -> float:
    """Analytic model FLOPs per token for one training step (fwd + bwd = 3x
    fwd), matmuls only, causal attention at half the full score cost. This is
    the standard MFU numerator: rematerialization recompute does NOT count."""
    h, hd = cfg.dim, cfg.head_dim
    qd = cfg.n_heads * hd
    kvd = cfg.n_kv_heads * hd
    per_layer_params = (
        h * qd  # wq
        + 2 * h * kvd  # wk, wv
        + qd * h  # wo
        + h * cfg.moe_experts  # router
        + cfg.moe_topk * 3 * h * cfg.moe_ffn  # active experts (SwiGLU)
    )
    n_active = cfg.n_layers * per_layer_params + h * cfg.vocab  # + unembed
    attn_core = cfg.n_layers * 2 * cfg.n_heads * hd * seq  # causal qk^T + att@v
    fwd = 2.0 * n_active + attn_core
    return 3.0 * fwd


class _Harness:
    """One config variant held resident so samples can be interleaved with
    another variant's (A-B-A-B), so slow drift hits both sides alike.

    All iterations of a sample run inside ONE jitted fori_loop dispatch, so
    per-dispatch overhead stays out of the step time; the sample ends on a
    host read of the loss, which waits for the device.
    """

    def __init__(self, cfg_kw, tokens, targets):
        from jax import lax

        self.cfg, mesh, self._params, step, self._opt = _build(cfg_kw)
        step = jax.jit(step)

        def run(params, opt_state, n):
            def body(_, state):
                p, o, _m = state
                return step(p, o, tokens, targets)

            init = step(params, opt_state, tokens, targets)
            return lax.fori_loop(0, n - 1, body, init)

        # n traced -> one compile serves warmup and timing. params/opt_state
        # are DONATED: XLA aliases them into the loop-carried outputs, so the
        # step never pays an input copy of the largest buffers (each call
        # rebinds self._params/_opt to the returned state, keeping the
        # donated references dead).
        self._run = jax.jit(run, donate_argnums=(0, 1))

    def _call(self, n):
        self._params, self._opt, m = self._run(self._params, self._opt, n)
        return float(m["loss"])  # host read: waits for the device

    def warmup(self):
        self._call(2)  # compile + warm
        # The first call returns the state with XLA's canonicalized output
        # shardings, which can differ from the inputs' NamedShardings
        # (observed on 1-device meshes: named specs come back replicated) —
        # so the NEXT call recompiles for the new argument shardings.
        # Without this throwaway call the timed call would be mostly XLA
        # compile. After it, shardings are at their fixed point and every
        # later call is a pure cache hit.
        self._call(1)

    def sample(self, iters):
        """Median-able single observation: seconds per step over `iters`."""
        t0 = time.perf_counter()
        self._call(iters)
        return (time.perf_counter() - t0) / iters


def _interleaved_dts(ours, base, rounds, iters):
    """A-B-A-B sample schedule; returns (ours_dts, base_dts) lists."""
    ours_dts, base_dts = [], []
    for _ in range(rounds):
        ours_dts.append(ours.sample(iters))
        base_dts.append(base.sample(iters))
    return ours_dts, base_dts


def main():
    import numpy as np

    from uccl_tpu.utils.device import enable_compile_cache

    # Knobs are validated BEFORE the device is touched: a typo must fail in
    # milliseconds. Fast-path MoE impl: "sort" (ragged layout,
    # capacity-padded GEMMs) or "ll" (packed grouped GEMMs via ragged_dot).
    moe_impl = os.environ.get("UCCL_TPU_BENCH_MOE", "sort")
    if moe_impl not in ("sort", "ll", "dense"):
        sys.exit(f"[bench] UCCL_TPU_BENCH_MOE={moe_impl!r}: want sort|ll|dense")
    # Remat schedule for BOTH the fast path and the baseline (identical
    # numerics across modes — tests/test_flagship.py::TestRematModes).
    remat = os.environ.get("UCCL_TPU_BENCH_REMAT", "full")
    if remat not in ("full", "dots", "mlp", "none"):
        sys.exit(
            f"[bench] UCCL_TPU_BENCH_REMAT={remat!r}: want full|dots|mlp|none"
        )
    attn_impl = os.environ.get("UCCL_TPU_BENCH_ATTN", "flash")
    if attn_impl not in ("flash", "xla"):
        sys.exit(f"[bench] UCCL_TPU_BENCH_ATTN={attn_impl!r}: want flash|xla")
    try:
        batch_env = int(os.environ.get("UCCL_TPU_BENCH_BATCH", "0"))
        seq_env = int(os.environ.get("UCCL_TPU_BENCH_SEQ", "0"))
        rounds = int(os.environ.get("UCCL_TPU_BENCH_ROUNDS", "9"))
        iters = int(os.environ.get("UCCL_TPU_BENCH_ITERS", "5"))
    except ValueError as e:
        sys.exit(f"[bench] bad UCCL_TPU_BENCH_{{BATCH,SEQ,ROUNDS,ITERS}}: {e}")
    if batch_env < 0 or seq_env < 0:
        sys.exit("[bench] UCCL_TPU_BENCH_BATCH/SEQ must not be negative")
    if rounds < 1 or iters < 1:
        sys.exit("[bench] UCCL_TPU_BENCH_ROUNDS/ITERS must be >= 1")

    platform, device_kind, peak = _require_tpu()
    enable_compile_cache()

    # The HBM ceiling moves with the remat mode's saved-activation
    # footprint (PERF.md, carried-forward table): "mlp" saves the expert
    # tensors, "none" saves everything.
    batch = batch_env or {"mlp": 16, "none": 8}.get(remat, 32)
    seq = seq_env or 1024
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, _BASE_VOCAB, (batch, seq)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, _BASE_VOCAB, (batch, seq)), jnp.int32)

    # Ours and the vendor baseline (stock XLA lowering of the same model:
    # dense GShard einsum dispatch, plain XLA attention; same shapes, same
    # optimizer) are held resident together so samples interleave. Anything
    # that fails here — a kernel that does not compile, a pair that does not
    # fit — is the benchmark's result; nothing is retried another way.
    ours = _Harness(
        {"attn_impl": attn_impl, "moe_impl": moe_impl, "remat": remat},
        tokens, targets,
    )
    ours.warmup()
    base = _Harness(
        {"attn_impl": "xla", "moe_impl": "dense", "remat": remat},
        tokens, targets,
    )
    base.warmup()
    ours_dts, base_dts = _interleaved_dts(ours, base, rounds, iters)

    dt, base_dt = _median(ours_dts), _median(base_dts)
    tps, base_tps = batch * seq / dt, batch * seq / base_dt
    spread = lambda xs: (max(xs) - min(xs)) / _median(xs)  # noqa: E731

    result = {
        "metric": "flagship_moe_train_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tps / base_tps, 3),
        "step_time_ms": round(dt * 1e3, 2),
        "baseline_tokens_per_sec": round(base_tps, 1),
        # Medians of `rounds` interleaved A-B samples, `iters` steps each;
        # rel_spread = (max-min)/median of the per-round step times. A
        # headline whose spread is wide is noise, not evidence.
        "rounds": rounds,
        "iters_per_round": iters,
        "rel_spread": round(spread(ours_dts), 3),
        "baseline_rel_spread": round(spread(base_dts), 3),
        "samples_ms": [round(d * 1e3, 1) for d in ours_dts],
        "baseline_samples_ms": [round(d * 1e3, 1) for d in base_dts],
        "platform": platform,
        "device": device_kind,
        "attn_impl": attn_impl,
        "moe_impl": moe_impl,
        "remat": remat,
        "batch": batch,
        "seq": seq,
        "mfu": round(_model_flops_per_token(ours.cfg, seq) * tps / peak, 4),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
