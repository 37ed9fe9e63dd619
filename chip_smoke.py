"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the flagship MoE (vocab 16,384 / dim 1024 / 4 layers / 16+4
heads x 64 / 8 experts top-2 x 2816 / sequence 1024; weights random from a
seed), in ONE process on every chip that process sees:

1. attention: the compiled Pallas flash kernel, forward and backward, against
   the XLA reference at the flagship head geometry;
2. server: ``uccl_tpu.serve.main(["--server", "--stack", "moe", ...])`` —
   a burst of mixed-length requests through ``ServingEngine``, once with
   whole-prompt prefill and once with ``--prefill-chunk``;
3. trainer: ``uccl_tpu.train.main([...])`` with ``--mesh dp=<chips>`` for a
   handful of steps.

It catches nothing: a phase that raises or a check that fails ends the run
non-zero. Without a TPU it exits non-zero before anything else and prints no
result. Standard output ends with two JSON lines: first the report — per
phase the set-up (compile) seconds, steady seconds per step or request, peak
bytes in use and the resolved attention path, MoE path and activation dtype
(facts about the bring-up, not a benchmark: ``"claim": null``) — and last the
verdict, exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``
with the device as JAX reports it.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import math
import sys
import time

# the one width this repo has had on a chip (head_dim = dim / heads = 64;
# top-2 routing is fixed by the entry points)
FLAGSHIP = dict(vocab=16384, dim=1024, layers=4, heads=16, kv_heads=4,
                experts=8, ffn=2816)
# the loss on the seeded random stream starts near ln(vocab) + 0.5 (unit-
# variance logits) and cannot fall much below ln(vocab) in a few steps
LOSS_BAND = (-0.5, 1.5)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


def _size_flags(size: dict) -> list:
    return [a for k, v in size.items()
            for a in (f"--{k.replace('_', '-')}", str(v))]


def attention_phase(size: dict, seq: int) -> dict:
    """Flash attention (compiled on a TPU, interpreted on the CPU) against
    the XLA reference: outputs and input gradients, bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from uccl_tpu.ops.attention import _auto_block, attention_reference
    from uccl_tpu.ops.pallas_attention import flash_attention
    from uccl_tpu.utils import device

    d = size["dim"] // size["heads"]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (2, seq, size["heads"], d), jnp.bfloat16)
    k = jax.random.normal(kk, (2, seq, size["kv_heads"], d), jnp.bfloat16)
    v = jax.random.normal(kv, (2, seq, size["kv_heads"], d), jnp.bfloat16)

    def value_and_grads(attn):
        def loss(q, k, v):
            return jnp.sum(jnp.sin(attn(q, k, v).astype(jnp.float32)))

        return jax.jit(lambda q, k, v: (
            attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ))

    t0 = time.perf_counter()
    got = jax.block_until_ready(
        value_and_grads(lambda q, k, v: flash_attention(q, k, v, True))(
            q, k, v))
    setup_s = time.perf_counter() - t0
    want = value_and_grads(
        lambda q, k, v: attention_reference(q, k, v, causal=True))(q, k, v)
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"),
                          jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        _check(bool(jnp.isfinite(a).all()), f"flash {name} is not finite")
        errs[name] = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        # bf16 inputs, f32 accumulation on both sides: a few bf16 ulps
        _check(errs[name] < 0.05,
               f"flash {name} differs from the reference by {errs[name]:.3g} "
               "of its largest value")
    return {"interpret": device.pallas_interpret(),
            "block": _auto_block(seq), "seq": seq,
            "setup_s": round(setup_s, 3), "rel_err": errs}


def server_phase(size: dict, *, slots: int, world: int, requests: int,
                 prompt_len: int, new_tokens: int,
                 prefill_chunk: int) -> dict:
    """``serve.main --server --stack moe``: every request completed with the
    tokens it asked for, books balanced, no slot leaked."""
    from uccl_tpu import serve

    argv = ["--server", "--stack", "moe", *_size_flags(size),
            "--dp", str(world), "--slots", str(slots),
            "--requests", str(requests), "--arrival-rate", "0",
            "--prompt-len", str(prompt_len), "--new-tokens", str(new_tokens)]
    if prefill_chunk:
        argv += ["--prefill-chunk", str(prefill_chunk)]
    s = serve.main(argv)
    _check(s["submitted"] == s["completed"] == requests,
           f"submitted {s['submitted']} / completed {s['completed']} of "
           f"{requests} requests")
    _check(s["queued"] == 0 and s["active"] == 0,
           f"queue {s['queued']} / active {s['active']} after the drain")
    _check(s["leaked_slots"] == 0, f"{s['leaked_slots']} leaked slots")
    _check(s["short_requests"] == 0
           and s["output_tokens"] == requests * new_tokens,
           f"{s['output_tokens']} tokens out, {s['short_requests']} short "
           f"requests; asked {requests} x {new_tokens}")
    keep = ("device", "dtype", "devices_used", "prefill_impl", "decode_impl",
            "ll_wire", "moe_wire", "slots", "requests", "new_tokens",
            "prefill_chunk", "warmup_s", "compiles_after_warmup",
            "peak_bytes_in_use", "wall_s", "ttft_ms", "tpot_ms",
            "decode_step_ms")
    out = {k: s[k] for k in keep if k in s}
    out["prompt_len"] = prompt_len
    out["request_s"] = round(s["wall_s"] / requests, 4)
    return out


def trainer_phase(size: dict, *, chips: int, batch: int, seq: int,
                  steps: int) -> dict:
    """``train.main --model flagship --mesh dp=<chips>``: a finite loss near
    ln(vocab) at every step."""
    from uccl_tpu import train

    s = train.main(["--model", "flagship", *_size_flags(size),
                    "--mesh", f"dp={chips}", "--batch", str(batch),
                    "--seq", str(seq), "--steps", str(steps),
                    "--log-every", "1"])
    _check(len(s["losses"]) == steps, f"{len(s['losses'])} of {steps} losses")
    ln_v = math.log(size["vocab"])
    for i, loss in enumerate(s["losses"]):
        _check(math.isfinite(loss)
               and ln_v + LOSS_BAND[0] <= loss <= ln_v + LOSS_BAND[1],
               f"step {i + 1} loss {loss} outside ln(vocab) {ln_v:.3f} "
               f"{LOSS_BAND[0]:+}/{LOSS_BAND[1]:+}")
    return s


def run(size: dict, *, seq: int, batch_per_chip: int, steps: int,
        slots_per_chip: int, requests: int, prompt_len: int,
        new_tokens: int, prefill_chunk: int) -> dict:
    """Every phase once, on all the devices this process sees. Sizes are the
    caller's: ``main`` passes the flagship's, the CPU test a tiny one."""
    import jax

    from uccl_tpu.collective.dma import WIRE_FALLBACK
    from uccl_tpu.utils import device

    device.enable_compile_cache()
    n = len(jax.devices())
    phases = {"attention": attention_phase(size, seq)}
    # the server runs before the trainer: peak_bytes_in_use is a high-water
    # mark of the process, and the trainer's is the larger
    serve_kw = dict(slots=slots_per_chip * n, world=n, requests=requests,
                    prompt_len=prompt_len, new_tokens=new_tokens)
    phases["serve_whole_prompt"] = server_phase(
        size, prefill_chunk=0, **serve_kw)
    phases["serve_chunked"] = server_phase(
        size, prefill_chunk=prefill_chunk, **serve_kw)
    phases["train"] = trainer_phase(
        size, chips=n, batch=batch_per_chip * n, seq=seq, steps=steps)
    # the default path is the lax wire; an opt-in pallas wire that quietly
    # rode lax instead would show up here
    _check(WIRE_FALLBACK.total() == 0,
           f"ep_wire_fallback_total = {WIRE_FALLBACK.samples()}")
    return phases


def main() -> int:
    from importlib import metadata

    import jax
    import jaxlib

    dev = jax.devices()[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {device['count']}  jax {jax.__version__}  "
          f"jaxlib {jaxlib.__version__}  libtpu {libtpu}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    phases = run(FLAGSHIP, seq=1024, batch_per_chip=8, steps=5,
                 slots_per_chip=8, requests=32, prompt_len=256,
                 new_tokens=16, prefill_chunk=64)
    # on the chip the kernel is compiled, the trainer takes it, and the
    # activations are the MXU's dtype
    _check(phases["attention"]["interpret"] is False, "flash was interpreted")
    _check(phases["train"]["attn_impl"] == "flash",
           f"trainer attention was {phases['train']['attn_impl']!r}")
    _check(phases["train"]["dtype"] == "bfloat16",
           f"trainer activations were {phases['train']['dtype']}")
    for name, p in phases.items():
        if name != "attention":
            _check(p["device"] == device, f"{name} ran on {p['device']}")
            _check(p["peak_bytes_in_use"], f"{name} reports no peak memory")
    print(json.dumps({"report": "chip_smoke", "phases": phases,
                      "total_s": round(time.perf_counter() - t0, 1),
                      "claim": None}), flush=True)
    # the verdict: these keys and no others, the last line of stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
