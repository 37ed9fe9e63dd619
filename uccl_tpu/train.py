"""Unified trainer entry: ``python -m uccl_tpu.train``.

The consumer-facing front door the reference's users reach through
torchrun + Megatron/DDP scripts (examples/ddp_train.py there; OSDI AE
workloads, collective/utran_osdi26ae.md:151-163): pick a model family,
describe the mesh, train — with periodic orbax checkpoints and
bit-identical resume (tests/test_checkpoint.py proves the state trees are
checkpoint-transparent; this wires the loop around them).

    python -m uccl_tpu.train --model flagship --mesh dp=2,cp=2,tp=2 \
        --devices 8 --steps 20 --batch 8 --seq 64 \
        --ckpt-dir /tmp/run1 --ckpt-every 10
    # later, continue from the newest checkpoint:
    python -m uccl_tpu.train ... --ckpt-dir /tmp/run1 --resume

Data is a seeded synthetic stream where step i's batch depends only on i,
so an interrupted+resumed run replays the exact uninterrupted trajectory
(the resume test's contract). Swap ``_batch_for_step`` for a real loader
in production.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

# Runnable both as `python -m uccl_tpu.train` and as a plain script path
# (the launcher's contract: scripts/launch.py train.py ...). Only the
# script-path case needs the repo root on sys.path — a library import must
# not mutate it (it could shadow an installed uccl_tpu).
if __package__ in (None, ""):
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def parse_mesh(spec: str):
    """"dp=2,cp=2,tp=2" -> MeshConfig (unnamed axes default to 1)."""
    from uccl_tpu.parallel.mesh import MeshConfig

    sizes = {}
    if spec:
        for part in spec.split(","):
            m = re.fullmatch(r"(pp|dp|cp|tp)=(\d+)", part.strip())
            if not m:
                raise SystemExit(
                    f"bad --mesh entry {part!r} (want e.g. dp=2,tp=2)"
                )
            sizes[m.group(1)] = int(m.group(2))
    return MeshConfig(**sizes)


def build(args, mesh):
    """Returns (cfg, params, train_step, init_opt) for the model family."""
    import jax
    import jax.numpy as jnp

    if args.model == "flagship":
        from uccl_tpu.models import flagship as fam
    else:
        from uccl_tpu.models import dense as fam

    size_kw = dict(
        vocab=args.vocab, dim=args.dim, n_layers=args.layers,
        n_heads=args.heads, n_kv_heads=args.kv_heads,
        head_dim=args.dim // args.heads,
        n_microbatches=args.microbatches,
        # activations in the MXU's dtype on a TPU; float32 on the CPU, where
        # the tests compare trajectories bit for bit (params, gradients and
        # optimizer state are float32 everywhere)
        dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
        else jnp.float32,
    )
    if args.model == "flagship":
        size_kw.update(
            moe_experts=args.experts, moe_ffn=args.ffn,
            moe_topk=2, remat=args.remat,
        )
    else:
        size_kw.update(ffn=args.ffn, remat=args.remat)
    cfg = (fam.FlagshipConfig if args.model == "flagship"
           else fam.DenseConfig)(**size_kw)
    params = fam.shard_params(
        fam.init_params(jax.random.PRNGKey(args.seed), cfg), mesh, cfg
    )
    train_step, init_opt = fam.make_train_step(cfg, mesh, learning_rate=args.lr)
    return cfg, params, train_step, init_opt


def _batch_for_step(step_i, batch, seq, vocab, corpus=None):
    """Deterministic batch (host arrays): a function of the step index
    ONLY, so resumed runs see the same stream. Device placement is the
    caller's job — single-controller jit takes numpy directly; multihost
    shards it via make_array_from_callback.

    With a ``corpus`` (a 1-D int token memmap from --data), batch rows are
    contiguous windows at deterministic step-indexed offsets and targets
    are the next-token shift — the standard LM objective. Without one, the
    stream is seeded synthetic noise."""
    import numpy as np

    if corpus is not None:
        n = corpus.shape[0] - seq - 1
        rng = np.random.default_rng(10_000 + step_i)
        starts = rng.integers(0, n, batch)
        tokens = np.stack([corpus[s : s + seq] for s in starts])
        targets = np.stack([corpus[s + 1 : s + seq + 1] for s in starts])
        return tokens.astype(np.int32), targets.astype(np.int32)
    rng = np.random.default_rng(10_000 + step_i)
    tokens = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    targets = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    return tokens, targets


def _open_corpus(path, vocab, seq):
    """Memmap a 1-D int token file (.npy). Validated once over the WHOLE
    corpus: every id in [0, vocab), long enough for one window — an
    out-of-range id would otherwise clamp in the embedding gather and
    silently corrupt training."""
    import numpy as np

    corpus = np.load(path, mmap_mode="r")
    if corpus.ndim != 1 or not np.issubdtype(corpus.dtype, np.integer):
        raise SystemExit(f"--data {path}: want a 1-D integer token array")
    if corpus.shape[0] < seq + 2:
        raise SystemExit(
            f"--data {path}: {corpus.shape[0]} tokens < one {seq}-token window"
        )
    hi, lo = int(corpus.max()), int(corpus.min())
    if lo < 0 or hi >= vocab:
        raise SystemExit(
            f"--data {path}: token ids span [{lo}, {hi}], outside "
            f"[0, {vocab})"
        )
    return corpus


def _on_mesh(tree, mesh):
    """Leaves born on one device (optimizer scalars like adam's count)
    replicated over the mesh; mesh-sharded leaves untouched. Mixed, the
    single-device leaves come back mesh-replicated from the first step and
    the second step compiles again for the new argument shardings."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: x if isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, replicated),
        tree,
    )


def _latest_step(ckpt_dir):
    steps = []
    for name in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", name)
        if m:
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def _save(ckpt_dir, step_i, params, opt_state, model_cfg=None):
    """ONE orbax save of the combined state tree: the write is a single
    atomic directory rename, so an interrupted run can never leave a
    half-checkpoint that _latest_step would pick but _restore cannot load.
    ``model_cfg`` (model family + size flags) is recorded ONCE as
    config.json beside the checkpoints — serving reads it back instead of
    guessing sizes from flags."""
    import orbax.checkpoint as ocp

    if model_cfg is not None:
        cfg_path = os.path.join(ckpt_dir, "config.json")
        if not os.path.exists(cfg_path):
            os.makedirs(ckpt_dir, exist_ok=True)
            tmp = f"{cfg_path}.{os.getpid()}.tmp"  # rank-unique
            with open(tmp, "w") as f:
                json.dump(model_cfg, f)
            os.replace(tmp, cfg_path)
    path = os.path.join(ckpt_dir, f"step_{step_i}")
    ocp.PyTreeCheckpointer().save(path, {"params": params, "opt": opt_state})


def _restore(ckpt_dir, step_i, params, opt_state, mesh):
    """Restore WITH explicit target shardings: the live trees' shardings
    become orbax restore_args, so a checkpoint saved under one process
    topology resumes under another (elastic restart; without this, orbax
    can only re-apply the save-time shardings and cross-topology resume
    dies with a 'sharding ... should be specified' error). Leaves without
    a mesh sharding (optimizer scalars like adam's count are born on one
    device) restore REPLICATED over the mesh — a committed single-device
    scalar would conflict with the 8-device params inside jit."""
    import jax
    import orbax.checkpoint as ocp
    from jax.sharding import NamedSharding, PartitionSpec as P

    path = os.path.join(ckpt_dir, f"step_{step_i}")
    item = {"params": params, "opt": opt_state}

    def args_for(x):
        sh = getattr(x, "sharding", None)
        if not isinstance(sh, NamedSharding):
            sh = NamedSharding(mesh, P())
        return ocp.ArrayRestoreArgs(
            sharding=sh, global_shape=x.shape, dtype=x.dtype
        )

    tree = ocp.PyTreeCheckpointer().restore(
        path, item=item, restore_args=jax.tree.map(args_for, item)
    )
    return tree["params"], tree["opt"]


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m uccl_tpu.train")
    ap.add_argument("--model", default="flagship",
                    choices=["flagship", "dense"])
    ap.add_argument("--mesh", default="", help="e.g. pp=2,dp=2,tp=2")
    ap.add_argument("--devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh (tests/dev)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    # model size
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--ffn", type=int, default=128)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument(
        "--remat", default="full", choices=["full", "dots", "mlp", "none"],
        help="backward recompute schedule (mlp: save the expert GEMMs, "
        "rematerialize attention — the measured v5e sweet spot for "
        "--model flagship; for --model dense it is equivalent to dots)",
    )
    ap.add_argument("--data", default="",
                    help="1-D int token .npy (memmapped); batches are "
                         "next-token windows at step-indexed offsets")
    # checkpointing
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

    from uccl_tpu.utils import device

    compiles0 = device.compile_counts()

    session = None
    if "UCCL_TPU_COORD" in os.environ:
        # Launched by scripts/launch.py (torchrun-shaped): join the
        # session BEFORE any device query so jax.distributed can assemble
        # the global device view.
        from uccl_tpu.parallel.distributed import initialize_from_env

        session = initialize_from_env()
        print(
            f"joined session rank {session.rank}/{session.world}", flush=True
        )

    from uccl_tpu.parallel.mesh import make_mesh

    # after the session join: this looks at the backend, and
    # jax.distributed must initialize before anything does
    device.enable_compile_cache()

    # Multi-controller mode (scripts/launch.py with jax.distributed on):
    # every process sees the GLOBAL device list; batches must be assembled
    # as global arrays and only rank 0 narrates.
    multihost = session is not None and session.world > 1
    chatty = not multihost or session.rank == 0
    mcfg = parse_mesh(args.mesh)
    devices = jax.devices()
    if args.mesh and mcfg.size != len(devices):
        raise SystemExit(
            f"mesh size {mcfg.size} != device count {len(devices)}"
        )
    mesh = make_mesh(mcfg if args.mesh else None, devices)
    dp = mcfg.dp if args.mesh else len(devices)
    cp = mcfg.cp if args.mesh else 1
    if args.batch % dp or args.seq % cp:
        raise SystemExit(
            f"--batch {args.batch} must divide by dp={dp} and --seq "
            f"{args.seq} by cp={cp} (data is sharded [batch/dp, seq/cp])"
        )
    corpus = _open_corpus(args.data, args.vocab, args.seq) if args.data \
        else None
    cfg, params, train_step, init_opt = build(args, mesh)
    opt_state = _on_mesh(init_opt(params), mesh)

    start = 0
    if args.resume:
        if not (args.ckpt_dir and os.path.isdir(args.ckpt_dir)):
            raise SystemExit("--resume needs an existing --ckpt-dir")
        latest = _latest_step(args.ckpt_dir)
        if latest is None:
            raise SystemExit(f"no step_N checkpoints in {args.ckpt_dir}")
        params, opt_state = _restore(
            args.ckpt_dir, latest, params, opt_state, mesh
        )
        start = latest
        if chatty:
            print(
                f"resumed from {args.ckpt_dir}/step_{latest}", flush=True
            )
    elif args.ckpt_dir and os.path.isdir(args.ckpt_dir) \
            and _latest_step(args.ckpt_dir) is not None:
        # fail BEFORE training, not at the first save (orbax refuses to
        # overwrite an existing step_N and would waste the whole run)
        raise SystemExit(
            f"{args.ckpt_dir} already holds checkpoints; pass --resume to "
            "continue from them or choose a fresh --ckpt-dir"
        )

    # params/opt_state are DONATED: XLA aliases them into the outputs, so
    # the step holds one copy of the largest buffers, not two. The outputs
    # are pinned to the inputs' shardings — left to XLA they can come back
    # canonicalized differently, and the second step would compile again.
    state_shardings = jax.tree.map(lambda x: x.sharding, (params, opt_state))
    step = jax.jit(train_step, donate_argnums=(0, 1),
                   out_shardings=(*state_shardings, None))
    if multihost:
        # Every process builds the SAME deterministic global batch (cheap,
        # synthetic); make_array_from_callback hands each process only its
        # addressable shards of the [batch, seq] arrays, laid out exactly
        # as the model's data spec expects — no resharding inside jit.
        from jax.sharding import NamedSharding, PartitionSpec as P

        data_sharding = NamedSharding(mesh, P("dp", "cp"))

        def place(arr):
            return jax.make_array_from_callback(
                arr.shape, data_sharding, lambda idx: arr[idx]
            )
    else:
        place = None
    t0 = time.perf_counter()
    t_first = None
    metrics = None
    losses = []  # the logged ones: reading a loss waits for the device
    for i in range(start, args.steps):
        tokens, targets = _batch_for_step(
            i, args.batch, args.seq, args.vocab, corpus
        )
        if place is not None:
            tokens, targets = place(tokens), place(targets)
        params, opt_state, metrics = step(params, opt_state, tokens, targets)
        if t_first is None:
            # the first step carries trace + compile: timed apart, so the
            # rate below is the steady state's
            jax.block_until_ready(metrics)
            t_first = time.perf_counter()
        if chatty and args.log_every and (i + 1) % args.log_every == 0:
            extra = (
                f" ce {float(metrics['ce']):.6f}" if "ce" in metrics else ""
            )
            losses.append(float(metrics["loss"]))
            print(
                f"step {i + 1:5d} loss {losses[-1]:.6f}{extra}",
                flush=True,
            )
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            _save(args.ckpt_dir, i + 1, params, opt_state, model_cfg={
                "model": args.model, "vocab": args.vocab, "dim": args.dim,
                "layers": args.layers, "heads": args.heads,
                "kv_heads": args.kv_heads, "ffn": args.ffn,
                "experts": args.experts,
            })
            if chatty:
                print(f"checkpointed step {i + 1}", flush=True)
    final_loss = float(metrics["loss"]) if metrics else None  # device done
    t_end = time.perf_counter()
    done = args.steps - start
    summary = {
        "model": args.model,
        "device": device.describe(),
        "mesh": {"pp": mcfg.pp, "dp": mcfg.dp, "cp": mcfg.cp, "tp": mcfg.tp}
        if args.mesh else {"dp": len(devices)},
        "batch": args.batch, "seq": args.seq,
        "dtype": str(jax.numpy.dtype(cfg.dtype)),
        "remat": cfg.remat,
        "steps": done,
        "final_loss": round(final_loss, 6) if metrics else None,
        "losses": [round(x, 6) for x in losses],
        "steps_per_sec": round(done / (t_end - t0), 3) if done else 0.0,
        # first step = trace + compile + one step; the rest is steady state
        "first_step_s": round(t_first - t0, 3) if done else None,
        "step_s": round((t_end - t_first) / (done - 1), 4)
        if done > 1 else None,
        "peak_bytes_in_use": device.peak_bytes_in_use(),
        **device.counts_since(compiles0),
    }
    if args.model == "flagship":
        from uccl_tpu.models.flagship import resolve_attn_impl

        summary["attn_impl"] = resolve_attn_impl(cfg)
        summary["moe_impl"] = cfg.moe_impl
        summary["moe_wire"] = cfg.moe_wire
    if multihost:
        summary["processes"] = session.world
    if chatty:
        print(json.dumps(summary), flush=True)
    if session is not None:
        session.close()  # release the OOB store port/threads promptly
    return summary


if __name__ == "__main__":
    main()
