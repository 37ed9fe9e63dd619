"""Checkpoint/resume through orbax: the framework's params/opt pytrees are
checkpoint-transparent.

The reference treats checkpointing as out of comm-layer scope (SURVEY §5)
and leans on its consumers' frameworks; the equivalent contract here is
that every state tree the framework produces (flagship params, optimizer
state) round-trips through orbax unchanged and training resumes
bit-identically — so a user switching from the reference keeps their
checkpoint workflow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ocp = pytest.importorskip("orbax.checkpoint")


@pytest.fixture(scope="module")
def tiny_setup(mesh8):
    from uccl_tpu.models.flagship import (
        FlagshipConfig, init_params, make_train_step, shard_params,
    )

    mesh = mesh8
    cfg = FlagshipConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
        moe_ffn=128, vocab=256, moe_experts=8, n_microbatches=1,
    )
    params = shard_params(init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
    train_step, init_opt = make_train_step(cfg, mesh)
    return cfg, mesh, params, train_step, init_opt


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestOrbaxRoundTrip:
    def test_params_and_opt_state_roundtrip(self, tiny_setup, tmp_path):
        cfg, mesh, params, train_step, init_opt = tiny_setup
        opt_state = init_opt(params)
        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(tmp_path / "params", params)
        ckpt.save(tmp_path / "opt", opt_state)
        restored_p = ckpt.restore(tmp_path / "params", item=params)
        restored_o = ckpt.restore(tmp_path / "opt", item=opt_state)
        _tree_equal(params, restored_p)
        _tree_equal(opt_state, restored_o)

    def test_resume_is_bit_identical(self, tiny_setup, tmp_path, rng):
        """step; checkpoint; step again = restore; step — same trajectory."""
        cfg, mesh, params, train_step, init_opt = tiny_setup
        step = jax.jit(train_step)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32)
        targets = jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)), jnp.int32)

        p1, o1, _ = step(params, init_opt(params), tokens, targets)
        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(tmp_path / "p1", p1)
        ckpt.save(tmp_path / "o1", o1)
        p2, o2, m2 = step(p1, o1, tokens, targets)

        rp = ckpt.restore(tmp_path / "p1", item=p1)
        ro = ckpt.restore(tmp_path / "o1", item=o1)
        # restored trees are host arrays; resharding must be transparent
        from uccl_tpu.models.flagship import shard_params

        rp = shard_params(jax.tree.map(jnp.asarray, rp), mesh, cfg)
        p2r, o2r, m2r = step(rp, jax.tree.map(jnp.asarray, ro), tokens,
                             targets)
        _tree_equal(p2, p2r)
        np.testing.assert_allclose(
            float(m2["loss"]), float(m2r["loss"]), rtol=0, atol=0
        )
