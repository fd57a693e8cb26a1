"""MoE execution paths: shard_map EP must match the SPMD dispatch exactly.

On a (data=1, model=1) mesh the shard_map path runs with e_local = E and
rank 0, which must reproduce the single-program dispatch bit-for-bit
(same capacity, same stable argsort) — guarding the §Perf m1 optimization
against semantic drift.
"""

import dataclasses

import jax
import numpy as np

from repro.compat import make_mesh, use_mesh
from repro.models import blocks
from repro.models.common import Init
from repro.models.config import ModelConfig


def setup(seed=0):
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=64,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=128,
                      n_experts=8, top_k=2, expert_d_ff=96,
                      moe_strategy="ep")
    params, _ = blocks.init_moe(cfg, Init(jax.random.PRNGKey(seed)))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, 64))
    return cfg, params, x


def test_shardmap_matches_spmd():
    cfg, params, x = setup()
    y_spmd, aux_spmd = blocks.apply_moe_spmd(cfg, params, x)

    mesh = make_mesh((1, 1), ("data", "model"))
    with use_mesh(mesh):
        y_sm, aux_sm = blocks.apply_moe_shardmap(cfg, params, x, mesh)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_spmd),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_sm), float(aux_spmd), rtol=1e-5)


def test_moe_impl_dispatch():
    cfg, params, x = setup(2)
    cfg_sm = dataclasses.replace(cfg, moe_impl="shardmap")
    # without a model-axis mesh, shardmap falls back to spmd
    y1, _ = blocks.apply_moe(cfg_sm, params, x)
    y0, _ = blocks.apply_moe(cfg, params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0))


def test_capacity_drops_are_bounded():
    """Overflowing tokens are dropped, never mis-routed: with capacity
    factor ~0 every token routes to the residual passthrough only."""
    cfg, params, x = setup(3)
    tiny = dataclasses.replace(cfg, capacity_factor=0.0)
    y, _ = blocks.apply_moe_spmd(tiny, params, x)
    # capacity 1 slot: outputs stay finite and close to the residual
    assert np.isfinite(np.asarray(y)).all()


def test_grads_flow_both_paths():
    cfg, params, x = setup(4)

    def loss_spmd(p):
        return blocks.apply_moe_spmd(cfg, p, x)[0].sum()

    g1 = jax.grad(loss_spmd)(params)
    mesh = make_mesh((1, 1), ("data", "model"))

    def loss_sm(p):
        return blocks.apply_moe_shardmap(cfg, p, x, mesh)[0].sum()

    with use_mesh(mesh):
        g2 = jax.grad(loss_sm)(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)
