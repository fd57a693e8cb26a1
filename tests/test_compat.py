"""The repro.compat contract: pinned-API canary, Pallas ref indexing
(interpret + compiled), Auto-axis meshes under ``jax.set_mesh``, and the
no-raw-version-sensitive-calls source invariant."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import AxisType

from repro import compat
from repro.compat.version import KNOWN_BRANCHES
from repro.parallel import sharding

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"


# ---------------------------------------------------------------------------
# Pinned-API canary: a JAX bump must fail HERE, not as scattered
# AttributeErrors across the suite.
# ---------------------------------------------------------------------------

def test_pinned_api_canary():
    report = compat.check_pinned_api()          # raises on drift
    assert report["supported"], report
    for chain, known in KNOWN_BRANCHES.items():
        assert report[chain] in known, (chain, report)


def test_flatten_cost_analysis_accepts_both_shapes():
    assert compat.flatten_cost_analysis({"flops": 2.0}) == {"flops": 2.0}
    assert compat.flatten_cost_analysis({}) == {}
    assert compat.flatten_cost_analysis(None) == {}


def test_version_parse_is_tolerant():
    from repro.compat.version import _parse
    assert _parse("0.9.0") == (0, 9, 0)
    assert _parse("0.10.0.dev20260101") == (0, 10, 0)
    assert _parse("0.9") == (0, 9, 0)


def test_no_version_sensitive_calls_outside_compat():
    """The acceptance grep, enforced from inside the suite: mesh
    construction, activation and introspection live only in compat, and
    nothing calls the Pallas load/store functions JAX removed."""
    import re
    needles = [re.escape(n) for n in (
        "get_abstract_mesh", "pl.load(", "pl.store(", "thread_resources",
        "jax.set_mesh", "jax.make_mesh(", "jax.sharding.use_mesh")]
    offenders = []
    for path in SRC.rglob("*.py"):
        if "compat" in path.parts:
            continue
        text = path.read_text()
        offenders += [(str(path), n) for n in needles
                      if re.search(n, text)]
    assert not offenders, offenders


# ---------------------------------------------------------------------------
# Pallas ref indexing: the spelling every kernel uses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret", [True, False])
def test_load_store_block_roundtrip(interpret):
    """int + dynamic-slice + full-slice mixed ref indices, through a real
    pallas_call, on both execution paths."""
    if not interpret and jax.default_backend() != "tpu":
        pytest.skip("compiled Pallas TPU path needs a TPU backend")

    x = jnp.arange(2 * 8 * 128, dtype=jnp.float32).reshape(2, 8, 128)

    def kernel(x_ref, o_ref):
        row = x_ref[1, pl.ds(2, 4)]                                # [4, 128]
        assert row.shape == (4, 128)
        head = x_ref[0]                                            # [8, 128]
        assert head.shape == (8, 128)

        def body(t, acc):
            r = x_ref[0, t]                                        # [128]
            o_ref[1, t] = r * 2.0
            return acc + r.sum()

        total = jax.lax.fori_loop(0, 8, body, jnp.float32(0))
        o_ref[0] = head + total * 0.0
        o_ref[0, pl.ds(0, 4)] = row

    got = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x)

    want = np.asarray(x)
    want = want.copy()
    want[1] = want[0] * 2.0
    want[0, 0:4] = np.asarray(x)[1, 2:6]
    np.testing.assert_allclose(np.asarray(got), want)


# ---------------------------------------------------------------------------
# Meshes: Auto axes, activated with jax.set_mesh
# ---------------------------------------------------------------------------

def test_make_mesh_axes_are_auto():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_mesh_fallback_chain_resolves_identically():
    """The active mesh read back through compat resolves logical axes
    exactly as the concrete mesh it was activated with."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert compat.current_mesh() is None          # nothing active
    with compat.use_mesh(mesh):
        got = compat.current_mesh()
        assert got is not None and not got.empty
        assert tuple(got.axis_names) == ("data", "model")
        logical, shape = ("batch", None, "embed"), (4, 8, 16)
        assert (sharding.resolve(logical, got, shape=shape)
                == sharding.resolve(logical, mesh, shape=shape))
    assert compat.current_mesh() is None          # cleanly deactivated


def test_shard_is_noop_without_mesh_and_constrains_with():
    x = jnp.ones((4, 16))
    y = sharding.shard(x, ("batch", None))        # no mesh: identity
    assert y is x

    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        out = jax.jit(lambda a: sharding.shard(a, ("batch", None)))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
