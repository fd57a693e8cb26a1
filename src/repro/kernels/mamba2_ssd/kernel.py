"""Pallas TPU kernel for Mamba2 SSD — the chunked dual form.

The linear recurrence is sequential, but the *state-space dual* splits T
into chunks of L where, within a chunk, outputs are a masked quadratic form
(MXU matmuls) and only the [N, P] state crosses chunk boundaries:

    la      = cumsum(log a)                     per chunk, [L]
    scores  = (C @ B^T) * exp(la_t - la_s) * (s <= t)     [L, L]
    y_intra = scores @ (dt * x)                            [L, P]
    y_inter = exp(la) * (C @ S)                            [L, P]
    S'      = exp(la_L - la) -weighted B^T (dt*x) + exp(la_L) * S

This is exactly how SSD maps to the TPU: the three [L, *] matmuls hit the
MXU, the decay algebra is VPU work in log space, and the sequential carry
is a [N, P] f32 scratch that persists across the innermost grid dimension
(chunks), as in the WKV kernel.

grid = (B*H, T/L).  B/C are shared per head-group (GQA-style): the index
map folds heads onto groups, so no repeated HBM copies are materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)                 # [L, P]
    dt = dt_ref[0].astype(jnp.float32)               # [L]
    A = a_ref[0, 0]                                  # scalar (this head)
    Bm = b_ref[0].astype(jnp.float32)                # [L, N]
    Cm = c_ref[0].astype(jnp.float32)                # [L, N]
    S = s_ref[...]                                   # [N, P]

    xdt = x * dt[:, None]                            # [L, P]
    la = jnp.cumsum(dt * A)                          # [L] log decay prefix
    # pairwise decay exp(la_t - la_s) for s <= t, 0 otherwise
    diff = la[:, None] - la[None, :]                 # [L, L]
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(mask, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(scores * seg, xdt,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = jnp.exp(la)[:, None] * jax.lax.dot_general(
        Cm, S, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: S' = exp(la_L) S + sum_s exp(la_L - la_s) B_s xdt_s^T
    total = la[chunk - 1]
    wgt = jnp.exp(total - la)                        # [L]
    s_ref[...] = jnp.exp(total) * S + jax.lax.dot_general(
        Bm * wgt[:, None], xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray, Bm: jnp.ndarray,
        Cm: jnp.ndarray, *, chunk: int = 128,
        interpret: bool = True) -> jnp.ndarray:
    """x: [B,T,H,P]; dt: [B,T,H]; A: [H]; Bm,Cm: [B,T,G,N] -> [B,T,H,P]."""
    b, t, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    chunk = min(chunk, t)
    assert t % chunk == 0 and h % g == 0, (x.shape, Bm.shape, chunk)
    rep = h // g

    # [B*H, T, *] layouts; B/C stay [B*G, T, N] and are group-indexed.
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, t, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, t)
    Bf = Bm.transpose(0, 2, 1, 3).reshape(b * g, t, n)
    Cf = Cm.transpose(0, 2, 1, 3).reshape(b * g, t, n)

    grid = (b * h, t // chunk)
    x_spec = pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0))
    dt_spec = pl.BlockSpec((1, chunk), lambda i, c: (i, c))
    a_spec = pl.BlockSpec((1, 1), lambda i, c, H=h: (i % H, 0))
    bc_spec = pl.BlockSpec(
        (1, chunk, n), lambda i, c, H=h, R=rep: ((i // H) * (H // R)
                                                 + (i % H) // R, c, 0))

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[x_spec, dt_spec, a_spec, bc_spec, bc_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, A.reshape(h, 1).astype(jnp.float32), Bf, Cf)
    return out.reshape(b, h, t, p).transpose(0, 2, 1, 3)
