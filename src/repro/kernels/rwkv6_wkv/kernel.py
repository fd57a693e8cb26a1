"""Pallas TPU kernel for the RWKV6 WKV recurrence.

TPU adaptation: the recurrence is sequential in T but embarrassingly
parallel over (batch x heads) and fully vectorizable over the [K, V] state
plane.  Layout:

* grid = (B*H, T/CHUNK); the T axis is the *innermost* grid dim, which
  Pallas-TPU executes sequentially per core — the [K, V] f32 state lives in
  a VMEM scratch buffer that persists across chunk iterations (the same
  accumulator pattern as a matmul k-loop),
* each chunk streams [CHUNK, K] r/k/w tiles and a [CHUNK, V] v tile into
  VMEM and walks them with ``fori_loop``; all state math is rank-2 VPU work
  (outer products + row reductions — no MXU use, like the CUDA original).

RWKV6-7B shapes: K = V = 64 -> 16 KiB state; CHUNK = 256 keeps the streamed
tiles < 300 KiB, far under VMEM budget, so many heads can be multi-buffered.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                 chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                    # [K]

    def step(t, S):
        r_t = r_ref[0, t].astype(jnp.float32)           # [K]
        k_t = k_ref[0, t].astype(jnp.float32)
        v_t = v_ref[0, t].astype(jnp.float32)           # [V]
        w_t = w_ref[0, t].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                # [K, V]
        y = ((S + u[:, None] * kv) * r_t[:, None]).sum(0)   # [V]
        o_ref[0, t] = y.astype(o_ref.dtype)
        return w_t[:, None] * S + kv

    s_ref[...] = jax.lax.fori_loop(0, chunk, step, s_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
         u: jnp.ndarray, *, chunk: int = 256,
         interpret: bool = True) -> jnp.ndarray:
    """r,k,w: [B, H, T, K]; v: [B, H, T, V]; u: [H, K] -> [B, H, T, V]."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)

    rf = r.reshape(b * h, t, dk)
    kf = k.reshape(b * h, t, dk)
    vf = v.reshape(b * h, t, dv)
    wf = w.reshape(b * h, t, dk)

    grid = (b * h, t // chunk)
    tile_k = pl.BlockSpec((1, chunk, dk), lambda g, c: (g, c, 0))
    tile_v = pl.BlockSpec((1, chunk, dv), lambda g, c: (g, c, 0))
    u_spec = pl.BlockSpec((1, dk), lambda g, c, H=h: (g % H, 0))

    out = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[tile_k, tile_k, tile_v, tile_k, u_spec],
        out_specs=tile_v,
        out_shape=jax.ShapeDtypeStruct((b * h, t, dv), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, u)
    return out.reshape(b, h, t, dv)
