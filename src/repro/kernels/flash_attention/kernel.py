"""Pallas TPU flash attention (forward): GQA + causal + sliding window.

Blocked online-softmax attention (Rabe-Staats / FlashAttention) adapted to
the TPU memory hierarchy:

* grid = (batch*heads, Sq/BQ); each step holds one [BQ, D] query tile and
  the running (m, l, acc) in VMEM/VREGs,
* the key/value stream is tiled [BK, D] and walked with ``fori_loop``;
  blocks fully outside the causal/window band are skipped by clamping the
  loop bounds (this is where the SWA/local savings come from — a window of
  W keys touches ceil(W/BK)+1 blocks regardless of sequence length),
* MXU work is the [BQ, D] x [D, BK] logits matmul and the [BQ, BK] x
  [BK, D] value matmul; accumulation in f32.

Block sizes default to (BQ, BK) = (128, 128) — MXU-aligned and small
enough that q/k/v tiles + f32 accumulators stay well under VMEM budget
even at D = 256 (gemma3's head_dim).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
               window: Optional[int], bq: int, bk: int, sk: int,
               q_offset: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                    # [BQ, D]
    d = q.shape[-1]

    q_lo = qi * bq + q_offset                           # first query position
    q_hi = q_lo + bq - 1                                # last query position

    # key-block range actually intersecting the mask band
    hi = (q_hi // bk) + 1 if causal else sk // bk
    hi = jnp.minimum(hi, sk // bk) if causal else hi
    if window is not None:
        lo = jnp.maximum((q_lo - window + 1) // bk, 0)
    else:
        lo = 0

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * bk, bk)].astype(jnp.float32)   # [BK, D]
        v = v_ref[0, pl.ds(j * bk, bk)].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # [BQ, BK]
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_new = jnp.maximum(m, logits.max(-1))          # [BQ]
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "scale", "bq", "bk",
                              "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True) -> jnp.ndarray:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D].  GQA via head folding:
    each kv head serves Hq/Hkv query heads; we index kv by hq // group."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert hq % hkv == 0 and sq % bq == 0 and sk % bk == 0, \
        (q.shape, k.shape, bq, bk)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    q_offset = sk - sq          # queries sit at the end of the key timeline

    q4 = q.reshape(b * hq, sq, d)
    k4 = k.reshape(b * hkv, sk, d)
    v4 = v.reshape(b * hkv, sk, d)

    grid = (b * hq, sq // bq)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sk=sk, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i, g=group: (h // g, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda h, i, g=group: (h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        interpret=interpret,
    )(q4, k4, v4)
    return out.reshape(b, hq, sq, d)
