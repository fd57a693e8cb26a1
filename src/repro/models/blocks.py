"""Layer blocks: attention (GQA/RoPE/M-RoPE/SWA), MLP, MoE, Mamba2, RWKV6.

Every block is a pair of pure functions:

    init_<block>(cfg, init)         -> (params, specs)
    apply_<block>(cfg, params, x,…) -> y  (or (y, aux) / (y, new_cache))

Activation sharding follows repro.parallel.sharding logical axes; the
attention/MLP weights are 2-D sharded (tensor dim on "model", fsdp dim on
"data").
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import attention, decode_attention
from repro.kernels.mamba2_ssd.ops import ssd_mix
from repro.kernels.mamba2_ssd.ref import ssd_decode_ref
from repro.kernels.rwkv6_wkv.ops import wkv
from repro.kernels.rwkv6_wkv.ref import wkv6_decode_ref
from repro.parallel.sharding import shard
from .common import (
    Init, apply_mrope, apply_rope, rms_norm, tree_build,
)
from .config import ModelConfig


def _act(name: str):
    return jax.nn.silu if name == "silu" else jax.nn.gelu


def norm_apply(cfg: ModelConfig, p, x):
    if cfg.norm == "layer":
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        return y.astype(x.dtype) * p["scale"] + p["bias"]
    return rms_norm(x, p["scale"])


def init_norm(cfg: ModelConfig, init: Init, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layer":
        return tree_build(scale=init.ones((d,), (None,)),
                          bias=init.zeros((d,), (None,)))
    return tree_build(scale=init.ones((d,), (None,)))


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, init: Init):
    """Attention projections are stored 3-D ([d, H, hd] / [H, hd, d]).

    Keeping the head dim explicit lets the divisibility-aware sharding
    resolver make the right call per arch: a fused [d, H*hd] matrix would
    always "divide" and get column-sharded across head boundaries, forcing
    XLA to re-gather whole Q/K/V tensors when H doesn't divide the model
    axis (qwen1.5's 20 heads, every GQA arch's 8 KV heads).  §Perf d3.
    """
    d, hd = cfg.d_model, cfg.hd
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    entries = dict(
        wq=init.normal((d, hq, hd), ("embed_fsdp", "heads", None)),
        wk=init.normal((d, hkv, hd), ("embed_fsdp", "kv_heads", None)),
        wv=init.normal((d, hkv, hd), ("embed_fsdp", "kv_heads", None)),
        wo=init.normal((hq, hd, d), ("heads", None, "embed_fsdp")),
        norm=init_norm(cfg, init),
    )
    if cfg.qkv_bias:
        entries.update(
            bq=init.zeros((hq, hd), ("heads", None)),
            bk=init.zeros((hkv, hd), ("kv_heads", None)),
            bv=init.zeros((hkv, hd), ("kv_heads", None)),
        )
    return tree_build(**entries)


def _qkv(cfg, p, x):
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"][None, :, None, :]
        k = k + p["bk"][None, :, None, :]
        v = v + p["bv"][None, :, None, :]
    q = shard(q, ("batch", "heads", None, None))
    k = shard(k, ("batch", "kv_heads", None, None))
    v = shard(v, ("batch", "kv_heads", None, None))
    return q, k, v


def _rope_qk(cfg, q, k, positions, mrope_positions=None):
    if cfg.mrope_sections is not None and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections,
                        cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections,
                        cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def apply_attention(cfg: ModelConfig, p, x, *, positions,
                    window: Optional[int] = None, causal: bool = True,
                    mrope_positions=None, kv: Optional[Tuple] = None):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    b, s, d = x.shape
    h = norm_apply(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h)
    if kv is not None:
        k, v = kv                     # cross-attention: encoder KV
    elif positions is not None:
        q, k = _rope_qk(cfg, q, k, positions, mrope_positions)
    o = attention(q, k, v, causal=causal, window=window)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shard(x + out, ("batch", None, None))


def apply_attention_decode(cfg: ModelConfig, p, x, cache, *, window=None):
    """One-token decode step.  x: [B, 1, d]; cache: dict(k, v, length).

    Window layers keep a rolling buffer of size ``window`` (attention is
    permutation-invariant, so ring order is fine — RoPE is applied before
    caching).
    """
    b = x.shape[0]
    h = norm_apply(cfg, p["norm"], x)
    q, k, v = _qkv(cfg, p, h)                    # [B, H, 1, hd]
    length = cache["length"]                     # [] int32 tokens so far
    positions = jnp.full((b, 1), length, jnp.int32)
    q, k = _rope_qk(cfg, q, k, positions)
    smax = cache["k"].shape[2]
    slot = length % smax if window is not None else length
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, 0, slot, 0))
    valid = jnp.minimum(length + 1, smax)
    o = decode_attention(q[:, :, 0], ck, cv,
                         jnp.full((b,), valid, jnp.int32))    # [B, H, hd]
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(o.dtype))[:, None]
    return x + out, {"k": ck, "v": cv, "length": length + 1}


def attn_cache_spec(cfg: ModelConfig, b: int, s: int,
                    window: Optional[int] = None, dtype=jnp.bfloat16):
    smax = min(s, window) if window else s
    shape = (b, cfg.n_kv_heads, smax, cfg.hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype),
            "length": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, init: Init, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    entries = dict(
        w_up=init.normal((d, f), ("embed_fsdp", "mlp")),
        w_down=init.normal((f, d), ("mlp", "embed_fsdp")),
        norm=init_norm(cfg, init),
    )
    if cfg.act in ("silu", "geglu"):
        entries["w_gate"] = init.normal((d, f), ("embed_fsdp", "mlp"))
    return tree_build(**entries)


def apply_mlp(cfg: ModelConfig, p, x):
    h = norm_apply(cfg, p["norm"], x)
    up = h @ p["w_up"]
    if cfg.act == "silu":          # SwiGLU
        up = jax.nn.silu(h @ p["w_gate"]) * up
    elif cfg.act == "geglu":       # gemma GeGLU
        up = jax.nn.gelu(h @ p["w_gate"]) * up
    else:                          # plain GELU (whisper)
        up = jax.nn.gelu(up)
    up = shard(up, ("batch", None, "mlp"))
    return shard(x + up @ p["w_down"], ("batch", None, None))


# ---------------------------------------------------------------------------
# MoE (sort-based capacity dispatch; EP or TP sharding strategy)
#
# Two execution paths:
#   * apply_moe          — single-program dispatch (global argsort +
#     capacity scatter).  Compiles anywhere, but under SPMD the
#     data-dependent scatter/gather forces XLA to replicate the [E, C, d]
#     buffers across the mesh: measured 105 TB of collectives per kimi-k2
#     train step.  Kept as the baseline (EXPERIMENTS.md §Perf).
#   * apply_moe_shardmap — explicit expert parallelism.  Activations are
#     batch-sharded over (pod, data) and *replicated* over "model", while
#     experts are sharded over "model": every model-rank therefore already
#     holds all tokens and exactly E/|model| experts.  Each rank routes
#     locally, keeps only assignments to its own experts, runs its local
#     expert GEMMs, and one psum over "model" combines the partial outputs.
#     No global sort, no scatter resharding, one all-reduce per MoE layer.
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, init: Init):
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    ff_axis = "expert_mlp" if cfg.moe_strategy == "tp" else None
    e_axis = None if cfg.moe_strategy == "tp" else "experts"
    return tree_build(
        router=init.normal((d, e), (None, None)),
        w_gate=init.normal((e, d, f), (e_axis, "embed_fsdp", ff_axis)),
        w_up=init.normal((e, d, f), (e_axis, "embed_fsdp", ff_axis)),
        w_down=init.normal((e, f, d), (e_axis, ff_axis, "embed_fsdp")),
        norm=init_norm(cfg, init),
    )


def apply_moe(cfg: ModelConfig, p, x):
    if cfg.moe_impl == "shardmap":
        from repro.compat import current_mesh
        mesh = current_mesh()
        ok = mesh is not None and "model" in mesh.axis_names and (
            cfg.moe_strategy == "tp"                      # ff-sliced experts
            or cfg.n_experts % mesh.shape["model"] == 0)  # expert-sharded
        if ok:
            return apply_moe_shardmap(cfg, p, x, mesh)
    return apply_moe_spmd(cfg, p, x)


def _moe_local_compute(cfg: ModelConfig, p_local, h, my_rank, e_local):
    """Route ``h`` [t, d] against this rank's ``e_local`` experts; returns
    (partial output [t, d], aux).  Pure local math — no collectives."""
    t, d = h.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = h @ p_local["router"].astype(h.dtype)          # [t, E] (repl.)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    gate_w, idx = jax.lax.top_k(probs, k)                   # [t, k]
    gate_w = gate_w / gate_w.sum(-1, keepdims=True)
    frac = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(frac * probs.mean(0))

    # keep only assignments owned by this rank: local expert id in [0, e_l)
    lo = my_rank * e_local
    flat_e = idx.reshape(-1) - lo                           # [t*k]
    mine = (flat_e >= 0) & (flat_e < e_local)
    capacity = int(t * k // e * cfg.capacity_factor) + 1
    le = jnp.where(mine, flat_e, e_local)                   # trash expert
    order = jnp.argsort(le)                                 # local sort
    sorted_e = le[order]
    counts = jnp.zeros((e_local + 1,), jnp.int32).at[sorted_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank_in_e = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.where((rank_in_e < capacity) & (sorted_e < e_local),
                    rank_in_e, capacity)
    src = order // k
    buf = jnp.zeros((e_local, capacity + 1, d), h.dtype)
    buf = buf.at[jnp.minimum(sorted_e, e_local - 1), pos].set(
        jnp.where((sorted_e < e_local)[:, None], h[src], 0))

    gate = jnp.einsum("ecd,edf->ecf", buf[:, :capacity],
                      p_local["w_gate"].astype(h.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf[:, :capacity],
                    p_local["w_up"].astype(h.dtype))
    y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                     p_local["w_down"].astype(h.dtype))
    y_e = jnp.pad(y_e, ((0, 0), (0, 1), (0, 0)))
    gathered = jnp.where(
        ((sorted_e < e_local) & (pos < capacity))[:, None],
        y_e[jnp.minimum(sorted_e, e_local - 1), pos], 0)
    w_sorted = gate_w.reshape(-1)[order].astype(h.dtype)
    out = jnp.zeros((t, d), h.dtype).at[src].add(
        w_sorted[:, None] * gathered)
    return out, aux


def apply_moe_shardmap(cfg: ModelConfig, p, x, mesh):
    """Explicit MoE parallelism via shard_map + one psum("model")/layer.

    * strategy "ep" (kimi): experts sharded over "model"; each rank routes
      its (replicated) tokens to its own E/|model| experts.
    * strategy "tp" (mixtral, E < |model|): every rank owns ALL experts,
      ff-sliced over "model"; the local expert GEMMs produce partial sums
      over the sliced ff dim, combined by the same psum.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    e = cfg.n_experts
    msize = mesh.shape["model"]
    tp = cfg.moe_strategy == "tp"
    e_local = e if tp else e // msize
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # divisibility: drop batch axes that don't divide b (e.g. decode b=1)
    while batch_axes:
        prod = 1
        for a in batch_axes:
            prod *= mesh.shape[a]
        if b % prod == 0:
            break
        batch_axes = batch_axes[1:]

    def local_fn(router, w_gate, w_up, w_down, norm_scale, x_blk):
        my_rank = 0 if tp else jax.lax.axis_index("model")
        bl, sl, _ = x_blk.shape
        h = rms_norm(x_blk, norm_scale).reshape(bl * sl, d)
        p_local = {"router": router, "w_gate": w_gate, "w_up": w_up,
                   "w_down": w_down}
        out, aux = _moe_local_compute(cfg, p_local, h, my_rank, e_local)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        return x_blk + out.reshape(bl, sl, d), aux

    w_specs = ((P(None, None, "model"), P(None, None, "model"),
                P(None, "model", None)) if tp
               else (P("model"), P("model"), P("model")))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(),) + w_specs + (P(), P(batch_axes or None)),
        out_specs=(P(batch_axes or None), P()),
        check_vma=False)
    y, aux = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"],
                p["norm"]["scale"], x)
    return y, aux


def apply_moe_spmd(cfg: ModelConfig, p, x):
    """Top-k MoE with sort-based capacity dispatch.

    Memory-sane for hundreds of experts: no [T, E, C] one-hot tensors —
    assignments are sorted by expert (global argsort), scattered into an
    [E, C, d] capacity buffer (overflow dropped), processed as batched
    GEMMs with E (EP) or f (TP) sharded over "model", and combined back
    by a weighted scatter-add.

    Returns (y, aux) with the standard load-balance loss.
    """
    b, s, d = x.shape
    e, k, f = cfg.n_experts, cfg.top_k, cfg.expert_d_ff
    t = b * s
    h = norm_apply(cfg, p["norm"], x).reshape(t, d)

    logits = h @ p["router"].astype(h.dtype)               # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    gate_w, idx = jax.lax.top_k(probs, k)                  # [T, k]
    gate_w = gate_w / gate_w.sum(-1, keepdims=True)

    # load-balance aux (Switch): E * mean_e(frac_tokens_e * mean_prob_e)
    frac = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(frac * probs.mean(0))

    capacity = int(t * k // e * cfg.capacity_factor) + 1
    flat_e = idx.reshape(-1)                               # [T*k]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[sorted_e].add(1)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.where(rank < capacity, rank, capacity)       # overflow slot
    src = order // k                                       # token index

    # strategy-dependent logical axes: EP shards the expert dim, TP the
    # within-expert ff dim (both land on "model"; never both at once)
    e_ax = "experts" if cfg.moe_strategy == "ep" else None
    f_ax = "expert_mlp" if cfg.moe_strategy == "tp" else None
    buf = jnp.zeros((e, capacity + 1, d), h.dtype)
    buf = buf.at[sorted_e, pos].set(h[src])
    buf = shard(buf[:, :capacity], (e_ax, None, None))

    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(h.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(h.dtype))
    act = shard(jax.nn.silu(gate) * up, (e_ax, None, f_ax))
    y_e = jnp.einsum("ecf,efd->ecd", act, p["w_down"].astype(h.dtype))
    y_e = shard(y_e, (e_ax, None, None))
    y_e = jnp.pad(y_e, ((0, 0), (0, 1), (0, 0)))           # overflow reads 0

    gathered = y_e[sorted_e, pos]                          # [T*k, d]
    w_sorted = gate_w.reshape(-1)[order].astype(h.dtype)
    out = jnp.zeros((t, d), h.dtype).at[src].add(
        w_sorted[:, None] * gathered)
    out = shard(out.reshape(b, s, d), ("batch", None, None))
    return x + out, aux


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 backbone)
# ---------------------------------------------------------------------------

def init_mamba2(cfg: ModelConfig, init: Init):
    d = cfg.d_model
    h = cfg.ssm_heads
    p_dim = cfg.ssm_head_dim          # inner = H * P (zamba2: expand 2x)
    g, n = cfg.ssm_groups, cfg.ssm_state
    inner = h * p_dim
    return tree_build(
        w_in=init.normal((d, 2 * inner + 2 * g * n + h),
                         ("embed_fsdp", "mlp")),
        conv_w=init.normal((cfg.conv_kernel, inner + 2 * g * n), (None, None)),
        A_log=init.zeros((h,), (None,)),
        D=init.ones((h,), (None,)),
        dt_bias=init.zeros((h,), (None,)),
        norm=init_norm(cfg, init),
        gate_norm=init_norm(cfg, init, inner),
        w_out=init.normal((inner, d), ("mlp", "embed_fsdp")),
    )


def _causal_conv(x, w, state=None):
    """Depthwise causal conv.  x: [B, S, C]; w: [K, C].

    Returns (y, new_state) where state is the last K-1 inputs."""
    k = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    ys = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return ys, xp[:, -(k - 1):]


def _mamba_split(cfg, p, x):
    g, n = cfg.ssm_groups, cfg.ssm_state
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    zxbcdt = x @ p["w_in"]
    return jnp.split(zxbcdt, [inner, 2 * inner, 2 * inner + g * n,
                              2 * inner + 2 * g * n], axis=-1)


def apply_mamba2(cfg: ModelConfig, p, x):
    b, s, d = x.shape
    h_heads, g, n = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    p_dim = cfg.ssm_head_dim
    hidden = norm_apply(cfg, p["norm"], x)
    z, xc, Bc, Cc, dt = _mamba_split(cfg, p, hidden)
    conv_in = jnp.concatenate([xc, Bc, Cc], -1)
    conv_out, _ = _causal_conv(conv_in, p["conv_w"])
    conv_out = jax.nn.silu(conv_out)
    xc, Bc, Cc = jnp.split(conv_out, [xc.shape[-1],
                                      xc.shape[-1] + Bc.shape[-1]], -1)
    xh = xc.reshape(b, s, h_heads, p_dim)
    Bm = Bc.reshape(b, s, g, n)
    Cm = Cc.reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [B,S,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssd_mix(xh, dt, A, Bm, Cm)                           # [B,S,H,P]
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(b, s, h_heads * p_dim)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"]["scale"])
    return shard(x + y @ p["w_out"], ("batch", None, None))


def apply_mamba2_decode(cfg: ModelConfig, p, x, cache):
    """x: [B, 1, d]; cache: dict(conv [B,K-1,C], ssm [B,H,N,P])."""
    b, _, d = x.shape
    h_heads, g, n = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    p_dim = cfg.ssm_head_dim
    hidden = norm_apply(cfg, p["norm"], x)
    z, xc, Bc, Cc, dt = _mamba_split(cfg, p, hidden)
    conv_in = jnp.concatenate([xc, Bc, Cc], -1)
    conv_out, conv_state = _causal_conv(conv_in, p["conv_w"], cache["conv"])
    conv_out = jax.nn.silu(conv_out)
    xc, Bc, Cc = jnp.split(conv_out, [xc.shape[-1],
                                      xc.shape[-1] + Bc.shape[-1]], -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, ssm = ssd_decode_ref(xc.reshape(b, h_heads, p_dim),
                            dt.reshape(b, h_heads), A,
                            Bc.reshape(b, g, n), Cc.reshape(b, g, n),
                            cache["ssm"])
    y = y + p["D"][None, :, None] * xc.reshape(b, h_heads, p_dim)
    y = y.reshape(b, 1, h_heads * p_dim)
    y = rms_norm(y * jax.nn.silu(z), p["gate_norm"]["scale"])
    return x + y @ p["w_out"], {"conv": conv_state, "ssm": ssm}


def mamba_cache_spec(cfg: ModelConfig, b: int, dtype=jnp.bfloat16):
    h = cfg.ssm_heads
    p_dim = cfg.ssm_head_dim
    c = h * p_dim + 2 * cfg.ssm_groups * cfg.ssm_state
    return {"conv": jax.ShapeDtypeStruct((b, cfg.conv_kernel - 1, c), dtype),
            "ssm": jax.ShapeDtypeStruct((b, h, cfg.ssm_state, p_dim),
                                        jnp.float32)}


# ---------------------------------------------------------------------------
# RWKV6 block
# ---------------------------------------------------------------------------

def init_rwkv6(cfg: ModelConfig, init: Init):
    d = cfg.d_model
    lora = 32
    return tree_build(
        norm_t=init_norm(cfg, init),
        norm_c=init_norm(cfg, init),
        mu=init.normal((5, d), (None, None), std=0.2),     # r,k,v,w,g shifts
        wr=init.normal((d, d), ("embed_fsdp", "heads")),
        wk=init.normal((d, d), ("embed_fsdp", "heads")),
        wv=init.normal((d, d), ("embed_fsdp", "heads")),
        wg=init.normal((d, d), ("embed_fsdp", "heads")),
        w_base=init.zeros((d,), (None,)),
        w_lora_a=init.normal((d, lora), (None, None)),
        w_lora_b=init.normal((lora, d), (None, None)),
        bonus=init.normal((cfg.d_model // cfg.rwkv_head_dim,
                           cfg.rwkv_head_dim), (None, None)),
        ln_x=init.ones((d,), (None,)),
        wo=init.normal((d, d), ("heads", "embed_fsdp")),
        mu_c=init.normal((2, d), (None, None), std=0.2),   # channel-mix
        ck=init.normal((d, cfg.d_ff), ("embed_fsdp", "mlp")),
        cv=init.normal((cfg.d_ff, d), ("mlp", "embed_fsdp")),
        cr=init.normal((d, d), ("embed_fsdp", None)),
    )


def _token_shift(x, last):
    """prev-token stream: [last, x_0 .. x_{S-2}]."""
    return jnp.concatenate([last[:, None], x[:, :-1]], axis=1)


def _rwkv_time_mix(cfg, p, x, x_prev, state=None):
    b, s, d = x.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd
    mix = lambda i: x + (x_prev - x) * p["mu"][i]
    r = mix(0) @ p["wr"]
    k = mix(1) @ p["wk"]
    v = mix(2) @ p["wv"]
    w_in = mix(3)
    g = mix(4) @ p["wg"]
    w = p["w_base"] + jnp.tanh(w_in @ p["w_lora_a"]) @ p["w_lora_b"]
    w = jnp.exp(-jnp.exp(w.astype(jnp.float32))).astype(x.dtype)

    def heads(t):
        return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)

    if state is None:
        y = wkv(heads(r), heads(k), heads(v), heads(w), p["bonus"])
        new_state = None
    else:
        y, new_state = wkv6_decode_ref(
            r.reshape(b, nh, hd), k.reshape(b, nh, hd),
            v.reshape(b, nh, hd), w.reshape(b, nh, hd), p["bonus"], state)
        y = y[:, None].reshape(b, 1, nh, hd).transpose(0, 2, 1, 3)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, d)
    y = rms_norm(y, p["ln_x"]) * jax.nn.silu(g)
    return y @ p["wo"], new_state


def _rwkv_channel_mix(cfg, p, x, x_prev):
    mix = lambda i: x + (x_prev - x) * p["mu_c"][i]
    k = jnp.square(jax.nn.relu(mix(0) @ p["ck"]))
    r = jax.nn.sigmoid(mix(1) @ p["cr"])
    return r * (k @ p["cv"])


def apply_rwkv6(cfg: ModelConfig, p, x):
    h = norm_apply(cfg, p["norm_t"], x)
    last = jnp.zeros_like(h[:, 0])
    y, _ = _rwkv_time_mix(cfg, p, h, _token_shift(h, last))
    x = x + y
    h2 = norm_apply(cfg, p["norm_c"], x)
    x = x + _rwkv_channel_mix(cfg, p, h2, _token_shift(h2, last))
    return shard(x, ("batch", None, None))


def apply_rwkv6_decode(cfg: ModelConfig, p, x, cache):
    """cache: dict(last_t, last_c [B,d], wkv [B,H,K,V])."""
    h = norm_apply(cfg, p["norm_t"], x)
    y, wkv_state = _rwkv_time_mix(cfg, p, h, cache["last_t"][:, None],
                                  state=cache["wkv"])
    x = x + y
    h2 = norm_apply(cfg, p["norm_c"], x)
    x = x + _rwkv_channel_mix(cfg, p, h2, cache["last_c"][:, None])
    new = {"last_t": h[:, 0], "last_c": h2[:, 0], "wkv": wkv_state}
    return x, new


def rwkv_cache_spec(cfg: ModelConfig, b: int, dtype=jnp.bfloat16):
    d, hd = cfg.d_model, cfg.rwkv_head_dim
    nh = d // hd
    return {"last_t": jax.ShapeDtypeStruct((b, d), dtype),
            "last_c": jax.ShapeDtypeStruct((b, d), dtype),
            "wkv": jax.ShapeDtypeStruct((b, nh, hd, hd), jnp.float32)}
