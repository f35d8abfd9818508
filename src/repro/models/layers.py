"""Core NN layers: norms, RoPE, attention (naive + blockwise), FFNs.

Pure functions over param dicts. Shapes use the convention
  x: [B, S, d_model]   q: [B, T, nq, h]   k/v: [B, S, nkv, h]

The attention mask is always derived from *positions* (``q_pos``/``kv_pos``)
so the same code path serves training (arange positions), prefill, decode
against a ring-buffer KV cache (stored absolute positions, -1 = empty slot),
and sliding windows.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -2.0e38  # fp32-safe


# ---------------------------------------------------------------- norms ----
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ----------------------------------------------------------------- rope ----
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, n, h]; positions: [S] or [B, S] (absolute token positions)."""
    dtype = x.dtype
    h = x.shape[-1]
    freqs = rope_freqs(h, theta)                            # [h/2]
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]   # [S, h/2]
        ang = ang[None, :, None, :]                                     # [1,S,1,h/2]
    else:
        ang = positions.astype(jnp.float32)[..., None] * freqs          # [B,S,h/2]
        ang = ang[:, :, None, :]                                        # [B,S,1,h/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ------------------------------------------------------------ attention ----
def _mask(q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
          window: Optional[int]) -> jax.Array:
    """Boolean mask [*, T, S]; True = attend. kv_pos == -1 marks empty slots."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    m = kp >= 0
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= (qp - kp) < window
    return m


def _sdpa(q, k, v, mask, scale):
    """q:[B,T,nq,h] k,v:[B,S,nkv,h] mask:[B?,T,S] -> [B,T,nq,h]."""
    B, T, nq, h = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qh = q.reshape(B, T, nkv, g, h)
    scores = jnp.einsum("btkgh,bskh->bkgts", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    while mask.ndim < 3:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, T, nq, h).astype(v.dtype)


def _blockwise_sdpa(q, k, v, q_pos, kv_pos, causal, window, scale,
                    block_kv: int):
    """Flash-style online-softmax scan over KV blocks. Memory O(T * block_kv)."""
    B, T, nq, h = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    nb = -(-S // block_kv)
    pad = nb * block_kv - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, pad),), constant_values=-1)
    kb = k.reshape(B, nb, block_kv, nkv, h).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_kv, nkv, h).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(nb, block_kv)
    qh = q.reshape(B, T, nkv, g, h).astype(jnp.float32)

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kc, vc, pc = blk                                   # [B,bk,nkv,h], [bk]
        s = jnp.einsum("btkgh,bskh->bkgts", qh, kc.astype(jnp.float32)) * scale
        msk = _mask(q_pos, pc, causal, window)             # [T, bk]
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p, vc.astype(jnp.float32))
        return (m_cur, l_cur, acc), ()

    m0 = jnp.full((B, nkv, g, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nkv, g, T), jnp.float32)
    a0 = jnp.zeros((B, nkv, g, T, h), jnp.float32)
    # remat each KV block: without this, the backward pass of the scan saves
    # the per-block probability tensors — i.e. the full S×S score matrix.
    step = jax.checkpoint(step, prevent_cse=False)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, nq, h).astype(v.dtype)


def seq_parallel_attention(q, k, v, *, causal: bool, window: Optional[int],
                           impl: str, block_kv: int) -> jax.Array:
    """Context-parallel self-attention: shard the QUERY sequence over the
    'model' axis (k/v replicated) via shard_map.

    Used when n_kv_heads doesn't divide the model axis — head-sharding would
    pad 3→16 KV heads (≈5× wasted MXU work on e.g. SmolLM) and emit reshard
    copies. Sequence rows split exactly, so per-chip FLOPs are the ideal
    1/|model| share. K/V per chip is tiny for exactly these few-head models.
    """
    from repro.runtime import pspec as PS
    mesh = PS.active_mesh()
    spec_q = PS.resolve(("batch", "seq_model", None, None), shape=q.shape)
    spec_kv = PS.resolve(("batch", None, None, None), shape=k.shape)
    model_ax = spec_q[1]
    S = q.shape[1]

    def local(ql, kl, vl):
        r = jax.lax.axis_index(model_ax)
        Sl = ql.shape[1]
        q_start = r * Sl
        q_pos = q_start + jnp.arange(Sl)
        S_kv = kl.shape[1]
        scale = 1.0 / math.sqrt(ql.shape[-1])
        if (window is not None and causal and Sl + window < S_kv):
            # sliding-window band: this rank's queries can only see
            # [q_start - window + 1, q_start + Sl); slice that band out of
            # the replicated K/V (dynamic start, static size) instead of
            # attending the full sequence — 3.2× fewer window-layer FLOPs
            # at train_4k, 10.7× at prefill_32k (gemma-3 geometry).
            band = Sl + window
            start = jnp.clip(q_start - window, 0, S_kv - band)
            kb = lax.dynamic_slice(kl, (0, start, 0, 0),
                                   (kl.shape[0], band) + kl.shape[2:])
            vb = lax.dynamic_slice(vl, (0, start, 0, 0),
                                   (vl.shape[0], band) + vl.shape[2:])
            kv_pos = start + jnp.arange(band)
            if impl == "naive" or band <= block_kv:
                return _sdpa(ql, kb, vb,
                             _mask(q_pos, kv_pos, causal, window), scale)
            return _blockwise_sdpa(ql, kb, vb, q_pos, kv_pos, causal,
                                   window, scale, block_kv)
        kv_pos = jnp.arange(S_kv)
        if impl == "naive" or S_kv <= block_kv:
            return _sdpa(ql, kl, vl, _mask(q_pos, kv_pos, causal, window),
                         scale)
        return _blockwise_sdpa(ql, kl, vl, q_pos, kv_pos, causal, window,
                               scale, block_kv)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(spec_q, spec_kv, spec_kv),
                         out_specs=spec_q, check_vma=False)(q, k, v)


def use_seq_parallel(q, k) -> bool:
    """Active when the run's rules replicate attention heads over 'model'
    (pspec.seq_attn_rules — chosen per cell when KV-head padding would be
    ≥2×; see runtime.steps.lower_cell). Measured on arctic-480b: −22%
    t_coll, −62% temp vs padded head sharding."""
    from repro.runtime import pspec as PS
    if PS.active_mesh() is None:
        return False
    if PS.logical_axis_size("heads") != 1:
        return False                       # heads are model-sharded: TP path
    n_model = PS.logical_axis_size("seq_model")
    if n_model <= 1:
        return False
    S, T = k.shape[1], q.shape[1]
    return T == S and S % n_model == 0


def attention(q, k, v, *, q_pos, kv_pos, causal: bool = True,
              window: Optional[int] = None, impl: str = "blockwise",
              block_kv: int = 1024) -> jax.Array:
    """Grouped-query attention; see module docstring for shapes."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    T, S = q.shape[1], k.shape[1]
    if impl == "pallas" and T > 1 and T == S:
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal, window)
    if T == 1 or impl == "naive" or S <= block_kv:
        return _sdpa(q, k, v, _mask(q_pos, kv_pos, causal, window), scale)
    return _blockwise_sdpa(q, k, v, q_pos, kv_pos, causal, window, scale,
                           block_kv)


def attention_projections(params, x, *, n_heads, n_kv_heads, head_dim):
    """x:[B,S,d] -> q:[B,S,nq,h], k,v:[B,S,nkv,h] using fused wqkv."""
    B, S, _ = x.shape
    qkv = x @ params["wqkv"].astype(x.dtype)
    if "bqkv" in params:
        qkv = qkv + params["bqkv"].astype(x.dtype)
    q_sz = n_heads * head_dim
    kv_sz = n_kv_heads * head_dim
    q, k, v = jnp.split(qkv, [q_sz, q_sz + kv_sz], axis=-1)
    return (q.reshape(B, S, n_heads, head_dim),
            k.reshape(B, S, n_kv_heads, head_dim),
            v.reshape(B, S, n_kv_heads, head_dim))


# ----------------------------------------------------------------- ffn -----
def ffn(params, x, *, gated: bool = True) -> jax.Array:
    if gated:
        h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (
            x @ params["wu"].astype(x.dtype))
    else:
        h = jax.nn.gelu(x @ params["wu"].astype(x.dtype))
    return h @ params["wd"].astype(x.dtype)
