"""Shared neural-net layers: norms, rotary embeddings, GQA attention.

All functions are pure; parameters are plain arrays.  Attention is
implemented *blockwise* (online-softmax over KV chunks, flash-attention
style) so that prefill over 32k+ sequences never materializes an S×S score
matrix — this keeps the dry-run memory analysis honest and is the same
tiling the Pallas kernels use on TPU.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "rmsnorm",
    "rope",
    "apply_rope",
    "blockwise_attention",
    "decode_attention",
    "chunk_attention",
    "glu",
    "softplus",
]


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            gemma_style: bool = False) -> jax.Array:
    """RMSNorm with float32 statistics; gemma_style scales by (1 + w)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if gemma_style else w.astype(jnp.float32)
    return (y * scale).astype(dt)


def softplus(x: jax.Array) -> jax.Array:
    """``log(1 + e^x)`` in float32, branch-free and within a few ulp of
    ``e^-|x|`` on every backend: ``max(x, 0) + log1p(u)`` with
    ``u = e^-|x|`` in (0, 1] and ``log1p(u) = 2 atanh(s)``,
    ``s = u / (2 + u)`` <= 1/3, summed as its odd series to ``s^17``.

    ``jax.nn.softplus`` goes through the backend's ``log1p``, which on a
    TPU v5e is off by up to 1e-4 (2.6e-4 relative) near ``|x| = ln 2``
    and jumps there: two f32 computations whose ``x`` differ by an ulp
    can then take dt apart by 1e-4, and the SSM state carries that on."""
    x = x.astype(jnp.float32)
    u = jnp.exp(-jnp.abs(x))
    s = u / (2.0 + u)
    s2 = s * s
    acc = jnp.full_like(s, 1.0 / 17.0)
    for k in range(15, 0, -2):
        acc = acc * s2 + 1.0 / k
    return jnp.maximum(x, 0.0) + 2.0 * s * acc


def glu(h: jax.Array, activation: str = "silu") -> jax.Array:
    """Fused gate/up projection output (..., 2F) -> activated (..., F)."""
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    return act(gate) * up


# ---------------------------------------------------------------------------
# Rotary position embeddings (NeoX-style halves; optional M-RoPE sections).
# ---------------------------------------------------------------------------


def rope(positions: jax.Array, head_dim: int, theta: float,
         mrope_sections: Optional[Tuple[int, int, int]] = None
         ) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for ``positions``.

    positions: (..., ) int32 for standard RoPE, or (..., 3) for M-RoPE
    (temporal/height/width).  Returns cos, sin of shape (..., head_dim//2).
    """
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if mrope_sections is None:
        ang = positions.astype(jnp.float32)[..., None] * inv_freq
    else:
        assert positions.shape[-1] == len(mrope_sections)
        parts = []
        start = 0
        for i, sec in enumerate(mrope_sections):
            parts.append(
                positions[..., i : i + 1].astype(jnp.float32)
                * inv_freq[start : start + sec]
            )
            start += sec
        assert start == half, "mrope sections must cover head_dim//2"
        ang = jnp.concatenate(parts, axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate (..., n_heads, head_dim) by per-position cos/sin (..., hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # broadcast over the heads axis
    s = sin[..., None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise causal GQA attention (training / prefill).
# ---------------------------------------------------------------------------


def blockwise_attention(
    q: jax.Array,          # (B, S, H, hd)
    k: jax.Array,          # (B, S, KV, hd)
    v: jax.Array,          # (B, S, KV, hd)
    *,
    scale: Optional[float] = None,
    chunk: int = 512,
    causal: bool = True,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention over KV chunks; O(S·chunk) memory.

    GQA: H = KV * G query heads share each KV head.  The KV sequence is
    scanned in ``chunk``-sized blocks; running max / sum / accumulator are
    carried exactly as in FlashAttention (and in our Pallas kernel).
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    chunk = min(chunk, s)
    n_chunks = s // chunk if s % chunk == 0 else -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qg = q.reshape(b, s, kvh, g, hd).astype(jnp.float32) * scale
    kc = k.reshape(b, n_chunks, chunk, kvh, hd)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd)

    q_idx = jnp.arange(s)

    def step(carry, inp):
        m, l, acc = carry
        ci, k_blk, v_blk = inp
        # logits: (B, S, KV, G, chunk)
        logits = jnp.einsum(
            "bskgd,bckd->bskgc", qg, k_blk.astype(jnp.float32))
        k_idx = ci * chunk + jnp.arange(chunk)
        valid = k_idx < s
        if causal:
            valid = valid[None, :] & (k_idx[None, :] <= q_idx[:, None])
            logits = jnp.where(valid[None, :, None, None, :], logits, -jnp.inf)
        else:
            logits = jnp.where(valid[None, None, None, None, :], logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # guard fully-masked rows (m_new == -inf) against NaN
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, kvh, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s, kvh, g), jnp.float32)
    acc0 = jnp.zeros((b, s, kvh, g, hd), jnp.float32)
    if unroll:
        carry = (m0, l0, acc0)
        for ci in range(n_chunks):
            carry, _ = step(carry, (jnp.int32(ci), kc[:, ci], vc[:, ci]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            step,
            (m0, l0, acc0),
            (jnp.arange(n_chunks), kc.swapaxes(0, 1), vc.swapaxes(0, 1)),
        )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, s, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (one new token against a KV cache).
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,          # (B, H, hd) — the new token's queries
    k_cache: jax.Array,    # (B, S, KV, hd) — cache incl. the new token's K
    v_cache: jax.Array,    # (B, S, KV, hd)
    seq_lens: jax.Array,   # (B,) int32: live length incl. the new token
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Masked softmax attention of one query token over the cache.

    Works unchanged when the cache's S axis is sharded (flash-decoding):
    the softmax max/sum reductions become cross-shard collectives under
    GSPMD, which is exactly the split-KV decode scheme.
    """
    b, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, kvh, g, hd).astype(jnp.float32) * scale
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    mask = jnp.arange(k_cache.shape[1])[None, :] < seq_lens[:, None]  # (B, S)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunk attention (N new tokens against a KV cache — chunked prefill).
# ---------------------------------------------------------------------------


def chunk_attention(
    q: jax.Array,          # (B, N, H, hd) — the chunk's queries
    k_cache: jax.Array,    # (B, S, KV, hd) — cache incl. the chunk's K
    v_cache: jax.Array,    # (B, S, KV, hd)
    seq_lens: jax.Array,   # (B,) int32: live length *before* the chunk
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Masked softmax attention of N chunk queries over the cache.

    Chunk position ``i`` of request ``b`` sits at absolute position
    ``seq_lens[b] + i`` and may attend to cache entries ``< seq_lens[b]
    + i + 1`` — history plus the causal prefix of its own chunk (whose
    K/V have already been written into the cache).  For ``N == 1`` this
    is exactly ``decode_attention``; the same exact (non-online) softmax
    keeps chunked prefill numerically aligned with token-by-token decode.
    """
    b, n, h, hd = q.shape
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, n, kvh, g, hd).astype(jnp.float32) * scale
    logits = jnp.einsum("bnkgd,bskd->bnkgs", qg,
                        k_cache.astype(jnp.float32))
    # (B, N, S): key position < seq_lens[b] + i + 1
    lim = seq_lens[:, None] + jnp.arange(n)[None, :] + 1
    mask = jnp.arange(k_cache.shape[1])[None, None, :] < lim[:, :, None]
    logits = jnp.where(mask[:, :, None, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bnkgs,bskd->bnkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(jnp.float32))
    return out.reshape(b, n, h, hd).astype(q.dtype)
