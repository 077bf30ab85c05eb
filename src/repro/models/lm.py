"""Unified LM assembly for every assigned architecture family.

One parameter/step implementation covers dense GQA transformers, MoE
transformers, Mamba2 (SSM), Jamba-style hybrids and modality-stub
backbones.  Layers are grouped into *blocks* of ``cfg.block_period``
layers with identical structure, and the model scans over stacked block
parameters — this keeps the lowered HLO small (one block body) even for
80-layer 110B-parameter configs, which is what makes the 512-device
dry-run compile quickly.

Sharding is injected through a ``policy`` object (see
``distributed.sharding.ShardingPolicy``); with ``policy=None`` everything
runs unsharded (smoke tests, oracle comparisons).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import (apply_rope, blockwise_attention, chunk_attention,
                     rmsnorm, rope)
from .moe import moe_ffn
from .ssm import ssm_decode, ssm_prefill

__all__ = [
    "block_structure", "init_params", "init_cache", "forward",
    "train_loss", "prefill_step", "prefill_chunk", "serve_step",
]


# ---------------------------------------------------------------------------
# Block structure: positions of each layer kind within one scan block.
# ---------------------------------------------------------------------------


def block_structure(cfg) -> Dict[str, Any]:
    """Per-block layer layout: which positions are attn/ssm and mlp/moe."""
    p = cfg.block_period
    attn_pos = [i for i in range(p) if cfg.layer_kind(i) == "attn"]
    ssm_pos = [i for i in range(p) if cfg.layer_kind(i) == "ssm"]
    mlp_pos = [i for i in range(p) if cfg.ffn_kind(i) == "mlp"]
    moe_pos = [i for i in range(p) if cfg.ffn_kind(i) == "moe"]
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return {
        "period": p,
        "n_blocks": cfg.n_layers // p,
        "attn_pos": attn_pos,
        "ssm_pos": ssm_pos,
        "mlp_pos": mlp_pos,
        "moe_pos": moe_pos,
    }


# ---------------------------------------------------------------------------
# Parameter initialization (stacked over blocks for lax.scan).
# ---------------------------------------------------------------------------


def _norm(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg, key: jax.Array, dtype=jnp.bfloat16) -> Dict[str, Any]:
    st = block_structure(cfg)
    nb = st["n_blocks"]
    d, hd = cfg.d_model, cfg.hd
    keys = iter(jax.random.split(key, 64))
    nk = lambda: next(keys)

    params: Dict[str, Any] = {}
    if not cfg.embed_input:
        params["embed"] = _norm(nk(), (cfg.vocab, d), dtype, 0.02)
    elif cfg.tie_embeddings:
        raise ValueError("tied embeddings require an embedding table")
    if not cfg.tie_embeddings:
        params["lm_head"] = _norm(nk(), (d, cfg.vocab), dtype, d**-0.5)
    params["final_ln"] = jnp.ones((d,), dtype)

    blocks: Dict[str, Any] = {}
    if st["attn_pos"]:
        na = len(st["attn_pos"])
        qd, kvd = cfg.n_heads * hd, cfg.n_kv_heads * hd
        attn = {
            "ln": jnp.ones((nb, na, d), dtype),
            "wq": _norm(nk(), (nb, na, d, qd), dtype, d**-0.5),
            "wk": _norm(nk(), (nb, na, d, kvd), dtype, d**-0.5),
            "wv": _norm(nk(), (nb, na, d, kvd), dtype, d**-0.5),
            "wo": _norm(nk(), (nb, na, qd, d), dtype, qd**-0.5),
        }
        if cfg.qkv_bias:
            attn["bq"] = jnp.zeros((nb, na, qd), dtype)
            attn["bk"] = jnp.zeros((nb, na, kvd), dtype)
            attn["bv"] = jnp.zeros((nb, na, kvd), dtype)
        blocks["attn"] = attn
    if st["ssm_pos"]:
        ns = len(st["ssm_pos"])
        din, nh, ng, n = (cfg.d_inner, cfg.ssm_nheads, cfg.ssm_ngroups,
                          cfg.ssm_state)
        gn = ng * n
        w = cfg.ssm_conv
        blocks["ssm"] = {
            "ln": jnp.ones((nb, ns, d), dtype),
            "zproj": _norm(nk(), (nb, ns, d, din), dtype, d**-0.5),
            "xproj": _norm(nk(), (nb, ns, d, din), dtype, d**-0.5),
            "bproj": _norm(nk(), (nb, ns, d, gn), dtype, d**-0.5),
            "cproj": _norm(nk(), (nb, ns, d, gn), dtype, d**-0.5),
            "dtproj": _norm(nk(), (nb, ns, d, nh), dtype, d**-0.5),
            "conv_wx": _norm(nk(), (nb, ns, w, din), dtype, 0.2),
            "conv_bx": jnp.zeros((nb, ns, din), dtype),
            "conv_wb": _norm(nk(), (nb, ns, w, gn), dtype, 0.2),
            "conv_bb": jnp.zeros((nb, ns, gn), dtype),
            "conv_wc": _norm(nk(), (nb, ns, w, gn), dtype, 0.2),
            "conv_bc": jnp.zeros((nb, ns, gn), dtype),
            "A_log": jnp.zeros((nb, ns, nh), jnp.float32),
            "D_skip": jnp.ones((nb, ns, nh), jnp.float32),
            "dt_bias": jnp.zeros((nb, ns, nh), jnp.float32),
            "gnorm": jnp.ones((nb, ns, din), dtype),
            "out_proj": _norm(nk(), (nb, ns, din, d), dtype, din**-0.5),
        }
    if st["mlp_pos"]:
        nm = len(st["mlp_pos"])
        f = cfg.d_ff
        blocks["mlp"] = {
            "ln": jnp.ones((nb, nm, d), dtype),
            "wi": _norm(nk(), (nb, nm, d, 2, f), dtype, d**-0.5),
            "wo": _norm(nk(), (nb, nm, f, d), dtype, f**-0.5),
        }
    if st["moe_pos"]:
        ne = len(st["moe_pos"])
        e, fe = cfg.n_experts, (cfg.moe_d_ff or cfg.d_ff)
        moe = {
            "ln": jnp.ones((nb, ne, d), dtype),
            "router": _norm(nk(), (nb, ne, d, e), jnp.float32, d**-0.5),
            "w1": _norm(nk(), (nb, ne, e, d, 2, fe), dtype, d**-0.5),
            "w2": _norm(nk(), (nb, ne, e, fe, d), dtype, fe**-0.5),
        }
        if cfg.n_shared_experts:
            fs = fe * cfg.n_shared_experts
            moe["shared_wi"] = _norm(nk(), (nb, ne, d, 2, fs), dtype, d**-0.5)
            moe["shared_wo"] = _norm(nk(), (nb, ne, fs, d), dtype, fs**-0.5)
        blocks["moe"] = moe
    params["blocks"] = blocks
    return params


def init_cache(cfg, batch: int, max_seq: int, dtype=jnp.bfloat16,
               kv_dtype=None) -> Dict[str, jax.Array]:
    """Decode caches, stacked (n_blocks, per_block, ...) for lax.scan.

    ``kv_dtype`` overrides the K/V element type only (fp8 quantized cache);
    conv states stay ``dtype`` and SSD states stay f32."""
    st = block_structure(cfg)
    nb = st["n_blocks"]
    cache: Dict[str, jax.Array] = {}
    if st["attn_pos"]:
        na = len(st["attn_pos"])
        cache["k"] = jnp.zeros((nb, na, batch, max_seq, cfg.n_kv_heads,
                                cfg.hd), kv_dtype or dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
    if st["ssm_pos"]:
        ns = len(st["ssm_pos"])
        gn = cfg.ssm_ngroups * cfg.ssm_state
        w = cfg.ssm_conv
        cache["conv_x"] = jnp.zeros((nb, ns, batch, w, cfg.d_inner), dtype)
        cache["conv_b"] = jnp.zeros((nb, ns, batch, w, gn), dtype)
        cache["conv_c"] = jnp.zeros((nb, ns, batch, w, gn), dtype)
        cache["ssm"] = jnp.zeros((nb, ns, batch, cfg.ssm_nheads,
                                  cfg.ssm_head_dim, cfg.ssm_state),
                                 jnp.float32)
    return cache


# ---------------------------------------------------------------------------
# Per-position sub-layer helpers.
# ---------------------------------------------------------------------------


def _take(tree, idx: int):
    return jax.tree.map(lambda a: a[idx], tree)


def _attn_seq(h, p, cfg, cos, sin, policy, unroll=False):
    """Attention sub-layer over a full sequence.  h (B, S, D)."""
    b, s, d = h.shape
    x = rmsnorm(h, p["ln"], cfg.norm_eps, cfg.gemma_norm)
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if policy:
        q = policy.act(q, "qkv")
        k, v = policy.act(k, "kv"), policy.act(v, "kv")
    o = blockwise_attention(q, k, v, chunk=min(512, s), unroll=unroll)
    o = o.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    return h + (policy.act(o, "resid") if policy else o)


def _attn_chunk(h, p, cache_k, cache_v, cfg, cos, sin, seq_lens, valid,
                policy):
    """Attention sub-layer for an N-token chunk.  h (B, N, D).

    The chunk's K/V are scattered into the cache at absolute positions
    ``seq_lens[b] + i`` (padding positions — ``valid[b, i]`` False — are
    routed out of bounds and dropped), then every chunk query attends over
    the cache exactly as the decode path does.
    """
    b, n, d = h.shape
    x = rmsnorm(h, p["ln"], cfg.norm_eps, cfg.gemma_norm)
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, n, cfg.n_heads, cfg.hd)
    k = k.reshape(b, n, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, n, cfg.n_kv_heads, cfg.hd)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    max_seq = cache_k.shape[1]
    pos = seq_lens[:, None] + jnp.arange(n)[None, :]
    pos = jnp.where(valid, pos, max_seq)  # OOB -> dropped by the scatter
    if policy is not None and policy.masked_cache_update:
        # masked rewrite (same invariant as _attn_decode): elementwise on
        # the cache so a sequence-sharded cache updates shard-locally
        hit = ((jnp.arange(max_seq)[None, None, :] == pos[:, :, None])
               & valid[:, :, None])                      # (B, N, S)
        onehot = hit.astype(jnp.float32)
        knew = jnp.einsum("bns,bnkd->bskd", onehot,
                          k.astype(jnp.float32)).astype(cache_k.dtype)
        vnew = jnp.einsum("bns,bnkd->bskd", onehot,
                          v.astype(jnp.float32)).astype(cache_v.dtype)
        any_hit = jnp.any(hit, axis=1)[:, :, None, None]  # (B, S, 1, 1)
        cache_k = jnp.where(any_hit, knew, cache_k)
        cache_v = jnp.where(any_hit, vnew, cache_v)
    else:
        bidx = jnp.arange(b)[:, None]
        cache_k = cache_k.at[bidx, pos].set(k.astype(cache_k.dtype),
                                            mode="drop")
        cache_v = cache_v.at[bidx, pos].set(v.astype(cache_v.dtype),
                                            mode="drop")
    if policy:
        cache_k = policy.act(cache_k, "kv_cache")
        cache_v = policy.act(cache_v, "kv_cache")
    o = chunk_attention(q, cache_k, cache_v, seq_lens)
    o = o.reshape(b, n, cfg.n_heads * cfg.hd) @ p["wo"]
    return h + o, cache_k, cache_v


def _ssm_chunk(h, p, states, cfg, valid):
    """Mamba2 sub-layer for an N-token chunk: step ``ssm_decode`` over the
    chunk positions, freezing conv/SSD states at padding positions so a
    short chunk leaves the request's state exactly where token-by-token
    decode would.  h (B, N, D) *already normed*; returns y (B, N, D)."""

    def tok(st, inp):
        x_t, v_t = inp  # (B, D), (B,)
        y, new_st = ssm_decode(x_t, st, p, cfg)
        merged = {
            k: jnp.where(v_t.reshape((-1,) + (1,) * (new_st[k].ndim - 1)),
                         new_st[k], st[k])
            for k in st
        }
        return merged, y

    if h.shape[1] == 1:  # decode: no scan machinery around a single step
        states, y = tok(states, (h[:, 0], valid[:, 0]))
        return y[:, None], states
    states, ys = jax.lax.scan(
        tok, states, (h.swapaxes(0, 1), valid.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), states


def _ffn(h, kind, p, cfg, policy, mesh, dropless=False):
    """``dropless`` sizes MoE expert capacity so no token is dropped (the
    serving path, like the tGraph interpreter and the megakernel);
    otherwise ``cfg.capacity_factor`` applies (training)."""
    if kind == "none":
        return h
    shape = h.shape
    x = rmsnorm(h, p["ln"], cfg.norm_eps, cfg.gemma_norm)
    if kind == "mlp":
        hh = jnp.einsum("...d,dgf->...gf", x, p["wi"])
        if policy:
            hh = policy.act(hh, "mlp_hidden")
        act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
        y = (act(hh[..., 0, :]) * hh[..., 1, :]) @ p["wo"]
    else:  # moe
        x2d = x.reshape(-1, shape[-1])
        # capacity T·top_k·cf/E >= T: every token fits in every expert
        cf = cfg.n_experts / cfg.top_k if dropless else None
        if mesh is not None and policy is not None:
            y = moe_ffn(x2d, p, cfg, mesh=mesh, ep_axis=policy.ep_axis,
                        dp_axes=policy.dp, fsdp_axes=policy.fsdp_axes,
                        two_d=getattr(policy, "moe_2d", False),
                        capacity_factor=cf)
        else:
            y = moe_ffn(x2d, p, cfg, mesh=None, capacity_factor=cf)
        y = y.reshape(shape)
    return h + (policy.act(y, "resid") if policy else y)


# ---------------------------------------------------------------------------
# Full forward over a sequence (training / prefill).
# ---------------------------------------------------------------------------


def forward(params, cfg, tokens_or_embeds, positions, *, policy=None,
            mesh=None, remat: bool = False, return_hidden: bool = False,
            unroll: bool = False):
    """Sequence forward.  tokens (B, S) int32 or embeds (B, S, D);
    positions (B, S) int32 or (B, S, 3) for M-RoPE."""
    st = block_structure(cfg)
    if cfg.embed_input:
        h = tokens_or_embeds
    else:
        h = params["embed"][tokens_or_embeds]
    if cfg.gemma_norm:  # gemma scales embeddings by sqrt(d)
        h = (h.astype(jnp.float32) * math.sqrt(cfg.d_model)).astype(h.dtype)
    if policy:
        h = policy.act(h, "resid")
    cos = sin = None
    if st["attn_pos"]:
        cos, sin = rope(positions, cfg.hd, cfg.rope_theta, cfg.mrope_sections)

    def block_fn(h, bp):
        ai = si = mi = ei = 0
        for pos in range(st["period"]):
            if cfg.layer_kind(pos) == "attn":
                h = _attn_seq(h, _take(bp["attn"], ai), cfg, cos, sin,
                              policy, unroll=unroll)
                ai += 1
            else:
                p = _take(bp["ssm"], si)
                x = rmsnorm(h, p["ln"], cfg.norm_eps, cfg.gemma_norm)
                y = ssm_prefill(x, p, cfg, policy=policy, unroll=unroll)
                h = h + (policy.act(y, "resid") if policy else y)
                si += 1
            fk = cfg.ffn_kind(pos)
            if fk == "mlp":
                h = _ffn(h, "mlp", _take(bp["mlp"], mi), cfg, policy, mesh)
                mi += 1
            elif fk == "moe":
                h = _ffn(h, "moe", _take(bp["moe"], ei), cfg, policy, mesh)
                ei += 1
        return h, None

    f = block_fn
    if remat:
        f = jax.checkpoint(block_fn,
                           policy=jax.checkpoint_policies.nothing_saveable)
    if unroll:
        nb = block_structure(cfg)["n_blocks"]
        for bi in range(nb):
            h, _ = f(h, _take(params["blocks"], bi))
    else:
        h, _ = jax.lax.scan(f, h, params["blocks"])
    h = rmsnorm(h, params["final_ln"], cfg.norm_eps, cfg.gemma_norm)
    if return_hidden:
        return h
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (h @ head).astype(jnp.float32)
    return policy.act(logits, "logits") if policy else logits


def train_loss(params, cfg, batch, *, policy=None, mesh=None,
               remat: bool = True, unroll: bool = False) -> jax.Array:
    """Mean next-token cross-entropy.  batch: {tokens|embeds, positions,
    labels, loss_mask?}."""
    inp = batch.get("embeds", batch.get("tokens"))
    logits = forward(params, cfg, inp, batch["positions"], policy=policy,
                     mesh=mesh, remat=remat, unroll=unroll)
    labels = batch["labels"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    mask = batch.get("loss_mask")
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)


def prefill_step(params, cfg, tokens_or_embeds, positions, *, policy=None,
                 mesh=None, unroll: bool = False):
    """Inference forward (no grads): logits for every position."""
    return forward(params, cfg, tokens_or_embeds, positions, policy=policy,
                   mesh=mesh, remat=False, unroll=unroll)


# ---------------------------------------------------------------------------
# Decode step (one new token with a KV/state cache) — the paper's home turf.
# ---------------------------------------------------------------------------


def serve_step(params, cfg, cache, tokens_or_embeds, seq_lens, *,
               policy=None, mesh=None, unroll: bool = False):
    """One decode step — exactly ``prefill_chunk`` with a width-1 chunk.

    tokens (B,) int32 or embeds (B, D); seq_lens (B,) int32 = live length
    *before* this token (the new token is written at index seq_lens).
    Returns (logits (B, V) float32, new_cache).
    """
    if cfg.embed_input:
        chunk_in = tokens_or_embeds[:, None, :]
    else:
        chunk_in = tokens_or_embeds[:, None]
    logits, new_cache = prefill_chunk(params, cfg, cache, chunk_in,
                                      seq_lens, policy=policy, mesh=mesh,
                                      unroll=unroll)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Chunked prefill: N new tokens per request through the decode cache.
# ---------------------------------------------------------------------------


def prefill_chunk(params, cfg, cache, tokens_or_embeds, seq_lens,
                  chunk_lens=None, *, policy=None, mesh=None,
                  unroll: bool = False):
    """Consume N prompt tokens per request in ONE step (paper §6.1's
    chunked prefill), writing K/V and SSM states through the exact same
    cache machinery as ``serve_step``.

    tokens (B, N) int32 or embeds (B, N, D); seq_lens (B,) int32 = live
    length *before* the chunk (token i lands at position seq_lens + i);
    chunk_lens (B,) int32 = valid tokens per request (default N).
    Positions >= chunk_lens are padding: they write no cache state and
    their logits are garbage — callers read logits at ``chunk_lens - 1``.
    Returns (logits (B, N, V) float32, new_cache).

    ``serve_step`` IS the N == 1 case of this function, so an engine can
    mix decode slots (chunk_len 1) and prefill slots (chunk_len up to N)
    in one batch without changing any request's sampled stream.
    """
    st = block_structure(cfg)
    if cfg.embed_input:
        h = tokens_or_embeds
    else:
        h = params["embed"][tokens_or_embeds]
    b, n = h.shape[:2]
    if chunk_lens is None:
        chunk_lens = jnp.full((b,), n, jnp.int32)
    valid = jnp.arange(n)[None, :] < chunk_lens[:, None]  # (B, N)
    if cfg.gemma_norm:
        h = (h.astype(jnp.float32) * math.sqrt(cfg.d_model)).astype(h.dtype)
    cos = sin = None
    if st["attn_pos"]:
        pos = seq_lens[:, None] + jnp.arange(n)[None, :]
        if cfg.mrope_sections is not None:
            pos = jnp.stack([pos] * 3, axis=-1)  # text-mode M-RoPE
        cos, sin = rope(pos, cfg.hd, cfg.rope_theta, cfg.mrope_sections)

    def block_fn(h, xs):
        bp, blk_cache = xs
        new_cache = dict(blk_cache)
        ai = si = mi = ei = 0
        for pos_i in range(st["period"]):
            if cfg.layer_kind(pos_i) == "attn":
                h, ck, cv = _attn_chunk(
                    h, _take(bp["attn"], ai), blk_cache["k"][ai],
                    blk_cache["v"][ai], cfg, cos, sin, seq_lens, valid,
                    policy)
                new_cache["k"] = new_cache["k"].at[ai].set(ck)
                new_cache["v"] = new_cache["v"].at[ai].set(cv)
                ai += 1
            else:
                p = _take(bp["ssm"], si)
                x = rmsnorm(h, p["ln"], cfg.norm_eps, cfg.gemma_norm)
                states = {k: blk_cache[k][si]
                          for k in ("conv_x", "conv_b", "conv_c", "ssm")}
                y, new_states = _ssm_chunk(x, p, states, cfg, valid)
                h = h + y
                for k, v in new_states.items():
                    new_cache[k] = new_cache[k].at[si].set(v)
                si += 1
            fk = cfg.ffn_kind(pos_i)
            if fk == "mlp":
                h = _ffn(h, "mlp", _take(bp["mlp"], mi), cfg, policy, mesh)
                mi += 1
            elif fk == "moe":
                h = _ffn(h, "moe", _take(bp["moe"], ei), cfg, policy, mesh,
                         dropless=True)
                ei += 1
        return h, new_cache

    if unroll:
        nb = st["n_blocks"]
        caches = []
        for bi in range(nb):
            h, nc = block_fn(h, (_take(params["blocks"], bi),
                                 _take(cache, bi)))
            caches.append(nc)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *caches)
    else:
        h, new_cache = jax.lax.scan(block_fn, h, (params["blocks"], cache))
    h = rmsnorm(h, params["final_ln"], cfg.norm_eps, cfg.gemma_norm)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (h @ head).astype(jnp.float32)
    return logits, new_cache
