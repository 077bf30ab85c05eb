"""Mamba2 (SSD — state-space duality) mixer: chunked prefill + recurrent
decode.  Follows arXiv:2405.21060 §6: the sequence is split into chunks;
within a chunk the SSD form is a masked-decay attention-like matmul, across
chunks a recurrent state (nh, hd, N) is propagated.

Parameter layout per layer (see ``models.lm.init_params``).  The input
projection is stored as *separate* tensors (z, x, B, C, dt) rather than one
packed matrix so that every piece shards cleanly on the tensor-parallel
axis (the packed layout's split boundaries are not shard-aligned):

  zproj/xproj (D, d_inner)   bproj/cproj (D, ng*N)   dtproj (D, nh)
  conv_wx (W, d_inner), conv_bx (d_inner,)  — likewise wb/bb, wc/bc
  A_log (nh,)   D_skip (nh,)   dt_bias (nh,)
  gnorm (d_inner,)   out_proj (d_inner, D)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .layers import rmsnorm, softplus

__all__ = ["ssm_prefill", "ssm_decode", "causal_conv", "conv_decode"]


def causal_conv(x: jax.Array, conv_w: jax.Array, conv_b: jax.Array
                ) -> jax.Array:
    """Causal depthwise conv over (B, S, C) with width W (shift-and-add)."""
    w = conv_w.shape[0]
    xf = x.astype(jnp.float32)
    out = jnp.zeros_like(xf)
    for i in range(w):
        shift = w - 1 - i  # tap i sees x[t - shift]
        shifted = jnp.pad(xf, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * conv_w[i].astype(jnp.float32)
    out = out + conv_b.astype(jnp.float32)
    return jax.nn.silu(out).astype(x.dtype)


def conv_decode(x_t: jax.Array, conv_state: jax.Array, conv_w: jax.Array,
                conv_b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single-token causal conv update.  x_t (B, C); conv_state (B, W, C)."""
    new_state = jnp.concatenate([conv_state[:, 1:], x_t[:, None]], axis=1)
    y = jnp.einsum("bwc,wc->bc", new_state.astype(jnp.float32),
                   conv_w.astype(jnp.float32)) + conv_b.astype(jnp.float32)
    return jax.nn.silu(y).astype(x_t.dtype), new_state.astype(conv_state.dtype)


def _ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bmat: jax.Array,
              cmat: jax.Array, chunk: int, unroll: bool = False
              ) -> jax.Array:
    """Chunked SSD core.

    x    (B, S, nh, hd)      dt (B, S, nh)  — softplus-ed, > 0
    a    (nh,)               — negative decay rates (-exp(A_log))
    bmat (B, S, ng, N)       cmat (B, S, ng, N)
    Returns y (B, S, nh, hd).
    """
    b, s, nh, hd = x.shape
    ng, n = bmat.shape[2], bmat.shape[3]
    hpg = nh // ng
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    nc = sp // chunk

    xc = x.reshape(b, nc, chunk, nh, hd).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, nh).astype(jnp.float32)
    bc = bmat.reshape(b, nc, chunk, ng, n).astype(jnp.float32)
    cc = cmat.reshape(b, nc, chunk, ng, n).astype(jnp.float32)

    da = dtc * a  # (b, nc, cs, nh), negative
    cums = jnp.cumsum(da, axis=2)

    # ---- intra-chunk (masked-decay attention form) ----
    gmat = jnp.einsum("bzign,bzjgn->bzgij", cc, bc)  # (b,nc,ng,cs,cs)
    diff = cums[:, :, :, None, :] - cums[:, :, None, :, :]  # (b,nc,i,j,nh)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    ldec = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    gh = jnp.repeat(gmat, hpg, axis=2)  # (b,nc,nh,cs,cs)
    # (b,nc,nh,i,j): scores * decay * dt_j
    m = (gh * ldec.transpose(0, 1, 4, 2, 3)
         * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :])
    y_intra = jnp.einsum("bznij,bzjnp->bzinp", m, xc)

    # ---- chunk states ----
    decay_last = jnp.exp(cums[:, :, -1:, :] - cums)  # (b,nc,cs,nh)
    bh = jnp.repeat(bc, hpg, axis=3).reshape(b, nc, chunk, nh, n)
    states = jnp.einsum("bzjn,bzjnp,bzjnq->bznpq",
                        dtc * decay_last, xc, bh)  # (b,nc,nh,hd,N)

    # ---- inter-chunk recurrence over running state ----
    chunk_decay = jnp.exp(cums[:, :, -1, :])  # (b,nc,nh)

    def step(running, inp):
        st, dec = inp  # (b,nh,hd,N), (b,nh)
        out = running  # state BEFORE this chunk
        new = running * dec[..., None, None] + st
        return new, out

    if unroll:
        running = jnp.zeros((b, nh, hd, n), jnp.float32)
        prevs = []
        for ci in range(nc):
            running, out = step(running,
                                (states[:, ci], chunk_decay[:, ci]))
            prevs.append(out)
        prev_states = jnp.stack(prevs, axis=1)
    else:
        _, prev_states = jax.lax.scan(
            step,
            jnp.zeros((b, nh, hd, n), jnp.float32),
            (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)),
        )
        prev_states = prev_states.swapaxes(0, 1)  # (b,nc,nh,hd,N)

    ch = jnp.repeat(cc, hpg, axis=3).reshape(b, nc, chunk, nh, n)
    y_inter = jnp.einsum("bzinq,bznpq->bzinp",
                         ch * jnp.exp(cums)[..., None], prev_states)

    y = (y_intra + y_inter).reshape(b, sp, nh, hd)[:, :s]
    return y


def ssm_prefill(x_seq: jax.Array, p: Dict[str, jax.Array], cfg,
                chunk: int = 256, policy=None,
                unroll: bool = False) -> jax.Array:
    """Full Mamba2 mixer over a sequence.  x_seq (B, S, D) -> (B, S, D)."""
    b, s, d = x_seq.shape
    nh, ng, n = cfg.ssm_nheads, cfg.ssm_ngroups, cfg.ssm_state
    hd = cfg.ssm_head_dim
    z = x_seq @ p["zproj"]
    xx = causal_conv(x_seq @ p["xproj"], p["conv_wx"], p["conv_bx"])
    bb = causal_conv(x_seq @ p["bproj"], p["conv_wb"], p["conv_bb"])
    cc = causal_conv(x_seq @ p["cproj"], p["conv_wc"], p["conv_bc"])
    dt_raw = x_seq @ p["dtproj"]
    if policy:
        z, xx = policy.act(z, "ssm_inner"), policy.act(xx, "ssm_inner")
    xs = xx.reshape(b, s, nh, hd)
    bmat = bb.reshape(b, s, ng, n)
    cmat = cc.reshape(b, s, ng, n)
    dt = softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = _ssd_scan(xs, dt, a, bmat, cmat, chunk, unroll=unroll)
    y = y + p["D_skip"].astype(jnp.float32)[None, None, :, None] \
        * xs.astype(jnp.float32)
    y = y.reshape(b, s, cfg.d_inner)
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x_seq.dtype),
                p["gnorm"], cfg.norm_eps)
    return y @ p["out_proj"]


def ssm_decode(x_t: jax.Array, states: Dict[str, jax.Array],
               p: Dict[str, jax.Array], cfg
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token Mamba2 step.

    x_t (B, D); states: conv_x (B, W, d_inner), conv_b/conv_c (B, W, ng*N),
    ssm (B, nh, hd, N).  Returns (y (B, D), new_states).
    """
    bsz, d = x_t.shape
    nh, ng, n = cfg.ssm_nheads, cfg.ssm_ngroups, cfg.ssm_state
    hd = cfg.ssm_head_dim
    z = x_t @ p["zproj"]
    xx, conv_x = conv_decode(x_t @ p["xproj"], states["conv_x"],
                             p["conv_wx"], p["conv_bx"])
    bb, conv_b = conv_decode(x_t @ p["bproj"], states["conv_b"],
                             p["conv_wb"], p["conv_bb"])
    cc, conv_c = conv_decode(x_t @ p["cproj"], states["conv_c"],
                             p["conv_wc"], p["conv_bc"])
    dt_raw = x_t @ p["dtproj"]
    xs = xx.reshape(bsz, nh, hd).astype(jnp.float32)
    bmat = bb.reshape(bsz, ng, n).astype(jnp.float32)
    cmat = cc.reshape(bsz, ng, n).astype(jnp.float32)
    dt = softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B, nh)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    da = jnp.exp(dt * a)  # (B, nh)
    hpg = nh // ng
    bh = jnp.repeat(bmat, hpg, axis=1)  # (B, nh, N)
    ch = jnp.repeat(cmat, hpg, axis=1)
    new_state = (states["ssm"] * da[..., None, None]
                 + (dt[..., None] * xs)[..., None] * bh[:, :, None, :])
    y = jnp.einsum("bnpq,bnq->bnp", new_state, ch)
    y = y + p["D_skip"].astype(jnp.float32)[None, :, None] * xs
    y = y.reshape(bsz, cfg.d_inner)
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x_t.dtype),
                p["gnorm"], cfg.norm_eps)
    new_states = {"conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c,
                  "ssm": new_state.astype(states["ssm"].dtype)}
    return y @ p["out_proj"], new_states
