"""Mamba-2 language model (SSD layers, arXiv:2405.21060), as published in
state-spaces/mamba2-2.7b.

The plain reference of the family.  Each layer is a pre-norm residual
block around the Mamba-2 mixer: RMSNorm, one input projection to z, x,
B, C and dt, a causal depthwise convolution (width ``conv_kernel``, with
bias) and SiLU over x, B and C, dt = softplus(dt + dt_bias) and
A = -exp(A_log), the recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

per head, the gated RMSNorm (y * SiLU(z), normed after the gate, over
the whole inner width since there is one group) and the output
projection.  A final RMSNorm and the head tied to the embedding end the
model.  It is written from that description in ``jax.numpy`` with every
contraction at an explicit precision, and imports nothing of the system
under test.

The recurrence runs as a sequential ``lax.scan`` over positions, not in
the chunked SSD form.  Softplus is summed from a series (``_softplus``),
not taken from the backend's ``log1p``.  Departures from a single pass:
the positions go in blocks (``_block``), each layer carrying its conv
inputs and SSM state from one block to the next, so the reference over a
long bucket holds one block's activations at a time; the in-projection
keeps the published order of its outputs as five weights (z, x, B, C,
dt), which is how the program's parameter tree holds them.

Beside the reference: where each weight sits in the parameter tree the
program is handed, how it is drawn, and the operations and bytes of one
decode launch and of one token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.precision import einsum


def dims(sizes: dict) -> dict:
    d, din = sizes["hidden_size"], sizes["intermediate_size"]
    nh, hd = sizes["num_heads"], sizes["head_dim"]
    g, n = sizes["n_groups"], sizes["state_size"]
    if din != sizes["expand"] * d or nh * hd != din:
        raise ValueError(f"inconsistent Mamba-2 widths: {sizes}")
    return {"d": d, "din": din, "nh": nh, "hd": hd, "g": g, "n": n,
            "gn": g * n, "conv": din + 2 * g * n,
            "w": sizes["conv_kernel"], "v": sizes["vocab_size"],
            "layers": sizes["num_hidden_layers"]}


def program_fields(sizes: dict) -> dict:
    """The program's ``ModelConfig`` fields these sizes must match."""
    m = dims(sizes)
    return {"family": "ssm", "d_model": m["d"], "n_heads": 0, "d_ff": 0,
            "vocab": m["v"], "n_layers": m["layers"],
            "ssm_state": m["n"], "ssm_expand": sizes["expand"],
            "ssm_head_dim": m["hd"], "ssm_ngroups": m["g"],
            "ssm_conv": m["w"], "norm_eps": sizes["layer_norm_epsilon"],
            "tie_embeddings": True}


def weight_spec(sizes: dict) -> dict:
    """``path -> (shape, draw)`` for every weight, in the layout of the
    parameter tree the program binds (one SSM layer per block).  The
    conv weights and biases follow the published default init's spread
    (uniform in ±1/√conv_kernel); A_log and dt_bias the published draws."""
    m = dims(sizes)
    d, din, gn, nh = m["d"], m["din"], m["gn"], m["nh"]
    conv = ("normal", m["w"] ** -0.5 / math.sqrt(3))
    spec = {
        "embed": ((m["v"], d), ("normal", 0.02)),
        "final_ln": ((d,), ("one_plus_normal", 0.1)),
    }
    layer = {
        "ln": ((d,), ("one_plus_normal", 0.1)),
        "zproj": ((d, din), ("normal", d ** -0.5)),
        "xproj": ((d, din), ("normal", d ** -0.5)),
        "bproj": ((d, gn), ("normal", d ** -0.5)),
        "cproj": ((d, gn), ("normal", d ** -0.5)),
        "dtproj": ((d, nh), ("normal", d ** -0.5)),
        "conv_wx": ((m["w"], din), conv), "conv_bx": ((din,), conv),
        "conv_wb": ((m["w"], gn), conv), "conv_bb": ((gn,), conv),
        "conv_wc": ((m["w"], gn), conv), "conv_bc": ((gn,), conv),
        "A_log": ((nh,), ("log_uniform_a", 0.0)),
        "D_skip": ((nh,), ("one_plus_normal", 0.1)),
        "dt_bias": ((nh,), ("dt_bias", 0.0)),
        "gnorm": ((din,), ("one_plus_normal", 0.1)),
        "out_proj": ((din, d), ("normal", din ** -0.5)),
    }
    for name, (shape, draw) in layer.items():
        spec["blocks/ssm/" + name] = ((m["layers"], 1) + shape, draw)
    return spec


# ---------------------------------------------------------------- reference

def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _softplus(x):
    """``log(1 + e^x)`` as ``max(x, 0) + 2 atanh(s)``, ``s = u / (2 + u)``,
    ``u = e^-|x|``: the odd series of atanh to ``s^17`` (``s <= 1/3``) is
    exact in f32 and has no branch.  ``jax.nn.softplus`` takes the
    backend's ``log1p``, which on a TPU is off by up to 1e-4 near
    ``|x| = ln 2`` and jumps there."""
    u = jnp.exp(-jnp.abs(x))
    s = u / (2.0 + u)
    s2 = s * s
    acc = 1.0 / 17.0
    for k in range(15, 0, -2):
        acc = acc * s2 + 1.0 / k
    return jnp.maximum(x, 0.0) + 2.0 * s * acc


def _block(length: int) -> int:
    """Positions per block: the largest power of two up to 512 that
    divides ``length``."""
    return math.gcd(length, 512)


def _conv(tail, x, w, b):
    """Causal depthwise conv + SiLU over ``x (B, P, C)`` that follows the
    ``K - 1`` inputs of ``tail (B, K - 1, C)``; returns the output and
    the new tail."""
    k = w.shape[0]
    full = jnp.concatenate([tail, x], axis=1)
    p = x.shape[1]
    y = b + sum(full[:, j:j + p] * w[j] for j in range(k))
    return jax.nn.silu(y), full[:, p:]


def _mixer(h, p, m, eps, mm, carry):
    """One layer over a block ``h (B, P, d)``; ``carry`` is the layer's
    ``(conv tails, SSM state)`` before the block."""
    tails, state = carry
    b, P, _ = h.shape
    x = _rmsnorm(h, p["ln"], eps)
    z = mm("bld,de->ble", x, p["zproj"])
    outs, new_tails = [], []
    for tag, tail in zip("xbc", tails):
        y, t = _conv(tail, mm("bld,de->ble", x, p[tag + "proj"]),
                     p["conv_w" + tag], p["conv_b" + tag])
        outs.append(y)
        new_tails.append(t)
    xs, bs, cs = outs
    dt = _softplus(mm("bld,de->ble", x, p["dtproj"]) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    rep = m["nh"] // m["g"]
    xs = xs.reshape(b, P, m["nh"], m["hd"])
    bs = jnp.repeat(bs.reshape(b, P, m["g"], m["n"]), rep, axis=2)
    cs = jnp.repeat(cs.reshape(b, P, m["g"], m["n"]), rep, axis=2)

    def step(hs, inp):                       # one position, every head
        x_t, dt_t, b_t, c_t = inp            # (B,H,hd) (B,H) (B,H,N) x2
        hs = (hs * jnp.exp(dt_t * a)[..., None, None]
              + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = mm("bhpn,bhn->bhp", hs, c_t) + p["D_skip"][:, None] * x_t
        return hs, y_t

    state, ys = jax.lax.scan(
        step, state, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                      bs.swapaxes(0, 1), cs.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1).reshape(b, P, m["din"])
    y = _rmsnorm(y * jax.nn.silu(z), p["gnorm"], eps)
    return h + mm("ble,ed->bld", y, p["out_proj"]), (tuple(new_tails), state)


def hidden(w: dict, sizes: dict, tokens, precision):
    """Final-normed hidden states ``(B, L, d)`` of a teacher-forced pass
    over ``tokens (B, L)`` from the zero state."""
    m = dims(sizes)
    eps = sizes["layer_norm_epsilon"]
    b, L = tokens.shape
    P = _block(L)
    mm = lambda eq, a, c: einsum(eq, a, c, precision)
    layers = [jax.tree.map(lambda a: a[i, 0], w["blocks"]["ssm"])
              for i in range(m["layers"])]
    zeros = lambda c: jnp.zeros((b, m["w"] - 1, c), jnp.float32)
    carry0 = [((zeros(m["din"]), zeros(m["gn"]), zeros(m["gn"])),
               jnp.zeros((b, m["nh"], m["hd"], m["n"]), jnp.float32))
              for _ in layers]

    def block(carry, tok):                   # tok (B, P)
        h = w["embed"][tok]
        new = []
        for p, c in zip(layers, carry):
            h, c = _mixer(h, p, m, eps, mm, c)
            new.append(c)
        return new, _rmsnorm(h, w["final_ln"], eps)

    _, hs = jax.lax.scan(block, carry0,
                         tokens.reshape(b, L // P, P).swapaxes(0, 1))
    return hs.swapaxes(0, 1).reshape(b, L, m["d"])


def head(w: dict) -> jax.Array:
    return w["embed"].T


# ------------------------------------------------------- operations, bytes

WEIGHT_BYTES = 2     # weights are bf16 values: what a deployment reads
STATE_BYTES = 4      # the SSM and conv states and the logits are f32

#: operations per SSM state element and token: the decay multiply, the
#: dt x B^T multiply and the add of the update, the multiply-add of C^T h
STATE_FLOPS = 5


def matmul_params(sizes: dict) -> int:
    """Weights a token multiplies with: every layer's projections and
    the tied head."""
    m = dims(sizes)
    layer = (m["d"] * (2 * m["din"] + 2 * m["gn"] + m["nh"])
             + m["din"] * m["d"])
    return m["layers"] * layer + m["d"] * m["v"]


def conv_params(sizes: dict) -> int:
    m = dims(sizes)
    return m["layers"] * m["w"] * m["conv"]


def vector_params(sizes: dict) -> int:
    """Every other weight: the norms, conv weights and biases, A_log, D
    and dt_bias."""
    m = dims(sizes)
    layer = m["d"] + m["conv"] + 3 * m["nh"] + m["din"]
    return m["layers"] * layer + conv_params(sizes) + m["d"]


def state_words(sizes: dict) -> int:
    """State words one slot holds: each layer's SSM state and its conv
    window of ``conv_kernel`` inputs."""
    m = dims(sizes)
    return m["layers"] * (m["nh"] * m["hd"] * m["n"] + m["w"] * m["conv"])


def token_flops(sizes: dict, position: int) -> int:
    """Model operations of one token, the same at every ``position``: two
    per weight multiply-add (projections, conv, head), plus the state
    update and readout of every head."""
    m = dims(sizes)
    state = m["layers"] * m["nh"] * m["hd"] * m["n"] * STATE_FLOPS
    return 2 * (matmul_params(sizes) + conv_params(sizes)) + state


def decode_launch(sizes: dict, positions) -> tuple:
    """``(flops, bytes)`` the algorithm needs for one decode launch that
    feeds one token to each active slot: every weight read once at 2 B
    (the tied embedding as the head), the embedding rows gathered, each
    slot's SSM and conv state read and written at 4 B, the logits
    written at 4 B."""
    m = dims(sizes)
    b = len(positions)
    flops = sum(token_flops(sizes, int(p)) for p in positions)
    weights = (matmul_params(sizes) + vector_params(sizes)) * WEIGHT_BYTES
    embed = b * m["d"] * WEIGHT_BYTES
    state = 2 * b * state_words(sizes) * STATE_BYTES
    logits = b * m["v"] * STATE_BYTES
    return flops, weights + embed + state + logits
